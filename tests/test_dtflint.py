"""dtflint static-analysis suite tests (ISSUE 10): every analyzer caught
red-handed on a fixture reproducing its historical bug class, proven
quiet on the corresponding clean shape, plus the baseline round-trip,
the --json schema, the runtime lock checker, and the invariant that the
LIVE tree is finding-free modulo the reviewed baseline."""

import json
import textwrap
import threading

import pytest

from distributed_tensorflow_tpu.tools import dtflint
from distributed_tensorflow_tpu.tools.dtflint import (RepoIndex,
                                                      run_analyzers)
from distributed_tensorflow_tpu.tools.dtflint.__main__ import main as cli
from distributed_tensorflow_tpu.tools.dtflint.core import (BaselineError,
                                                           parse_baseline)


def lint(tmp_path, files, analyzers=None):
    """Write fixture files and run the analyzers over them."""
    for name, text in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(text))
    index = RepoIndex.load(str(tmp_path))
    assert not index.errors, index.errors
    return run_analyzers(index, analyzers)


def rules(findings, path=None):
    return {f.rule for f in findings
            if path is None or f.path == path}


# ---------------------------------------------------------- jit-hygiene


def test_jit_per_call_rebuild_flagged(tmp_path):
    """The PR-7 bug class verbatim: a generate() that builds its jit
    program inside every call (BENCH_r04's 0.14x)."""
    findings = lint(tmp_path, {"gen.py": """
        import jax

        def generate_speculative(params, toks):
            step = jax.jit(lambda p, t: (p, t))
            return step(params, toks)
    """})
    assert "jit-per-call" in rules(findings)


def test_jit_per_call_memoized_and_builder_shapes_pass(tmp_path):
    findings = lint(tmp_path, {"ok.py": """
        import functools

        import jax

        @functools.lru_cache(maxsize=None)
        def _program(k):
            return jax.jit(lambda x: x * k)

        def build_train_step(loss_fn):
            return jax.jit(loss_fn)

        class Engine:
            def __init__(self):
                self._step = self._build_step()
                self._cache = {}

            def _build_step(self):
                return jax.jit(lambda x: x)

            def _prefill_fn(self, n):
                fn = self._cache.get(n)
                if fn is not None:
                    return fn
                fn = jax.jit(lambda x: x + n)
                self._cache[n] = fn
                return fn
    """})
    assert "jit-per-call" not in rules(findings)


def test_chunk_prefill_builder_memo_shape_pinned(tmp_path):
    """ISSUE 11 fixture: the serving engine's chunk-prefill program
    builder — constructed lazily but memoized through the blessed
    dict-memo shape, and CALLED FROM step() — must pass; the same
    builder without the memo is the r4 retrace class riding back in
    through this PR and must be flagged.  Both carry the engine's
    ``donate_argnames``: a keyword beside the jitted function changes
    neither verdict."""
    findings = lint(tmp_path, {"engine_like.py": """
        import jax

        class Engine:
            def __init__(self):
                self._chunk_fns = {}

            def _chunk_prefill_fn(self, chunk):
                fn = self._chunk_fns.get(chunk)
                if fn is not None:
                    return fn
                fn = jax.jit(lambda tree, pools: (tree, pools, chunk),
                             donate_argnames=("pools",))
                self._chunk_fns[chunk] = fn
                return fn

            def step(self, tree, toks):
                return self._chunk_prefill_fn(4)(tree, toks)
    """})
    assert "jit-per-call" not in rules(findings)

    findings = lint(tmp_path / "bad", {"engine_like.py": """
        import jax

        class Engine:
            def _chunk_prefill_fn(self, chunk):
                return jax.jit(lambda tree, pools: (tree, pools, chunk),
                               donate_argnames=("pools",))

            def step(self, tree, toks):
                return self._chunk_prefill_fn(4)(tree, toks)
    """})
    assert "jit-per-call" in rules(findings)


def test_jit_in_loop_flagged(tmp_path):
    findings = lint(tmp_path, {"loopy.py": """
        import jax

        def run(xs):
            out = []
            for x in xs:
                f = jax.jit(lambda v: v + 1)
                out.append(f(x))
            return out
    """})
    assert "jit-in-loop" in rules(findings)


def test_jit_closure_capture_flagged_and_arg_passing_passes(tmp_path):
    findings = lint(tmp_path, {"cap.py": """
        import jax

        def captured(params):
            def step(x):
                return params["w"] @ x
            return jax.jit(step)

        def passed():
            def step(params, x):
                return params["w"] @ x
            return jax.jit(step)
    """})
    caps = [f for f in findings if f.rule == "jit-closure-capture"]
    assert len(caps) == 1
    assert "captured" in caps[0].anchor


def test_host_sync_in_loop_flagged_only_inside_loops(tmp_path):
    findings = lint(tmp_path, {"sync.py": """
        import jax
        import numpy as np

        def decode_rounds(tokens):
            out = []
            while tokens:
                out.append(np.asarray(tokens.pop()))
            return out

        def single_sync(result):
            return np.asarray(result)
    """})
    hits = [f for f in findings if f.rule == "host-sync-in-loop"]
    assert len(hits) == 1
    assert hits[0].anchor == "decode_rounds"


def test_host_sync_ignored_without_jax(tmp_path):
    findings = lint(tmp_path, {"hostonly.py": """
        import numpy as np

        def crunch(rows):
            return [np.asarray(r) for r in rows]
    """})
    assert "host-sync-in-loop" not in rules(findings)


# ------------------------------------------------------ lock-discipline


def test_lock_order_cycle_flagged(tmp_path):
    findings = lint(tmp_path, {"locks.py": """
        import threading

        class AB:
            def __init__(self):
                self._l1 = threading.Lock()
                self._l2 = threading.Lock()

            def forward(self):
                with self._l1:
                    with self._l2:
                        pass

            def backward(self):
                with self._l2:
                    with self._l1:
                        pass
    """})
    assert "lock-order-cycle" in rules(findings)


def test_consistent_lock_order_passes(tmp_path):
    findings = lint(tmp_path, {"locks.py": """
        import threading

        class AB:
            def __init__(self):
                self._l1 = threading.Lock()
                self._l2 = threading.Lock()

            def one(self):
                with self._l1:
                    with self._l2:
                        pass

            def two(self):
                with self._l1:
                    with self._l2:
                        pass
    """})
    assert "lock-order-cycle" not in rules(findings)


def test_cross_class_lock_cycle_resolved_through_attr_types(tmp_path):
    """The serving shape: scheduler pops under its lock while consulting
    the pool; a pool method calling back into the scheduler under ITS
    lock closes the AB/BA cycle across two classes."""
    findings = lint(tmp_path, {"serve_like.py": """
        import threading

        class Sched:
            def __init__(self):
                self._lock = threading.Lock()
                self.pool = Pool(self)

            def pop(self):
                with self._lock:
                    self.pool.poke()

        class Pool:
            def __init__(self, sched: "Sched"):
                self._lock = threading.Lock()
                self.sched = sched

            def poke(self):
                with self._lock:
                    pass

            def kick(self):
                with self._lock:
                    self.sched.pop()
    """})
    assert "lock-order-cycle" in rules(findings)


def test_blocking_calls_under_lock_flagged(tmp_path):
    findings = lint(tmp_path, {"blocky.py": """
        import threading
        import time

        class B:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition()
                self._evt = threading.Event()

            def sleepy(self):
                with self._lock:
                    time.sleep(1.0)

            def dumpy(self, path):
                with self._lock:
                    with open(path, "w") as fh:
                        fh.write("x")

            def waity(self):
                with self._lock:
                    self._evt.wait(1.0)

            def fine(self):
                with self._cond:
                    self._cond.wait(timeout=0.5)
    """})
    hits = [f for f in findings if f.rule == "lock-blocking-call"]
    anchors = {f.anchor for f in hits}
    assert {"B.sleepy", "B.dumpy", "B.waity"} <= anchors
    # Condition.wait on the HELD condition releases the lock — exempt.
    assert "B.fine" not in anchors


def test_callback_under_lock_flagged(tmp_path):
    findings = lint(tmp_path, {"cb.py": """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def pop(self, admissible):
                with self._lock:
                    if admissible(1):
                        return 1
                    return None
    """})
    assert "lock-callback" in rules(findings)


def test_unsynchronized_attribute_flagged_and_locked_writes_pass(
        tmp_path):
    findings = lint(tmp_path, {"threads.py": """
        import threading

        class Racy:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def start(self):
                def loop():
                    self.count = self.count + 1
                threading.Thread(target=loop).start()

            def bump(self):
                self.count = self.count + 2

        class Clean:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def start(self):
                def loop():
                    with self._lock:
                        self.count = self.count + 1
                threading.Thread(target=loop).start()

            def bump(self):
                with self._lock:
                    self.count = self.count + 2
    """})
    hits = [f for f in findings if f.rule == "unsynchronized-attribute"]
    assert len(hits) == 1
    assert hits[0].anchor == "Racy.count"


# --------------------------------------------------- telemetry-contract


def test_emit_missing_required_field_flagged(tmp_path):
    """An emit() that cannot supply a REQUIRED_STEP_FIELDS field — the
    drift summarize_run --check only catches after a live run."""
    findings = lint(tmp_path, {
        "summarize_run.py": """
            REQUIRED_STEP_FIELDS = ("step", "wall_time", "loss", "mfu")

            def consume(records):
                return [r for r in records
                        if record_kind(r) == "train_step"]
        """,
        "producer.py": """
            def log_step(telemetry, loss):
                telemetry.emit("train_step", step=1, loss=loss)
        """})
    hits = [f for f in findings if f.rule == "telemetry-missing-field"]
    assert len(hits) == 1
    assert "mfu" in hits[0].message
    assert "wall_time" not in hits[0].message  # bus-injected, implicit


def test_emit_with_resolvable_dynamic_fields_passes(tmp_path):
    findings = lint(tmp_path, {
        "summarize_run.py": """
            REQUIRED_STEP_FIELDS = ("step", "wall_time", "loss", "mfu")

            def consume(records):
                return [r for r in records
                        if record_kind(r) == "train_step"]
        """,
        "producer.py": """
            def log_step(telemetry, loss, rate):
                extra = dict(mfu=rate * 0.5)
                telemetry.emit("train_step", step=1, loss=loss, **extra)
        """})
    assert "telemetry-missing-field" not in rules(findings)


def test_emit_fields_resolved_through_producer_function(tmp_path):
    """The slo shape: emit(**entry) where entry comes from a producer
    method building dict literals — resolved one level deep."""
    findings = lint(tmp_path, {
        "summarize_run.py": """
            REQUIRED_SLO_FIELDS = ("tenant", "burning")

            def consume(records):
                return [r for r in records if record_kind(r) == "slo"]
        """,
        "producer.py": """
            class Slo:
                def evaluate(self):
                    out = []
                    for name in ("a", "b"):
                        entry = {"tenant": name, "burning": False}
                        out.append(entry)
                    return out

            def tick(telemetry, slo):
                for entry in slo.evaluate():
                    telemetry.emit("slo", step=0, **entry)
        """})
    assert "telemetry-missing-field" not in rules(findings)


def test_kind_drift_both_directions_flagged(tmp_path):
    findings = lint(tmp_path, {
        "summarize_run.py": """
            def consume(records):
                evals = [r for r in records
                         if record_kind(r) == "evaluation"]
                return evals
        """,
        "producer.py": """
            def log(telemetry):
                telemetry.emit("eval", step=1, accuracy=0.9)
        """})
    assert "telemetry-unknown-kind" in rules(findings)      # "evaluation"
    assert "telemetry-unconsumed-kind" in rules(findings)   # "eval"


def test_statput_contract_unpublished_read_flagged(tmp_path):
    findings = lint(tmp_path, {
        "loop.py": """
            def publish(stat_publish_fn, step, loss):
                stat_payload = dict(step=step, loss=loss)
                stat_publish_fn(stat_payload)
        """,
        "watch_run.py": """
            def fetch(stat):
                return {"step": stat.get("step"),
                        "grad_norm": stat.get("grad_norm")}
        """})
    hits = [f for f in findings if f.rule == "stat-field-unpublished"]
    assert len(hits) == 1 and hits[0].anchor == "grad_norm"


# ------------------------------------------------- protocol-conformance


PROTO_CC = """
    void Handle(int fd) {
      if (cmd == "PING") {
        WriteLine(fd, "OK");
      } else if (cmd == "FETCH") {
        WriteLine(fd, "OK " + value);
      } else {
        WriteLine(fd, "ERR unknown command");
      }
    }
"""


def test_client_command_absent_from_server_flagged(tmp_path):
    findings = lint(tmp_path, {
        "coord.cc": PROTO_CC,
        "client.py": """
            class Client:
                def ping(self):
                    resp = self._request("PING 1")
                    if resp != "OK":
                        raise RuntimeError(resp)

                def fetch(self):
                    resp = self._request("FETCH key")
                    return resp.split()[1]

                def evict(self, task):
                    return self._request(f"EVICT {task}")
        """})
    hits = [f for f in findings if f.rule == "protocol-unknown-command"]
    assert len(hits) == 1 and "EVICT" in hits[0].message
    assert "protocol-unhandled-command" not in rules(findings)
    assert "protocol-reply-mismatch" not in rules(findings)


def test_server_command_without_client_flagged(tmp_path):
    findings = lint(tmp_path, {
        "coord.cc": PROTO_CC,
        "client.py": """
            class Client:
                def ping(self):
                    resp = self._request("PING 1")
                    if resp != "OK":
                        raise RuntimeError(resp)
        """})
    hits = [f for f in findings
            if f.rule == "protocol-unhandled-command"]
    assert len(hits) == 1 and hits[0].anchor == "FETCH"


def test_reply_arity_mismatch_flagged(tmp_path):
    findings = lint(tmp_path, {
        "coord.cc": PROTO_CC,
        "client.py": """
            class Client:
                def ping_payload(self):
                    resp = self._request("PING 1")
                    return resp.split()[1]

                def fetch(self):
                    resp = self._request("FETCH key")
                    return resp.split()[1]
        """})
    hits = [f for f in findings if f.rule == "protocol-reply-mismatch"]
    assert len(hits) == 1 and "PING" in hits[0].message


def test_live_protocol_is_fully_covered():
    """Every coord.cc command has a client sender and vice versa — the
    19-command contract (REPLJOIN/REPLSTREAM joined with coordinator HA,
    SHARDINFO with the sharded plane), checked against the REAL tree —
    and the NOTPRIMARY redirect is emitted server-side AND handled
    client-side (producer+consumer, zero baseline suppressions)."""
    index = RepoIndex.load(dtflint.DEFAULT_ROOT)
    findings = run_analyzers(index, ["protocol-conformance"])
    assert findings == [], [f.render() for f in findings]
    from distributed_tensorflow_tpu.tools.dtflint import (
        protocol_conformance as pc)
    cc = next(text for rel, text in index.cc.items()
              if rel.endswith("coordination/coord.cc"))
    commands = pc.server_commands(cc)
    assert len(commands) == 19
    assert "SHARDINFO" in commands
    assert "REPLJOIN" in commands and "REPLSTREAM" in commands
    assert pc._NOTPRIMARY_EMIT_RE.search(cc)


def test_notprimary_emitted_without_handler_flagged(tmp_path):
    findings = lint(tmp_path, {
        "coord.cc": """
            void Handle(int fd) {
              if (!is_primary) {
                Reply(fd, "NOTPRIMARY " + leader);
                return;
              }
              if (cmd == "PING") {
                Reply(fd, "OK");
              } else {
                Reply(fd, "ERR unknown command");
              }
            }
        """,
        "client.py": """
            class Client:
                def ping(self):
                    resp = self._request("PING 1")
                    if resp != "OK":
                        raise RuntimeError(resp)
        """})
    hits = [f for f in findings
            if f.rule == "protocol-notprimary-unhandled"]
    assert len(hits) == 1 and hits[0].path == "coord.cc"


def test_notprimary_handled_client_side_passes(tmp_path):
    findings = lint(tmp_path, {
        "coord.cc": """
            void Handle(int fd) {
              if (!is_primary) {
                Reply(fd, "NOTPRIMARY " + leader);
                return;
              }
              if (cmd == "PING") {
                Reply(fd, "OK");
              } else {
                Reply(fd, "ERR unknown command");
              }
            }
        """,
        "client.py": """
            class Client:
                def ping(self):
                    resp = self._request("PING 1")
                    if resp.startswith("NOTPRIMARY"):
                        self._failover(resp.split()[1])
                    elif resp != "OK":
                        raise RuntimeError(resp)
        """})
    assert "protocol-notprimary-unhandled" not in rules(findings)


def test_notprimary_scan_ignores_the_analyzer_package(tmp_path):
    """The handler scan must skip tools/dtflint itself: the analyzer's
    own source contains the literal (its emit regex, fixtures), and
    matching it would satisfy the scan forever — masking exactly the
    regression (client failover handling deleted) the rule exists to
    catch."""
    findings = lint(tmp_path, {
        "coord.cc": """
            void Handle(int fd) {
              if (!is_primary) {
                Reply(fd, "NOTPRIMARY " + leader);
                return;
              }
              if (cmd == "PING") {
                Reply(fd, "OK");
              } else {
                Reply(fd, "ERR unknown command");
              }
            }
        """,
        "tools/dtflint/protocol_conformance.py": """
            import re
            _RE = re.compile(r'Reply\\(fd,\\s*"NOTPRIMARY')
        """,
        "client.py": """
            class Client:
                def ping(self):
                    resp = self._request("PING 1")
                    if resp != "OK":
                        raise RuntimeError(resp)
        """}, analyzers=["protocol-conformance"])
    hits = [f for f in findings
            if f.rule == "protocol-notprimary-unhandled"]
    assert len(hits) == 1 and hits[0].path == "coord.cc"


def test_notprimary_handler_without_emitter_flagged(tmp_path):
    findings = lint(tmp_path, {
        "coord.cc": PROTO_CC,
        "client.py": """
            class Client:
                def ping(self):
                    resp = self._request("PING 1")
                    if resp.startswith("NOTPRIMARY"):
                        self._failover(resp.split()[1])
                    elif resp != "OK":
                        raise RuntimeError(resp)

                def fetch(self):
                    resp = self._request("FETCH key")
                    return resp.split()[1]
        """})
    hits = [f for f in findings
            if f.rule == "protocol-notprimary-unhandled"]
    assert len(hits) == 1 and hits[0].path == "client.py"
    assert "dead failover" in hits[0].message


# ------------------------------------------- baseline + CLI round trips


def test_baseline_round_trip_and_stale_warning(tmp_path, capsys):
    files = {"gen.py": """
        import jax

        def generate(params, toks):
            step = jax.jit(lambda p, t: (p, t))
            return step(params, toks)
    """}
    for name, text in files.items():
        (tmp_path / name).write_text(textwrap.dedent(text))
    baseline = tmp_path / "baseline.txt"

    # 1) no baseline: --check fails and names the finding
    rc = cli(["--root", str(tmp_path), "--baseline", str(baseline),
              "--check"])
    out = capsys.readouterr().out
    assert rc == 1 and "jit-per-call" in out

    # 2) baseline the finding (reason mandatory): --check passes
    index = RepoIndex.load(str(tmp_path))
    (finding,) = run_analyzers(index, ["jit-hygiene"])
    baseline.write_text(f"{finding.key}  # fixture: known and accepted\n")
    rc = cli(["--root", str(tmp_path), "--baseline", str(baseline),
              "--check"])
    capsys.readouterr()
    assert rc == 0

    # 3) fix the code: the stale entry warns (stderr) but does not fail
    (tmp_path / "gen.py").write_text(textwrap.dedent("""
        import functools

        import jax

        @functools.lru_cache(maxsize=None)
        def _generate_program(k):
            return jax.jit(lambda p, t: (p, t))
    """))
    rc = cli(["--root", str(tmp_path), "--baseline", str(baseline),
              "--check"])
    captured = capsys.readouterr()
    assert rc == 0
    assert "stale baseline entry" in captured.err


def test_baseline_requires_a_reason():
    with pytest.raises(BaselineError, match="reason"):
        parse_baseline("jit-per-call gen.py generate\n")
    parsed = parse_baseline(
        "jit-per-call gen.py generate  # reviewed: fixture\n")
    assert parsed == {"jit-per-call gen.py generate": "reviewed: fixture"}


def test_json_report_schema(tmp_path, capsys):
    (tmp_path / "gen.py").write_text(textwrap.dedent("""
        import jax

        def generate(params):
            return jax.jit(lambda p: p)(params)
    """))
    rc = cli(["--root", str(tmp_path), "--no-baseline", "--json", "-"])
    assert rc == 0  # no --check: reporting never fails the run
    captured = capsys.readouterr()
    # `--json -` stdout is PURE JSON (human lines go to stderr) — the
    # same stdout-purity contract as the watchers' --once --json.
    payload = json.loads(captured.out)
    assert "[dtflint]" in captured.err
    assert payload["schema_version"] == 1
    assert set(payload["counts"]) == {"new", "baselined",
                                      "stale_baseline", "files_scanned"}
    assert payload["counts"]["new"] == len(payload["findings"]) == 1
    f = payload["findings"][0]
    assert {"analyzer", "rule", "path", "line", "anchor", "key",
            "message", "baselined"} <= set(f)
    assert f["rule"] == "jit-per-call" and f["baselined"] is False


def test_live_tree_is_finding_free_modulo_baseline():
    """The acceptance invariant: dtflint --check exits 0 on the tree.
    Every new finding must be either fixed or explicitly baselined with
    a reviewed reason — this test is what keeps that loop honest."""
    index = RepoIndex.load(dtflint.DEFAULT_ROOT)
    assert not index.errors, index.errors
    findings = run_analyzers(index)
    baseline = dtflint.load_baseline(dtflint.DEFAULT_BASELINE)
    new, suppressed, stale = dtflint.apply_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"
    # The baseline is a reviewed artifact, not a dumping ground (two
    # suppressed findings share the make_stateful_eval_fn.evaluate key —
    # keys are line-number-free by design).
    assert len(suppressed) == 9
    assert len(baseline) == 8


# ------------------------------------------------------ runtime lockcheck


@pytest.fixture
def lockcheck():
    from distributed_tensorflow_tpu.utils import lockcheck as lc
    installed = lc.install(force=True)
    lc.reset()
    try:
        yield lc
    finally:
        lc.reset()
        if installed:
            lc.uninstall()


def test_lockcheck_records_inversion(lockcheck):
    a = threading.Lock()
    b = threading.Lock()
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    assert len(lockcheck.violations()) == 1
    assert "inversion" in lockcheck.violations()[0]
    with pytest.raises(AssertionError, match="inversion"):
        lockcheck.assert_clean()


def test_lockcheck_consistent_order_and_reentrancy_clean(lockcheck):
    a = threading.Lock()
    b = threading.Lock()
    for _ in range(3):
        with a:
            with b:
                pass
    r = threading.RLock()
    with r:
        with r:  # reentrant: no self-edge
            pass
    assert lockcheck.violations() == []
    lockcheck.assert_clean()


def test_lockcheck_condition_wait_releases(lockcheck):
    """Condition.wait releases the lock — the checker must model that,
    or every producer/consumer pair would report phantom inversions."""
    cond = threading.Condition()
    other = threading.Lock()
    hit = threading.Event()

    def waker():
        # takes `other` then the condition — the REVERSE textual order
        # of the waiter below; legal because wait() released the lock.
        with other:
            with cond:
                cond.notify_all()
                hit.set()

    t = threading.Thread(target=waker)
    with cond:
        t.start()
        cond.wait(timeout=5.0)
        # while waiting we held NO lock, so taking `other` now is the
        # only edge (cond -> other) and there is no reverse
    t.join(timeout=5.0)
    assert hit.is_set()
    assert lockcheck.violations() == []


def test_lockcheck_cross_thread_orders_conflict(lockcheck):
    a = threading.Lock()
    b = threading.Lock()
    done = threading.Event()

    def t1():
        with a:
            with b:
                pass
        done.set()

    th = threading.Thread(target=t1)
    th.start()
    th.join(timeout=5.0)
    assert done.is_set()
    with b:
        with a:
            pass
    assert len(lockcheck.violations()) == 1
