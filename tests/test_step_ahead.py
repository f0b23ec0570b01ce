"""The decode step is dispatched one step ahead of the host's reading of it
(``DecodeEngine.step``): step N+1 takes step N's tokens on the device, and
the host fetches and retires N while N+1 runs.  Held here, on toy models of
the four paged forms (grouped-query, hybrid with a recurrent state, latent
rows with routed experts, a weight-shared loop):

- the tokens are the serial hand-over's, token for token: against the full
  forward (greedy: every served token is its position's best) and against
  the engine's own step and prefill programs driven BY HAND, one request
  alone, with a fetch between every two steps, as the engine's loop was
  before it ran ahead (greedy and sampled: a key folds on (seed, position),
  so company in the batch changes nothing);
- a call hands back one step's tokens for every lane that rode it: from
  the first call after ``admit`` on, or from the second where the admission
  found a step in flight (that call lands the step dispatched before the
  lane sat, and dispatches the lane's first);
- in steady decode step N+1's dispatch precedes step N's fetch, and on a
  speculative or chunk-prefill turn it does not;
- ``steps_ahead`` / ``steps_serial`` / ``lane_steps_discarded`` against
  counts made here;
- nothing in flight is lost: an eos seen a step late, an abandoned request,
  a hot swap, ``fail_active``, ``settle``, the server's shutdown and drain.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import (FairScheduler,
                                                          Request)
from distributed_tensorflow_tpu.serving.server import ServingServer
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import Telemetry

BASE = dict(vocab_size=64, hidden_size=32, num_heads=4,
            intermediate_size=64, max_position=64, dtype="float32")
FORMS = {
    "gqa": dict(num_layers=2, kv_heads=2, pos_encoding="rope"),
    "hybrid": dict(
        num_layers=4, pos_encoding="none", norm="rmsnorm",
        activation="swiglu",
        layer_kinds=(gpt_lib.LINEAR_ATTENTION,) * 3 + (
            gpt_lib.FULL_ATTENTION,),
        linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16),
    # One cached row a token, routed experts after a leading dense layer:
    # the step's output carries the routing histogram behind its tokens.
    "latent": dict(
        num_layers=2, pos_encoding="none", norm="rmsnorm",
        activation="swiglu", latent_kv_rank=16, latent_q_rank=24,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        num_experts=4, experts_per_token=2, expert_intermediate_size=16,
        num_shared_experts=1, first_dense_layers=1),
    # Three loop steps over two layers: the loop's counters ride behind
    # the tokens.
    "looped": dict(
        num_layers=2, pos_encoding="rope", norm="rmsnorm",
        activation="swiglu", norm_placement="sandwich", loop_steps=3,
        exit_gate=True),
}
PROMPT = [11, 3, 40, 7, 25, 9, 31, 2, 18, 5, 44, 1]
SAMPLING = {"greedy": {},
            "sampled": dict(temperature=0.9, top_k=12, top_p=0.95)}
#: A greedy token's logit below its position's best in the full forward:
#: float32 on both sides, sums in another order.
GAP_TOL = 1e-4


@functools.lru_cache(maxsize=None)
def model_and_params(form, key=0):
    model = gpt_lib.GptLM(gpt_lib.GptConfig(**BASE, **FORMS[form]))
    params = model.init(jax.random.PRNGKey(key),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    return model, params


def engine_of(form, telemetry=None, slots=3, **kw):
    return DecodeEngine(*model_and_params(form), EngineConfig(
        num_slots=slots, page_size=4, num_pages=48, max_pages_per_seq=8,
        **kw), telemetry=telemetry)


def recording():
    records = []
    telemetry = Telemetry()
    orig = telemetry.emit
    telemetry.emit = lambda kind, step=0, **f: (
        records.append((kind, f)), orig(kind, step=step, **f))
    return telemetry, records


def drain(engine):
    while engine.active_slots:
        engine.step()


def live(engine):
    return [s.request for s in engine._slots if s is not None]


def step_counting(engine, seated_behind=(), **kw):
    """One call, and the proof that it handed every live lane one token;
    none to a lane ``seated_behind`` the step in flight, whose first step
    this call dispatches."""
    before = [(r, len(r.tokens)) for r in live(engine)]
    out = engine.step(**kw)
    assert before and all(
        len(r.tokens) == n + (r not in seated_behind) for r, n in before)
    return out


# ------------------------------------------------------------ the oracles


def serial_tokens(form, prompt, budget, sampling=None, seed=0, eos_id=None,
                  swap=None):
    """The request alone on a fresh engine: the engine's prefill seats it,
    then its step program is called BY HAND with the host's arrays and its
    output fetched before the next call.  ``swap`` = (step index, prepared
    tree): from that step on the program runs on that tree."""
    engine = engine_of(form)
    req = Request(prompt, budget, seed=seed, **(sampling or {}))
    engine.admit(req)
    tokens, positions = engine._tokens.copy(), engine._positions.copy()
    fixed = [jnp.asarray(a.copy()) for a in (
        engine._temp, engine._top_k, engine._top_p, engine._seeds)]
    tables = jnp.asarray(engine._tables.copy())
    tree, out = engine._tree, []
    for n in range(budget):
        if swap is not None and n == swap[0]:
            tree = swap[1]
        nxt, engine.pools = engine._step_fn(
            tree, jnp.asarray(tokens.copy()), jnp.asarray(positions.copy()),
            tables, engine.pools, *fixed)
        nxt = np.asarray(nxt)               # the host reads, then goes on
        out.append(int(nxt[0]))
        if out[-1] == eos_id:
            break
        tokens[0] = out[-1]
        positions[0] += 1
    return out


def assert_best_of_the_full_forward(form, req):
    """Nothing of the engine: one forward over prompt + tokens, and every
    served token is the best at its position."""
    model, params = model_and_params(form)
    seq = np.asarray(req.prompt + req.tokens)
    logits = np.asarray(model.apply({"params": params}, seq[None]))[0]
    P = len(req.prompt)
    at = logits[P - 1:-1]
    gap = at.max(-1) - np.take_along_axis(at, seq[P:, None], 1)[:, 0]
    assert gap.max() < GAP_TOL


# ------------------------------------------------- (a) the tokens, (c), (d)


@pytest.mark.parametrize("mode", sorted(SAMPLING))
@pytest.mark.parametrize("form", sorted(FORMS))
def test_tokens_are_the_serial_hand_overs(form, mode):
    """Budgets of different lengths, an admission mid-stream that finds a
    step in flight, a slot taken over by a later request: every request
    gets the tokens it gets alone under a serial hand-over, one a call."""
    sampling = SAMPLING[mode]
    telemetry, records = recording()
    engine = engine_of(form, telemetry)
    specs = {"a": (PROMPT[:7], 9, 3), "b": (PROMPT[:4], 3, 4),
             "c": (PROMPT[:9], 5, 5), "d": (PROMPT[:5], 4, 6),
             "e": (PROMPT[:6], 2, 7)}
    reqs = {k: Request(p, n, seed=s, **sampling)
            for k, (p, n, s) in specs.items()}
    engine.admit(reqs["a"])
    engine.admit(reqs["b"])
    step_counting(engine)
    step_counting(engine)
    in_flight = engine._flight
    assert in_flight is not None
    # The admission waits for the step in flight and leaves it unlanded:
    # the next call hands back ITS tokens (b's last) and dispatches c's
    # first step, on the host's seed token, beside a's on the device's.
    engine.admit(reqs["c"])
    assert engine._flight is in_flight
    assert [len(reqs[k].tokens) for k in "abc"] == [2, 2, 0]
    assert step_counting(engine, seated_behind=[reqs["c"]]) == [reqs["b"]]
    engine.admit(reqs["d"])             # takes over b's slot, same way
    behind = [reqs["d"]]
    while engine.active_slots:
        step_counting(engine, seated_behind=behind)
        behind = []
        if reqs["e"].t_admit is None and engine.free_slots:
            engine.admit(reqs["e"])     # whichever slot frees first
            behind = [reqs["e"]] if engine._flight is not None else []
    for k, (prompt, budget, seed) in specs.items():
        assert reqs[k].tokens == serial_tokens(form, prompt, budget,
                                               sampling, seed), k
        if mode == "greedy":
            assert_best_of_the_full_forward(form, reqs[k])
    # One compiled step, every dispatch in place, every step counted once.
    assert engine._step_fn._cache_size() == 1
    stats = engine.stats()
    steps = [f for kind, f in records if kind == "serve_step"]
    assert stats["pool_steps_copied"] == 0
    assert stats["engine_step"] == len(steps) \
        == stats["steps_ahead"] + stats["steps_serial"]
    assert stats["steps_ahead"] == sum(f["steps_ahead"] for f in steps) > 0
    assert stats["steps_serial"] == sum(f["steps_serial"] for f in steps)
    assert stats["lane_steps_discarded"] == 0
    assert engine._flight is None and engine.allocator.pages_in_use == 0


@pytest.mark.parametrize("form", sorted(FORMS))
def test_an_eos_is_seen_a_step_late_and_its_overshoot_dropped(form):
    """The lane that samples its eos rides the step already queued once
    more; that token is nobody's, the pages it wrote were the lane's own,
    and the slot's next tenant (whose prefill re-seats a recurrent state)
    and the lane beside it get their serial tokens."""
    alone = serial_tokens(form, PROMPT[:6], 8)
    eos = alone[2]
    want = alone[:alone.index(eos) + 1]
    engine = engine_of(form, slots=2)
    stopper = Request(PROMPT[:6], 8, eos_id=eos)
    beside = Request(PROMPT[:8], 9, seed=1)
    engine.admit(stopper)
    engine.admit(beside)
    while stopper.t_done is None:
        step_counting(engine)
    assert stopper.tokens == want
    # The overshoot's step is still in flight: it was dispatched when the
    # lane held its pages, and the next tenant's prefill, which takes the
    # freed pages and the slot, is queued behind it.
    assert engine._flight is not None
    assert engine.stats()["lane_steps_discarded"] == 0
    tenant = Request(PROMPT[:5], 4, seed=2)
    engine.admit(tenant)
    step_counting(engine, seated_behind=[tenant])
    assert engine.stats()["lane_steps_discarded"] == 1
    drain(engine)
    assert beside.tokens == serial_tokens(form, PROMPT[:8], 9, seed=1)
    assert tenant.tokens == serial_tokens(form, PROMPT[:5], 4, seed=2)
    stats = engine.stats()
    assert stats["lane_steps_discarded"] == 1
    assert stats["pool_steps_copied"] == 0


def test_the_last_lanes_eos_leaves_no_step_behind():
    alone = serial_tokens("gqa", PROMPT[:6], 8)
    req = Request(PROMPT[:6], 8, eos_id=alone[1])
    engine = engine_of("gqa")
    engine.admit(req)
    drain(engine)
    assert req.tokens == alone[:alone.index(alone[1]) + 1]
    assert engine._flight is None
    stats = engine.stats()
    assert stats["lane_steps_discarded"] == 1
    assert stats["engine_step"] == len(req.tokens) + 1


@pytest.mark.parametrize("form", sorted(FORMS))
def test_an_abandoned_request_takes_nothing_with_it(form):
    engine = engine_of(form, slots=2)
    quitter = Request(PROMPT[:6], 8)
    stayer = Request(PROMPT[:8], 7, seed=1)
    engine.admit(quitter)
    engine.admit(stayer)
    step_counting(engine)
    step_counting(engine)
    quitter.abandoned = True
    assert engine.step() == [quitter]
    assert quitter.tokens == serial_tokens(form, PROMPT[:6], 8)[:2]
    drain(engine)
    assert stayer.tokens == serial_tokens(form, PROMPT[:8], 7, seed=1)
    # It rode the step dispatched before anyone knew.
    assert engine.stats()["lane_steps_discarded"] == 1
    assert engine.allocator.pages_in_use == 0 and engine._flight is None


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_hot_swap_reaches_the_next_dispatch_not_the_step_in_flight(form):
    """Three calls have dispatched four steps; the swap is adopted by the
    fourth call, whose dispatch is the fifth step: the first on the new
    tree.  Nothing is dropped."""
    _, other = model_and_params(form, key=1)
    engine = engine_of(form)
    req = Request(PROMPT[:6], 9)
    engine.admit(req)
    for _ in range(3):
        step_counting(engine)
    engine.swap_params(other, step=42)
    drain(engine)
    assert engine.swaps == 1 and engine.model_step == 42
    assert req.tokens == serial_tokens(
        form, PROMPT[:6], 9, swap=(4, engine._prepare_params(other)))
    assert req.tokens != serial_tokens(form, PROMPT[:6], 9)


# ------------------------------------------------ nothing in flight is lost


@pytest.mark.parametrize("form", sorted(FORMS))
def test_fail_active_drops_the_step_in_flight_and_the_engine_serves_on(
        form):
    engine = engine_of(form)
    doomed = Request(PROMPT[:6], 8)
    engine.admit(doomed)
    engine.step()
    assert engine._flight is not None
    assert engine.fail_active("RuntimeError: boom") == [doomed]
    assert doomed.error and engine._flight is None
    assert not any(x.is_deleted() for x in jax.tree.leaves(engine.pools))
    assert engine.allocator.pages_in_use == 0
    nxt = Request(PROMPT[:7], 5, seed=3)
    engine.admit(nxt)
    drain(engine)
    assert nxt.tokens == serial_tokens(form, PROMPT[:7], 5, seed=3)
    assert engine.stats()["pool_steps_copied"] == 0


@pytest.mark.parametrize("seated", ["idle_engine", "behind_a_step"])
def test_one_token_a_call_from_the_first_call_that_lands_the_lanes_step(
        seated):
    """(c) ``req.tokens`` grows by one a call: from the first call after
    ``admit`` where nothing was in flight, from the second where the
    admission found a step dispatched before the lane sat."""
    engine = engine_of("gqa")
    if seated == "behind_a_step":
        engine.admit(Request(PROMPT[:4], 12, seed=1))
        engine.step()
    req = Request(PROMPT[:6], 6)
    engine.admit(req)
    assert (engine._flight is not None) == (seated == "behind_a_step")
    grew = []
    while req.t_done is None:
        engine.step()
        grew.append(len(req.tokens))
    first = 1 if seated == "behind_a_step" else 0
    assert grew == [0] * first + [1, 2, 3, 4, 5, 6]
    assert req.tokens == serial_tokens("gqa", PROMPT[:6], 6)


def test_settle_lands_the_step_in_flight_and_only_that():
    engine = engine_of("gqa")
    req = Request(PROMPT[:6], 2)
    engine.admit(req)
    engine.step()
    assert engine._flight is not None and len(req.tokens) == 1
    assert engine.settle() == [req]
    assert req.tokens == serial_tokens("gqa", PROMPT[:6], 2)
    assert engine._flight is None and engine.active_slots == 0
    assert engine.settle() == [] and engine.step() == []


def serving(engine):
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=60.0)
    srv.start()
    return srv


def wait_for(predicate, timeout=60.0):
    t_end = time.monotonic() + timeout
    while not predicate():
        assert time.monotonic() < t_end
        time.sleep(0.002)


def test_shutdown_leaves_no_unfetched_step():
    engine = engine_of("gqa", slots=2)
    srv = serving(engine)
    req = Request(PROMPT[:6], 24)
    try:
        srv.submit(req)
        wait_for(lambda: engine.stats()["steps_ahead"] >= 3)
    finally:
        srv.shutdown()
    assert engine._flight is None
    # Every step dispatched was landed: a token a step, the serial ones.
    assert len(req.tokens) == engine.step_index
    assert req.tokens == serial_tokens("gqa", PROMPT[:6], 24)[
        :len(req.tokens)]


def test_drain_finishes_queued_and_in_flight_requests():
    engine = engine_of("gqa", slots=1)
    srv = serving(engine)
    first, queued = Request(PROMPT[:6], 12), Request(PROMPT[:4], 5, seed=1)
    try:
        srv.submit(first)
        srv.submit(queued)
        wait_for(lambda: engine.step_index >= 2)
        assert srv.begin_drain()["status"] == "draining"
        assert first.event.wait(60.0) and queued.event.wait(60.0)
    finally:
        srv.shutdown()
    assert first.tokens == serial_tokens("gqa", PROMPT[:6], 12)
    assert queued.tokens == serial_tokens("gqa", PROMPT[:4], 5, seed=1)
    assert engine._flight is None and engine.allocator.pages_in_use == 0
    stats = engine.stats()
    assert stats["steps_ahead"] + stats["steps_serial"] \
        == stats["engine_step"] == 17


def test_an_abandoned_stream_in_the_server_frees_its_lane():
    engine = engine_of("gqa", slots=2)
    srv = serving(engine)
    gone, kept = Request(PROMPT[:6], 24), Request(PROMPT[:5], 12, seed=1)
    try:
        srv.submit(gone)
        srv.submit(kept)
        wait_for(lambda: len(gone.tokens) >= 3)
        gone.abandoned = True
        assert kept.event.wait(60.0) and gone.event.wait(60.0)
    finally:
        srv.shutdown()
    assert kept.tokens == serial_tokens("gqa", PROMPT[:5], 12, seed=1)
    assert gone.tokens == serial_tokens("gqa", PROMPT[:6], 24)[
        :len(gone.tokens)] and len(gone.tokens) >= 3
    assert engine._flight is None and engine.allocator.pages_in_use == 0


# ------------------------------------------------ (b) the order of events


class Fetched:
    """Stands for a step's output array: says when the host reads it."""

    def __init__(self, array, n, log):
        self.array, self.n, self.log = array, n, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        return np.asarray(self.array)


def logged(engine, name="_step_fn"):
    """A counting wrapper around the step program and the fetch of what it
    returns; the log of both, in the order they happened."""
    log, real, hand_over = [], getattr(engine, name), engine._hand_over
    # Taken on the device, not fetched.
    engine._hand_over = lambda out, seeded: hand_over(out.array, seeded)

    def counting(tree, tokens, *rest):
        n = sum(kind == "dispatch" for kind, _ in log)
        log.append(("dispatch", n))
        *out, pools = real(tree, tokens, *rest)
        return (Fetched(out[0], n, log), *out[1:], pools)
    setattr(engine, name, counting)
    return log


def ahead_by_the_log(log):
    """Dispatches made while the dispatch before them was unfetched."""
    at = {event: i for i, event in enumerate(log)}
    steps = sum(kind == "dispatch" for kind, _ in log)
    return [n for n in range(1, steps)
            if at[("dispatch", n)] < at[("fetch", n - 1)]]


def test_in_steady_decode_the_next_dispatch_precedes_the_fetch():
    telemetry, records = recording()
    engine = engine_of("gqa", telemetry)
    log = logged(engine)
    seen, real = [], profiling.annotate

    def spy(name, **stats):
        seen.append((name, stats))
        return real(name, **stats)
    engine.admit(Request(PROMPT[:6], 6))
    engine.admit(Request(PROMPT[:4], 4, seed=1))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(profiling, "annotate", spy)
        calls = 0
        while engine.active_slots:
            step_counting(engine)
            calls += 1
    assert calls == 6
    assert log == [("dispatch", 0), ("dispatch", 1), ("fetch", 0),
                   ("dispatch", 2), ("fetch", 1), ("dispatch", 3),
                   ("fetch", 2), ("dispatch", 4), ("fetch", 3),
                   ("dispatch", 5), ("fetch", 4), ("fetch", 5)]
    ahead = ahead_by_the_log(log)
    assert ahead == [1, 2, 3, 4, 5]
    # (d) the three places the counters are told, against the log's count.
    stats = engine.stats()
    assert (stats["steps_ahead"], stats["steps_serial"]) == (5, 1)
    steps = [f for kind, f in records if kind == "serve_step"]
    events = [s for name, s in seen if name == "serve.step.retire"]
    for told in (steps, events):
        assert [n for n, f in enumerate(told) if f["steps_ahead"]] == ahead
        assert [f["steps_serial"] for f in told] == [1, 0, 0, 0, 0, 0]
        assert [f["lane_steps_discarded"] for f in told] == [0] * 6
    # The regions keep their names, a stage a dispatch, a fetch a landing.
    names = [name for name, _ in seen if name.startswith("serve.step")]
    assert names.count("serve.step") == 6
    assert names.count("serve.step.stage") == names.count(
        "serve.step.fetch") == names.count("serve.step.retire") == 6


@pytest.mark.parametrize("arm", ["speculative", "chunk_prefill"])
def test_the_arms_that_stay_serial_fetch_before_they_dispatch(arm):
    kw = {"speculative": dict(spec_k=3),
          "chunk_prefill": dict(prefill_chunk=4)}[arm]
    engine = engine_of("gqa", **kw)
    log = logged(engine, "_spec_step_fn" if arm == "speculative"
                 else "_step_fn")
    reqs = [Request(PROMPT[:9], 7, speculative=arm == "speculative"),
            Request(PROMPT[:5], 5, seed=1)]
    for req in reqs:
        engine.admit(req)
    drain(engine)
    steps = sum(kind == "dispatch" for kind, _ in log)
    assert log == [(kind, n) for n in range(steps)
                   for kind in ("dispatch", "fetch")]
    assert ahead_by_the_log(log) == []
    stats = engine.stats()
    assert stats["steps_ahead"] == 0
    assert stats["steps_serial"] == stats["engine_step"] >= steps > 0
    plain = engine_of("gqa")
    for req in reqs:
        twin = Request(req.prompt, req.num_tokens, seed=req.seed)
        plain.admit(twin)
        drain(plain)
        assert req.tokens == twin.tokens
    assert plain.stats()["steps_ahead"] > 0


def test_a_plain_lane_beside_no_speculative_one_runs_ahead_until_one_sits():
    """``spec_k`` alone changes nothing: the turn is speculative when a
    speculative lane is live, which the engine sees at the admission."""
    engine = engine_of("gqa", spec_k=3)
    plain = Request(PROMPT[:6], 12)
    engine.admit(plain)
    engine.step()
    engine.step()
    # (counted as they land: the serial first, one ahead, one in flight)
    assert engine.stats()["steps_ahead"] == 1
    spec = Request(PROMPT[:8], 6, speculative=True, seed=1)
    engine.admit(spec)
    # The step in flight is landed by a call that dispatches nothing: the
    # speculative lane drafts from what the host has committed.
    step_counting(engine, seated_behind=[spec])
    while spec.t_done is None:
        assert engine._flight is None
        engine.step()
    assert engine.stats()["steps_ahead"] == 2
    drain(engine)                       # ... and ahead again without it
    assert engine.stats()["steps_ahead"] > 2
    assert plain.tokens == serial_tokens("gqa", PROMPT[:6], 12)
    assert spec.tokens == serial_tokens("gqa", PROMPT[:8], 6, seed=1)


@pytest.mark.parametrize("case", ["slot_free", "freeing_at_a_budget",
                                  "nobody_waits", "no_slot_in_sight"])
def test_a_turn_that_sees_an_admission_coming_does_not_run_ahead(case):
    """``queue_depth > 0`` and a slot free, or freeing at a budget in the
    step the call lands: the waiting request is seated next turn."""
    engine = engine_of("gqa", slots=2)
    engine.admit(Request(PROMPT[:6], 9))
    if case != "slot_free":
        # Its first step is its last: the slot frees when that lands.
        engine.admit(Request(PROMPT[:4], 1 if case == "freeing_at_a_budget"
                             else 9, seed=1))
    step_counting(engine, queue_depth=0 if case == "nobody_waits" else 1)
    ran_ahead = case in ("nobody_waits", "no_slot_in_sight")
    assert (engine._flight is not None) == ran_ahead
    assert engine.stats()["steps_serial"] == 1
    drain(engine)
    assert engine.stats()["steps_ahead"] > 0


def test_a_lane_at_its_budget_rides_the_next_step_idle():
    """No lane-step is spent on a lane whose budget the step in flight
    fills: its table row is the sentinel in the step after."""
    engine = engine_of("gqa", slots=2)
    log = []
    real = engine._step_fn

    def watching(tree, tokens, positions, tables, *rest):
        log.append(np.asarray(tables)[:, 0] < engine.config.num_pages)
        return real(tree, tokens, positions, tables, *rest)
    engine._step_fn = watching
    engine.admit(Request(PROMPT[:6], 5))
    engine.admit(Request(PROMPT[:4], 2, seed=1))
    pages = np.count_nonzero(engine._tables < engine.config.num_pages, 1)
    drain(engine)
    assert [row.tolist() for row in log] == [
        [True, True], [True, True], [True, False], [True, False],
        [True, False]]
    assert engine.stats()["lane_steps_discarded"] == 0
    # The pages a lane holds, times the steps it rode.
    assert engine.stats()["table_pages_held"] == 5 * pages[0] + 2 * pages[1]
