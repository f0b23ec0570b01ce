"""Serving tier (docs/serving.md): KV-page allocator, fair scheduler,
continuous-batching engine parity/isolation, hot swap, HTTP frontend, and
the subprocess e2e against a trained-in-test checkpoint."""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.client import Backpressure, ServeClient
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.kv_pool import (OutOfPages,
                                                        PageAllocator)
from distributed_tensorflow_tpu.serving.scheduler import (FairScheduler,
                                                          QueueFull, Request,
                                                          TenantConfig,
                                                          parse_tenants)
from distributed_tensorflow_tpu.serving.server import ServingServer
from distributed_tensorflow_tpu.utils.telemetry import Telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- page allocator


def test_allocator_alloc_free_roundtrip():
    alloc = PageAllocator(num_pages=8, page_size=4)
    pages = alloc.alloc("a", 10)          # 3 pages for 10 tokens
    assert pages == [0, 1, 2]
    assert alloc.pages_in_use == 3 and alloc.free_pages == 5
    assert alloc.alloc("b", 4) == [3]
    assert alloc.free("a") == 3
    assert alloc.pages_in_use == 1
    assert alloc.owned("a") == [] and alloc.owned("b") == [3]


def test_allocator_reuse_order_is_fifo_over_freed_pages():
    # Fresh pages dispense lowest-first; freed pages are reused
    # OLDEST-FREED-FIRST once the fresh run is exhausted.
    alloc = PageAllocator(num_pages=4, page_size=2)
    alloc.alloc("a", 4)                   # pages [0, 1]
    alloc.alloc("b", 4)                   # pages [2, 3]
    alloc.free("b")                       # free: [2, 3]
    alloc.free("a")                       # free: [2, 3, 0, 1]
    assert alloc.alloc("c", 8) == [2, 3, 0, 1]


def test_allocator_out_of_pages_is_atomic():
    alloc = PageAllocator(num_pages=4, page_size=4)
    alloc.alloc("a", 8)
    with pytest.raises(OutOfPages):
        alloc.alloc("b", 12)              # needs 3, only 2 free
    assert alloc.free_pages == 2          # nothing partially taken
    assert alloc.can_alloc(8) and not alloc.can_alloc(9)


def test_allocator_extend_and_double_alloc():
    alloc = PageAllocator(num_pages=6, page_size=4)
    alloc.alloc("a", 4)
    assert alloc.extend("a", 9) == [1, 2]   # grow to 3 pages
    assert alloc.extend("a", 6) == []       # already covered
    with pytest.raises(ValueError):
        alloc.alloc("a", 4)
    with pytest.raises(OutOfPages):
        alloc.extend("a", 100)
    assert alloc.owned("a") == [0, 1, 2]    # failed extend left it intact


def test_allocator_fragmentation_accounting():
    alloc = PageAllocator(num_pages=8, page_size=4)
    assert alloc.internal_fragmentation() == 0.0
    alloc.alloc("a", 5)                   # 2 pages = 8 slots, 5 asked
    assert alloc.internal_fragmentation() == pytest.approx(3 / 8)
    alloc.alloc("b", 4)                   # exact fit: adds no waste
    assert alloc.internal_fragmentation() == pytest.approx(3 / 12)
    snap = alloc.snapshot()
    assert snap["pages_in_use"] == 3 and snap["sequences"] == 2


def test_allocator_page_table_sentinel_padding():
    alloc = PageAllocator(num_pages=8, page_size=4)
    alloc.alloc("a", 6)
    table = alloc.page_table("a", max_pages=4)
    assert table.tolist() == [0, 1, 8, 8]   # sentinel == num_pages
    assert PageAllocator.empty_table(8, 3).tolist() == [8, 8, 8]
    with pytest.raises(ValueError):
        alloc.page_table("a", max_pages=1)


# ------------------------------------------------------- fair scheduler


def test_scheduler_backpressure_bounded_queue():
    sched = FairScheduler([TenantConfig("t", max_queue=2)])
    sched.submit(Request([1], 4, tenant="t"))
    sched.submit(Request([1], 4, tenant="t"))
    with pytest.raises(QueueFull):
        sched.submit(Request([1], 4, tenant="t"))
    assert sched.stats()["t"]["rejected"] == 1


def test_scheduler_fairness_under_unequal_tenants():
    """A flooding tenant must not starve a light one: with equal weights
    the pops interleave; service accounting keeps the light tenant's
    normalized service at/below the heavy one's."""
    sched = FairScheduler()
    heavy = [Request([1], 8, tenant="heavy") for _ in range(8)]
    light = [Request([1], 8, tenant="light") for _ in range(2)]
    for r in heavy[:4]:
        sched.submit(r)
    for r in light:
        sched.submit(r)
    for r in heavy[4:]:
        sched.submit(r)
    order = []
    while True:
        req = sched.next_request()
        if req is None:
            break
        order.append(req.tenant)
        sched.account(req.tenant, 8)      # each request serves 8 tokens
    # Both light requests pop inside the first four grants — the flood
    # cannot push them to the back.
    assert order.count("light") == 2 and order.count("heavy") == 8
    assert [t for t in order[:4]].count("light") == 2


def test_scheduler_weights_bias_service():
    sched = FairScheduler([TenantConfig("big", weight=3.0),
                           TenantConfig("small", weight=1.0)])
    for _ in range(12):
        sched.submit(Request([1], 1, tenant="big"))
        sched.submit(Request([1], 1, tenant="small"))
    grants = {"big": 0, "small": 0}
    for _ in range(8):
        req = sched.next_request()
        grants[req.tenant] += 1
        sched.account(req.tenant, 4)
    # 3:1 weights -> roughly 3/4 of the grants go to the big tenant.
    assert grants["big"] == 6 and grants["small"] == 2


def test_scheduler_fifo_within_tenant_and_admissible_filter():
    sched = FairScheduler()
    first = Request([1], 16, tenant="t")   # too big for the filter below
    second = Request([1], 2, tenant="t")
    sched.submit(first)
    sched.submit(second)
    # Head-of-line: the tenant's SECOND request must not overtake its
    # first just because the first doesn't fit right now.
    assert sched.next_request(lambda r: r.num_tokens <= 4) is None
    assert sched.next_request() is first
    assert sched.next_request() is second


def test_parse_tenants():
    cfgs = parse_tenants("a:2,b:1:8, c")
    assert [(c.name, c.weight, c.max_queue) for c in cfgs] == [
        ("a", 2.0, 64), ("b", 1.0, 8), ("c", 1.0, 64)]
    assert parse_tenants("") == []
    with pytest.raises(ValueError):
        parse_tenants("a:1:2:3")


# ----------------------------------------------------------- the engine


def small_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_position=64, dtype="float32")
    base.update(kw)
    return dataclasses.replace(gpt_lib.mini(), **base)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = small_cfg()
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    return model, params


def drain(engine, sched=None):
    """Run the engine dry, admitting from ``sched`` when given."""
    while True:
        if sched is not None:
            while engine.free_slots > 0:
                req = sched.next_request(engine.can_admit)
                if req is None:
                    break
                engine.admit(req)
        if engine.active_slots == 0:
            break
        engine.step(queue_depth=sched.depth() if sched else 0)


@pytest.mark.smoke
def test_engine_greedy_parity_with_generate(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8))
    req = Request([5, 6, 7, 8], 8)
    engine.validate(req)
    engine.admit(req)
    drain(engine)
    ref = np.asarray(gpt_lib.generate(
        model, params, jnp.asarray([[5, 6, 7, 8]], jnp.int32), 8))[0]
    assert req.tokens == ref[4:].tolist()
    assert engine.allocator.pages_in_use == 0   # retired pages freed


def test_engine_continuous_batching_isolation_and_telemetry(
        model_and_params):
    """Admitting mid-decode must not perturb the resident stream (paged
    isolation), and the step telemetry must prove the overlap."""
    model, params = model_and_params
    telemetry = Telemetry()
    records = []
    telemetry.emit = (lambda _orig: lambda kind, step=0, **f: (
        records.append((kind, step, f)), _orig(kind, step=step, **f))
    )(telemetry.emit)
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=3, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=telemetry)
    req_a = Request(list(range(1, 9)), 10)
    req_b = Request([9, 10, 11], 6)
    engine.admit(req_a)
    engine.step()                          # A is now mid-decode
    engine.admit(req_b)                    # B joins while A is in flight
    drain(engine)
    for req, prompt, n in ((req_a, list(range(1, 9)), 10),
                           (req_b, [9, 10, 11], 6)):
        ref = np.asarray(gpt_lib.generate(
            model, params, jnp.asarray([prompt], jnp.int32), n))[0]
        assert req.tokens == ref[len(prompt):].tolist()
    steps = [f for kind, _, f in records if kind == "serve_step"]
    # The admission-while-mid-decode step: one admitted, two active.
    assert any(s["admitted"] == 1 and s["active_slots"] == 2
               for s in steps)
    assert all(s["kv_pages_total"] == 32 for s in steps)
    reqs = [f for kind, _, f in records if kind == "serve_request"]
    assert len(reqs) == 2 and all(r["status"] == "ok" for r in reqs)
    assert all(r["ttft_ms"] is not None for r in reqs)


def test_engine_eos_and_seeded_sampling_reproducibility(model_and_params):
    """A sampled stream is a function of (seed, positions) only — batch
    composition must not change it; eos retires the lane early."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=3, page_size=4, num_pages=32, max_pages_per_seq=8))
    kw = dict(temperature=0.9, top_k=16, seed=7)
    alone = Request([5, 6, 7], 10, **kw)
    engine.admit(alone)
    drain(engine)
    crowd = Request([5, 6, 7], 10, **kw)
    engine.admit(Request([1, 2], 12, temperature=0.5, seed=3))
    engine.step()
    engine.admit(crowd)
    engine.admit(Request([4, 4, 4, 4], 8))
    drain(engine)
    assert crowd.tokens == alone.tokens
    # eos: the lane retires the step it emits the stop token.
    eos = alone.tokens[3]
    stopped = Request([5, 6, 7], 10, eos_id=eos, **kw)
    engine.admit(stopped)
    drain(engine)
    assert stopped.tokens == alone.tokens[:4]
    assert stopped.tokens[-1] == eos


def test_engine_int8_fp8_matches_contiguous_quantized_decode(
        model_and_params):
    """The paged engine under int8 weights + fp8 KV must reproduce the
    contiguous-cache quantized decode path token for token."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        quantize="int8", kv_dtype="float8"))
    req = Request([5, 6, 7, 8], 8)
    engine.admit(req)
    drain(engine)
    ref = np.asarray(gpt_lib.generate_cached(
        model, params, jnp.asarray([[5, 6, 7, 8]], jnp.int32), 8,
        quantize="int8", kv_dtype="float8"))[0]
    assert req.tokens == ref[4:].tolist()


def test_an_engine_that_consumes_its_params_serves_the_same_tokens(
        model_and_params):
    """``consume_params``: each float leaf that ``quantize="int8"`` has
    replaced is deleted as its int8 form is made (the float and the int8
    trees never lie whole side by side); the tokens are the engine's that
    keeps the caller's tree, the small leaves stay the caller's, and
    without int8 there is nothing to consume."""
    model, params = model_and_params
    kw = dict(num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
              quantize="int8", kv_dtype="float8")
    kept = Request([5, 6, 7, 8], 8)
    engine = DecodeEngine(model, params, EngineConfig(**kw))
    engine.admit(kept)
    drain(engine)
    mine = jax.tree.map(jnp.array, params)         # a copy to give away
    engine = DecodeEngine(model, mine, EngineConfig(**kw,
                                                    consume_params=True))
    req = Request([5, 6, 7, 8], 8)
    engine.admit(req)
    drain(engine)
    assert req.tokens == kept.tokens
    dead = [leaf.is_deleted() for leaf in jax.tree.leaves(mine)]
    big = [leaf.ndim >= 2 and leaf.size >= 4096
           for leaf in jax.tree.leaves(mine)]
    assert dead == big and not all(dead)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))
    with pytest.raises(ValueError, match="consume_params"):
        EngineConfig(consume_params=True)


def test_engine_validate_rejects_bad_requests(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=16, max_pages_per_seq=4))
    for bad in (Request([], 4), Request([1], 0), Request([999], 4),
                Request([1], 4, top_p=1.5), Request([1], 4, eos_id=999),
                Request([1] * 10, 10),    # 20 > capacity 16
                # int32-overflowing sampling params must 400 up front, not
                # OverflowError inside admit() and kill every live stream.
                Request([1], 4, seed=2 ** 31), Request([1], 4, top_k=2 ** 31),
                Request([1], 4, seed=-1), Request([1], 4, top_k=-1)):
        with pytest.raises(ValueError):
            engine.validate(bad)


def test_engine_validate_rejects_reservation_larger_than_pool(
        model_and_params):
    """A request whose worst-case page reservation exceeds the WHOLE pool
    passes the capacity check on small pools but can never be admitted —
    it must be a 400 at validate, not a permanent head-of-line stall."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=2, max_pages_per_seq=8))
    with pytest.raises(ValueError, match="pool"):
        engine.validate(Request([1] * 5, 6))   # 3 pages > 2-page pool
    engine.validate(Request([1] * 4, 4))       # 2 pages: fits
    assert engine.can_admit(Request([1] * 4, 4))


def test_engine_hot_swap_mid_stream_continuity(model_and_params):
    """A weight swap between steps must not drop the in-flight stream:
    the pre-swap prefix is the old model's greedy decode, the stream runs
    to its full budget, and the swap is visible in engine stats."""
    model, params = model_and_params
    params2 = gpt_lib.GptLM(model.cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 16), jnp.int32))["params"]
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8))
    req = Request([5, 6, 7, 8], 10)
    engine.admit(req)
    for _ in range(4):
        engine.step()
    prefix = list(req.tokens)
    engine.swap_params(params2, step=42)   # staged (any thread)
    drain(engine)                          # adopted between steps
    assert len(req.tokens) == 10           # nothing dropped
    ref = np.asarray(gpt_lib.generate(
        model, params, jnp.asarray([[5, 6, 7, 8]], jnp.int32), 4))[0]
    assert prefix == ref[4:].tolist()
    assert engine.model_step == 42 and engine.swaps == 1


# ------------------------------------------------------ model watcher


def test_model_watcher_picks_up_new_verified_checkpoint(tmp_path):
    from distributed_tensorflow_tpu.serving.hot_swap import (
        ModelWatcher, newest_verified_step)
    from distributed_tensorflow_tpu.tools import checkpoint_io

    ckpt = tmp_path / "checkpoints"
    for step, blob in ((2, b"x" * 64), (5, b"y" * 64)):
        d = ckpt / str(step)
        d.mkdir(parents=True)
        (d / "data.bin").write_bytes(blob)
        checkpoint_io.write_manifest(str(d))
    found = newest_verified_step(str(ckpt))
    assert found is not None and found[0] == 5
    # Corrupt the newest: the watcher must fall back to the older valid.
    (ckpt / "5" / "data.bin").write_bytes(b"y" * 63)
    assert newest_verified_step(str(ckpt))[0] == 2

    swapped = []
    watcher = ModelWatcher(
        str(tmp_path), lambda step: {"step": step},
        lambda params, step: swapped.append((params, step)),
        initial_step=0)
    assert watcher.poll_once() == 2
    assert swapped == [({"step": 2}, 2)]
    assert watcher.poll_once() is None     # nothing newer verifies
    # Repair step 5's manifest: next poll swaps forward.
    checkpoint_io.write_manifest(str(ckpt / "5"))
    assert watcher.poll_once() == 5
    assert watcher.current_step == 5


def test_model_watcher_load_failure_degrades_to_stale(tmp_path):
    from distributed_tensorflow_tpu.serving.hot_swap import ModelWatcher
    from distributed_tensorflow_tpu.tools import checkpoint_io

    d = tmp_path / "checkpoints" / "3"
    d.mkdir(parents=True)
    (d / "data.bin").write_bytes(b"z" * 16)
    checkpoint_io.write_manifest(str(d))

    def broken_load(step):
        raise RuntimeError("restore exploded")

    watcher = ModelWatcher(str(tmp_path), broken_load,
                           lambda *_: pytest.fail("must not swap"))
    assert watcher.poll_once() is None     # stale weights, not a crash
    assert watcher.current_step == 0


# ------------------------------------------------------- HTTP frontend


@pytest.fixture()
def server(model_and_params):
    model, params = model_and_params
    telemetry = Telemetry()
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=3, page_size=4, num_pages=48, max_pages_per_seq=8),
        telemetry=telemetry)
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=60.0, telemetry=telemetry)
    srv.start()
    yield srv
    srv.shutdown()


def test_server_two_tenants_concurrent(server, model_and_params):
    model, params = model_and_params
    client = ServeClient(f"http://127.0.0.1:{server.port}")
    results = {}

    def call(i, tenant):
        results[(tenant, i)] = client.generate(
            [i, i + 1, i + 2], 6, tenant=tenant)

    threads = [threading.Thread(target=call, args=(i, t))
               for i in (1, 2) for t in ("alice", "bob")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for (tenant, i), out in results.items():
        ref = np.asarray(gpt_lib.generate(
            model, params, jnp.asarray([[i, i + 1, i + 2]], jnp.int32),
            6))[0]
        assert out["tokens"] == ref.tolist(), (tenant, i)
        assert out["ttft_ms"] is not None
    stats = client.stats()
    assert stats["tenants"]["alice"]["completed"] == 2
    assert stats["tenants"]["bob"]["completed"] == 2
    assert stats["engine"]["kv_pool"]["pages_in_use"] == 0
    health = client.health()
    assert health["status"] == "ok"


def test_server_backpressure_and_validation(model_and_params):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=16, max_pages_per_seq=4))
    srv = ServingServer(
        engine, FairScheduler([TenantConfig("t", max_queue=1)]),
        port=0, request_timeout_s=60.0)
    # Don't start the engine loop thread: requests stay queued, so the
    # bound is deterministic.
    srv._http = __import__("http.server", fromlist=["ThreadingHTTPServer"]
                           ).ThreadingHTTPServer(
        ("127.0.0.1", 0), srv._make_handler())
    http_thread = threading.Thread(target=srv._http.serve_forever,
                                   daemon=True)
    http_thread.start()
    try:
        client = ServeClient(f"http://127.0.0.1:{srv.port}")
        with pytest.raises(ValueError):
            client.generate([], 4, tenant="t")          # 400
        with pytest.raises(ValueError):
            client.generate([1] * 20, 20, tenant="t")   # over capacity
        ok = threading.Thread(
            target=lambda: _swallow(lambda: client.generate(
                [1], 2, tenant="t")), daemon=True)
        ok.start()
        time.sleep(0.3)                                 # let it queue
        with pytest.raises(Backpressure):
            client.generate([1], 2, tenant="t")         # 429: queue full
    finally:
        srv._http.shutdown()
        srv._http.server_close()


def _swallow(fn):
    try:
        fn()
    except Exception:
        pass


# ------------------------------------------------------ subprocess e2e


@pytest.mark.slow
def test_serve_cli_e2e_with_hot_swap(tmp_path):
    """The acceptance scenario end to end, as real processes: train a
    checkpoint in-test, serve it from the CLI, decode for two tenants
    concurrently (continuous batching proven from the telemetry), write a
    NEWER checkpoint mid-stream and watch the hot swap land without
    dropping requests, then gate the stream with summarize_run --check."""
    import optax

    from distributed_tensorflow_tpu.training.state import TrainState
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    cfg = gpt_lib.mini()
    model = gpt_lib.GptLM(cfg)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["tokens"])
        loss, _ = gpt_lib.lm_loss(logits, batch["tokens"])
        return loss

    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32), jnp.int32))["params"]
    state = TrainState.create(
        lambda p, t: model.apply({"params": p}, t), params,
        optax.adam(3e-3))
    step_fn = jax.jit(
        lambda st, batch: st.apply_gradients(
            jax.grad(loss_fn)(st.params, batch)))
    batch = {"tokens": jnp.asarray(
        gpt_lib.synthetic_lm_batch(0, 8, 32, cfg)["tokens"])}
    for _ in range(10):     # "trained-in-test": a few real steps
        state = step_fn(state, batch)
    logdir = tmp_path / "run"
    sv = Supervisor(is_chief=True, logdir=str(logdir),
                    init_fn=lambda: state)
    assert sv.maybe_save(state, force=True)

    metrics = tmp_path / "serve.jsonl"
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "distributed_tensorflow_tpu.tools.serve",
         "--logdir", str(logdir), "--port", "0",
         "--platform", "cpu", "--slots", "4", "--page_size", "8",
         "--num_pages", "64", "--max_pages_per_seq", "8",
         "--metrics_file", str(metrics), "--hot_swap",
         "--swap_poll_s", "0.5", "--tenants", "alice:2,bob:1"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        # The banner prints the served model (the checkpoint namespace —
        # here the logdir basename "run") and the bound port (--port 0 ->
        # ephemeral); noise lines (e.g. orbax restore warnings) may
        # precede it.
        seen = []
        line = ""
        for _ in range(80):
            line = proc.stdout.readline()
            if not line or (line.startswith("serving ") and " on :" in line):
                break
            seen.append(line)
        assert line.startswith("serving run "), "".join(seen)
        port = int(line.split(" on :")[1].split(" ")[0].rstrip("—").strip())
        client = ServeClient(f"http://127.0.0.1:{port}", timeout_s=300.0)
        for _ in range(60):
            try:
                client.health()
                break
            except Exception:
                time.sleep(1)

        results = {}

        def call(key, tenant, n):
            results[key] = (n, client.generate(
                [3, 4, 5], n, tenant=tenant, seed=1))

        # Six requests over four slots with staggered budgets: the first
        # four admit together, and each early retirement backfills a
        # queued request WHILE the longer lanes are mid-decode — the
        # continuous-batching overlap the telemetry must prove.
        threads = [threading.Thread(
                       target=call, args=((t, i), t, 12 + 6 * i))
                   for i in (0, 1, 2) for t in ("alice", "bob")]
        for t in threads:
            t.start()
        # Mid-stream: save a NEWER checkpoint for the watcher to swap in.
        for _ in range(5):
            state = step_fn(state, batch)
        assert sv.maybe_save(state, force=True)
        sv.close()
        for t in threads:
            t.join()
        assert all(len(v["tokens"]) == 3 + n
                   for n, v in results.values()), results
        # Wait for the swap to land (poll cadence 0.5s + load time).
        swapped = False
        for _ in range(60):
            if client.health().get("model_step", 0) >= 2:
                swapped = True
                break
            time.sleep(1)
        assert swapped, "hot swap never landed"
        post = client.generate([3, 4, 5], 4, tenant="alice")
        assert post["model_step"] >= 2
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()

    # The stream satisfies the CI contract and proves the overlap.
    from distributed_tensorflow_tpu.tools import summarize_run
    records, errors = summarize_run.load_records(str(metrics))
    assert not summarize_run.check_records(records, errors)
    summary = summarize_run.build_summary(records)
    (worker,) = summary["workers"].values()
    serving = worker["serving"]
    assert serving["requests"] >= 5
    assert serving["peak_active_slots"] >= 2       # concurrent tenants
    assert serving["overlap_admissions"] >= 1      # joined mid-decode
    assert set(serving["tenants"]) >= {"alice", "bob"}
    assert serving["tenants"]["alice"]["ttft_ms"]["p50"] > 0


# ------------------------------------------------- speculative decode arm


def test_chunk_paged_matches_step_paged_sequence(model_and_params):
    """decode_chunk_paged == K sequential decode_step_paged calls (same
    logits for the fed tokens, same pool state for the committed ones)."""
    model, params = model_and_params
    cfg = model.cfg
    B, P, K = 1, 6, 4
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, (B, P)).astype(np.int32)
    chunk = rng.integers(0, cfg.vocab_size, (B, K)).astype(np.int32)

    def prefilled():
        pools = gpt_lib.init_kv_pool(cfg, 16, 4)
        caches = gpt_lib.init_kv_cache(cfg, B, 8)
        _, caches = model.apply({"params": params}, jnp.asarray(prompt),
                                caches, method=gpt_lib.GptLM.prefill)
        new = []
        for (kc, vc), (kp, vp) in zip(caches, pools):
            kp = kp.at[jnp.asarray([0, 1])].set(
                kc[0].reshape(2, 4, -1))
            vp = vp.at[jnp.asarray([0, 1])].set(
                vc[0].reshape(2, 4, -1))
            new.append((kp, vp))
        return new

    tables = jnp.asarray(np.asarray([[0, 1, 2, 3]], np.int32))
    logits_c, pools_c = model.apply(
        {"params": params}, jnp.asarray(chunk), prefilled(), tables,
        jnp.full((B,), P, jnp.int32),
        method=gpt_lib.GptLM.decode_chunk_paged)
    logits_c = np.asarray(logits_c)

    pools_s = prefilled()
    for i in range(K):
        ref, pools_s = model.apply(
            {"params": params}, jnp.asarray(chunk[:, i]), pools_s, tables,
            jnp.full((B,), P + i, jnp.int32),
            method=gpt_lib.GptLM.decode_paged)
        np.testing.assert_allclose(logits_c[:, i], np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    for (kc, vc), (ks, vs) in zip(pools_c, pools_s):
        np.testing.assert_allclose(np.asarray(kc), np.asarray(ks),
                                   rtol=1e-6, atol=1e-6)


def test_chunk_paged_oob_drafts_never_touch_real_pages(model_and_params):
    """Draft positions past the page table must DROP, not clamp onto the
    last real page (which holds committed K/V)."""
    model, params = model_and_params
    cfg = model.cfg
    pools = gpt_lib.init_kv_pool(cfg, 8, 4)
    # One row owning ALL its table's pages; chunk speculates past them.
    tables = jnp.asarray(np.asarray([[0, 1]], np.int32))   # MP = 2 -> 8 slots
    before = [(np.asarray(k), np.asarray(v)) for k, v in pools]
    chunk = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    _, pools2 = model.apply(
        {"params": params}, chunk, pools, tables,
        jnp.asarray([6], jnp.int32),     # positions 6..9; 8/9 are OOB
        method=gpt_lib.GptLM.decode_chunk_paged)
    for (kb, vb), (ka, va) in zip(before, pools2):
        ka = np.asarray(ka)
        # Slots 6, 7 of page 1 written; everything else — including page
        # 0 and the other pools' pages — untouched.
        assert not np.array_equal(ka[1, 2:], kb[1, 2:]) or ka[1, 2:].any()
        np.testing.assert_array_equal(ka[0], kb[0])
        np.testing.assert_array_equal(ka[2:], kb[2:])


# ------------------------------------------- the pool's row held flat

_PAGES, _PAGE, _MP = 9, 4, 3     # the pool; a lane's table: 12 slots
_TABLES = np.asarray([[2, 5, _PAGES], [0, 3, 7], [_PAGES] * _MP], np.int32)
_POSITIONS = np.asarray([6, 7, 0], np.int32)     # lane 2 idle


def _flat_row_case(heads, kv_heads, kv_dtype):
    """A two-layer model, contiguous caches [3, 12, G, D] of junk in the
    pool's dtype, and junk pools that hold each live lane's allocated
    pages of the same rows, built in the [.., G, D] view and handed over
    as :func:`init_kv_pool` shapes them: the sentinel's page after the
    allocator's ``_PAGES``, all zeros."""
    cfg = small_cfg(hidden_size=8 * heads, num_heads=heads,
                    kv_heads=kv_heads)
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng([heads, kv_heads])
    caches, pools, views = [], [], []
    for entry in gpt_lib.init_kv_pool(cfg, _PAGES, _PAGE, dtype=kv_dtype):
        cache_pair, pool_pair, view_pair = [], [], []
        for leaf in entry:
            assert leaf.shape == (_PAGES + 1, _PAGE, kv_heads * 8)
            assert leaf.dtype == jnp.dtype(kv_dtype)
            cache = rng.normal(size=(3, _MP * _PAGE, kv_heads, 8))
            view = rng.normal(size=(_PAGES + 1, _PAGE, kv_heads, 8))
            view[_PAGES] = 0
            for lane, table in enumerate(_TABLES):
                for i, page in enumerate(table):
                    if page < _PAGES:
                        view[page] = cache[lane, i * _PAGE:(i + 1) * _PAGE]
            cache_pair.append(jnp.asarray(cache, kv_dtype))
            view_pair.append(np.asarray(jnp.asarray(view, kv_dtype)))
            pool_pair.append(jnp.asarray(view, kv_dtype).reshape(leaf.shape))
        caches.append(tuple(cache_pair))
        pools.append(tuple(pool_pair))
        views.append(view_pair)
    return cfg, model, params, caches, pools, views


def _expect_pools(views, caches_after, pools_after, written):
    """Every pool leaf, viewed [.., G, D], is what it was, except that the
    live lanes' slots ``written`` (lane -> logical positions) hold what
    the contiguous path wrote there: bit for bit, idle lane included, and
    the sentinel's page still all zeros."""
    for view_pair, cache_pair, pool_pair in zip(views, caches_after,
                                                pools_after):
        for view, cache, pool in zip(view_pair, cache_pair, pool_pair):
            want = view.copy()
            for lane, slots in written.items():
                for s in slots:
                    want[_TABLES[lane, s // _PAGE], s % _PAGE] = np.asarray(
                        cache)[lane, s]
            assert pool.dtype == cache.dtype
            np.testing.assert_array_equal(
                np.asarray(pool).reshape(want.shape).view(np.uint8),
                want.view(np.uint8))


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float8_e4m3fn"])
@pytest.mark.parametrize("heads,kv_heads", [(8, 8), (6, 3)],
                         ids=["kv8", "kv3"])
@pytest.mark.parametrize("program", ["step", "chunk", "landing"])
def test_flat_pool_row_is_the_contiguous_cache(
        program, heads, kv_heads, kv_dtype):
    """PR 37 holds a K/V pool's row flat, [pages, page, G * D]: the paged
    decode step, the paged chunk and the prefill's landing give the pool
    contents (viewed [.., G, D]) of the contiguous-cache path bit for bit
    and its logits (the chunk's bit for bit, the step's to float32
    rounding), whether G is a multiple of 8 or not and whatever the pool's
    dtype; an idle lane writes nowhere; a row's bytes are what they
    were."""
    cfg, model, params, caches, pools, views = _flat_row_case(
        heads, kv_heads, kv_dtype)
    itemsize = jnp.dtype(kv_dtype).itemsize
    assert gpt_lib.kv_row_bytes_per_token(cfg, kv_dtype) == (
        2 * 2 * kv_heads * 8 * itemsize) == sum(
            x.nbytes for x in jax.tree.leaves(pools)) // (
                (_PAGES + 1) * _PAGE)
    tables, positions = jnp.asarray(_TABLES), jnp.asarray(_POSITIONS)
    apply = lambda method, *a: jax.jit(  # noqa: E731
        lambda *a: model.apply({"params": params}, *a, method=method))(*a)
    if program == "step":
        token = jnp.asarray([3, 9, 0], jnp.int32)
        want, caches_after = apply(gpt_lib.GptLM.decode_ragged, token,
                                   caches, positions)
        got, pools_after = apply(gpt_lib.GptLM.decode_paged, token, pools,
                                 tables, positions)
        written = {0: [6], 1: [7]}
    elif program == "chunk":
        # Lane 0's chunk runs past its two pages: slots 8, 9 drop.
        chunk = jnp.asarray([[3, 9, 4, 1], [7, 7, 2, 5], [0] * 4], jnp.int32)
        want, caches_after = apply(gpt_lib.GptLM.decode_chunk, chunk,
                                   caches, positions)
        got, pools_after = apply(gpt_lib.GptLM.decode_chunk_paged, chunk,
                                 pools, tables, positions)
        written = {0: [6, 7], 1: [7, 8, 9, 10]}
    else:
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=3, page_size=_PAGE, num_pages=_PAGES,
            max_pages_per_seq=_MP,
            kv_dtype={"float8_e4m3fn": "float8"}.get(kv_dtype, kv_dtype)))
        assert [x.shape for x in jax.tree.leaves(engine.pools)] == [
            x.shape for x in jax.tree.leaves(pools)]
        toks = jnp.asarray(np.random.default_rng(5).integers(
            0, cfg.vocab_size, (1, _MP * _PAGE)), jnp.int32)
        want, caches_after = None, apply(
            gpt_lib.GptLM.prefill, toks, gpt_lib.init_kv_cache(
                cfg, 1, _MP * _PAGE, dtype=kv_dtype))[1]
        # The bucket's last page is beyond the lane's allocation: dropped.
        pools_after = engine._prefill_fn(_MP)(
            engine._tree, toks, pools, tables[0])
        written = {0: range(2 * _PAGE)}
    _expect_pools(views, caches_after, pools_after, written)
    if want is not None:
        # The chunk attends through ``_attend_cache_chunk`` like the
        # contiguous path: equal.  The step attends the FLAT rows
        # (``_attend_rows``): the same float32 sums with zeros among them,
        # in another order: a few units in the last place of logits of
        # size 1-3 (sound readings here 1e-6; a wrong row reads 1e-1).
        got, want = np.asarray(got[:2]), np.asarray(want[:2])
        assert np.isfinite(got).all() and np.abs(want).max() > 1
        np.testing.assert_allclose(
            got, want, rtol=0, atol=0 if program == "chunk" else 5e-6)


def test_engine_spec_parity_and_multi_token_rounds(model_and_params):
    """The paged speculative arm: a spec lane emits the SAME tokens as
    plain greedy decode, in fewer engine steps when the stream is
    predictable; per-request stats expose accepted/round."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        spec_k=6))
    # A looping prompt: untrained greedy decode settles into a cycle the
    # n-gram drafter can mine.
    prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7]
    GEN = 16
    req = Request(prompt, GEN, speculative=True)
    engine.validate(req)
    engine.admit(req)
    steps = 0
    while engine.active_slots:
        engine.step()
        steps += 1
    ref = np.asarray(gpt_lib.generate_cached(
        model, params, jnp.asarray([prompt], jnp.int32), GEN))[0]
    assert req.tokens == ref[len(prompt):].tolist()
    assert req.spec_rounds == steps
    assert len(req.tokens) == GEN


def test_engine_spec_mixed_batch_with_admission_and_retirement(
        model_and_params):
    """Spec + plain + seeded-sampled lanes share the chunk step under
    mid-stream admission/retirement; every lane matches its non-spec
    engine twin token for token."""
    model, params = model_and_params
    spec_cfg = EngineConfig(num_slots=3, page_size=4, num_pages=32,
                            max_pages_per_seq=8, spec_k=6)
    plain_cfg = dataclasses.replace(spec_cfg, spec_k=0)

    def requests():
        return (Request([5, 6, 7, 5, 6, 7], 12, speculative=True),
                Request([1, 2, 3, 4], 10),
                Request([9, 10, 11], 8, temperature=0.8, top_k=16,
                        seed=21))

    def run(cfg):
        engine = DecodeEngine(model, params, cfg)
        r_spec, r_plain, r_samp = requests()
        engine.admit(r_spec)
        engine.step()                      # spec lane is mid-decode
        engine.admit(r_plain)              # joins while spec in flight
        engine.step()
        engine.admit(r_samp)
        while engine.active_slots:
            engine.step()
        assert engine.allocator.pages_in_use == 0
        return r_spec.tokens, r_plain.tokens, r_samp.tokens

    got = run(spec_cfg)
    want = run(plain_cfg)
    assert got == want


def test_engine_spec_eos_mid_chunk_retires_exactly(model_and_params):
    """An eos accepted mid-chunk truncates the emission at the eos and
    retires the lane — same tokens as the eos-aware plain path."""
    model, params = model_and_params
    prompt = [5, 6, 7, 5, 6, 7]
    free = np.asarray(gpt_lib.generate_cached(
        model, params, jnp.asarray([prompt], jnp.int32), 12))[0]
    eos = int(free[len(prompt) + 4])
    ref = np.asarray(gpt_lib.generate_cached(
        model, params, jnp.asarray([prompt], jnp.int32), 12,
        eos_id=eos))[0]
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=32, max_pages_per_seq=8,
        spec_k=6))
    req = Request(prompt, 12, eos_id=eos, speculative=True)
    engine.admit(req)
    while engine.active_slots:
        engine.step()
    want = ref[len(prompt):].tolist()
    while want and want[-1] == eos and len(want) > 1 and want[-2] == eos:
        want.pop()                         # generate_cached pads with eos
    assert req.tokens[-1] == eos
    assert req.tokens == want[:len(req.tokens)]
    assert eos in req.tokens


def test_engine_spec_telemetry_and_validation(model_and_params):
    model, params = model_and_params
    telemetry = Telemetry()
    records = []
    telemetry.emit = (lambda _orig: lambda kind, step=0, **f: (
        records.append((kind, f)), _orig(kind, step=step, **f))
    )(telemetry.emit)
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        spec_k=6), telemetry=telemetry)
    with pytest.raises(ValueError, match="greedy-only"):
        engine.validate(Request([1, 2], 4, speculative=True,
                                temperature=0.7))
    req = Request([5, 6, 7, 5, 6, 7], 10, speculative=True)
    engine.admit(req)
    while engine.active_slots:
        engine.step()
    steps = [f for kind, f in records if kind == "serve_step"]
    assert all("spec_rows" in s and "spec_accepted" in s for s in steps)
    assert sum(s["spec_accepted"] for s in steps) == len(req.tokens)
    assert all(s["spec_rows"] == 1 for s in steps)
    reqs = [f for kind, f in records if kind == "serve_request"]
    assert reqs and reqs[0].get("speculative") is True
    assert reqs[0]["spec_rounds"] == len(steps)
    assert reqs[0]["spec_accepted_per_round"] == pytest.approx(
        len(req.tokens) / len(steps), abs=0.01)


def test_engine_spec_flag_without_engine_support_decodes_plain(
        model_and_params):
    """Request-level opt-in on a server without --spec_k: plain decode,
    same tokens (the flag is a performance hint, never a contract)."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=32, max_pages_per_seq=8))
    req = Request([5, 6, 7, 8], 8, speculative=True)
    engine.admit(req)
    while engine.active_slots:
        engine.step()
    ref = np.asarray(gpt_lib.generate_cached(
        model, params, jnp.asarray([[5, 6, 7, 8]], jnp.int32), 8))[0]
    assert req.tokens == ref[4:].tolist()


def test_server_speculative_request_over_http(model_and_params):
    """End-to-end over the HTTP frontend: a speculative request returns
    the greedy tokens plus spec stats; temperature + speculative 400s."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        spec_k=6))
    server = ServingServer(engine, FairScheduler(), port=0,
                           request_timeout_s=30.0)
    server.start()
    try:
        client = ServeClient(f"http://127.0.0.1:{server.port}")
        prompt = [5, 6, 7, 5, 6, 7]
        out = client.generate(prompt, 10, speculative=True)
        ref = np.asarray(gpt_lib.generate_cached(
            model, params, jnp.asarray([prompt], jnp.int32), 10))[0]
        assert out["tokens"] == ref.tolist()
        assert out["spec_rounds"] >= 1
        assert out["spec_accepted_per_round"] >= 1.0
        with pytest.raises(ValueError, match="greedy-only"):
            client.generate(prompt, 4, speculative=True, temperature=0.5)
    finally:
        server.shutdown()


# ------------------------------------------------------ chunked prefill


@pytest.mark.smoke
def test_chunked_prefill_token_parity_with_whole_bucket(model_and_params):
    """ISSUE 11 acceptance: the chunked-prefill engine emits token-for-
    token what the whole-bucket engine emits on the same workload —
    greedy AND seeded-sampled lanes, with the long prompt admitted while
    other lanes are mid-decode."""
    model, params = model_and_params

    def requests():
        return (Request(list(range(1, 14)), 8),           # 13-token prompt
                Request([5, 6, 7], 6, temperature=0.8, top_k=16, seed=21),
                Request([9], 5))                          # P=1 degenerate

    def run(prefill_chunk, **cfg_kw):
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=3, page_size=4, num_pages=32, max_pages_per_seq=8,
            prefill_chunk=prefill_chunk, **cfg_kw))
        long_req, samp, tiny = requests()
        engine.admit(samp)
        engine.step()                       # samp is mid-decode
        engine.admit(long_req)              # long prompt joins chunked
        engine.admit(tiny)
        while engine.active_slots:
            engine.step()
        assert engine.allocator.pages_in_use == 0
        return long_req.tokens, samp.tokens, tiny.tokens

    chunked_out = run(4)
    assert chunked_out == run(0)
    ref = np.asarray(gpt_lib.generate(
        model, params, jnp.asarray([list(range(1, 14))], jnp.int32), 8))[0]
    assert chunked_out[0] == ref[13:].tolist()
    # The quantized serving arm (int8 weights + fp8 KV): the chunk path
    # writes/reads the same narrowed pool the whole-bucket path does.
    quant = dict(quantize="int8", kv_dtype="float8")
    assert run(4, **quant) == run(0, **quant)


def test_chunked_prefill_rides_the_resident_step(model_and_params):
    """While a long prompt prefills in chunks, an already-live lane must
    KEEP EMITTING tokens — the continuous-batching discipline the whole-
    bucket path violates (its admit() blocks the loop for the full
    prompt forward).  Telemetry carries the prefill decomposition."""
    model, params = model_and_params
    telemetry = Telemetry()
    records = []
    telemetry.emit = (lambda _orig: lambda kind, step=0, **f: (
        records.append((kind, f)), _orig(kind, step=step, **f))
    )(telemetry.emit)
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        prefill_chunk=3), telemetry=telemetry)
    live = Request([5, 6, 7], 20)
    engine.admit(live)
    engine.step()
    long_req = Request(list(range(1, 14)), 4)   # target 12 -> 4 chunks
    engine.admit(long_req)
    before = len(live.tokens)
    emitted_during_prefill = 0
    while any(s is not None and s.prefilling for s in engine._slots):
        n0 = len(live.tokens)
        engine.step()
        emitted_during_prefill += len(live.tokens) - n0
    # The live lane decoded THROUGH the neighbor's prefill.
    assert emitted_during_prefill >= 3
    assert len(live.tokens) > before
    while engine.active_slots:
        engine.step()
    ref = np.asarray(gpt_lib.generate(
        model, params, jnp.asarray([[5, 6, 7]], jnp.int32), 20))[0]
    assert live.tokens == ref[3:].tolist()
    steps = [f for kind, f in records if kind == "serve_step"]
    assert all("prefill_rows" in s and "prefill_ms" in s for s in steps)
    chunk_steps = [s for s in steps if s["prefill_rows"]]
    # 1 chunk for the live lane's own 2-position prefill +
    # ceil(12 / 3) = 4 for the long prompt.
    assert len(chunk_steps) == 5
    assert engine.prefill_ms_total > 0.0


def test_chunked_prefill_spec_lane_live_during_neighbor_prefill(
        model_and_params):
    """A speculative lane mid-decode while a neighbor chunk-prefills:
    both lanes match their plain-engine twins token for token (the spec
    chunk program and the prefill chunk program share a step)."""
    model, params = model_and_params

    def run(prefill_chunk, spec_k):
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
            spec_k=spec_k, prefill_chunk=prefill_chunk))
        spec_req = Request([5, 6, 7, 5, 6, 7], 12,
                           speculative=bool(spec_k))
        engine.admit(spec_req)
        engine.step()                       # spec lane mid-decode
        long_req = Request(list(range(1, 14)), 6)
        engine.admit(long_req)              # prefills while spec decodes
        while engine.active_slots:
            engine.step()
        return spec_req.tokens, long_req.tokens

    got = run(4, 6)
    want = run(0, 0)
    assert got == want


def test_chunked_prefill_abandoned_lane_retires_and_frees_pages(
        model_and_params):
    """A caller giving up mid-prefill must free the lane's pages at the
    next step boundary — prefilling lanes ride the same abandonment
    path as decoding ones."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=32, max_pages_per_seq=8,
        prefill_chunk=2))
    req = Request(list(range(1, 14)), 4)
    engine.admit(req)
    assert engine.allocator.pages_in_use > 0
    engine.step()                           # one chunk lands
    req.abandoned = True
    retired = engine.step()
    assert [r.id for r in retired] == [req.id]
    assert engine.allocator.pages_in_use == 0
    assert engine.active_slots == 0


def test_prefill_compile_cache_lru_bounded(model_and_params):
    """Satellite (ISSUE 11): adversarial prompt lengths must not grow
    one resident jitted prefill program per page count forever — the
    cache is LRU-bounded at prefill_cache_cap and /statz reports the
    resident count + evictions."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=64, max_pages_per_seq=8,
        prefill_cache_cap=2))
    outs = {}
    for pages in (1, 2, 3, 1):              # 3 evicts 1's slot; 1 rebuilds
        p = pages * 4 - 1
        req = Request(list(range(1, p + 1)), 3)
        engine.admit(req)
        while engine.active_slots:
            engine.step()
        outs.setdefault(p, []).append(tuple(req.tokens))
    assert len(engine._prefill_fns) <= 2
    cache = engine.stats()["compile_cache"]
    assert cache["prefill_programs"] <= 2
    assert cache["cap"] == 2
    assert cache["evictions"] >= 1
    # A rebuilt (previously evicted) program still computes the same
    # stream.
    assert outs[3][0] == outs[3][1]


def test_chunked_engine_stats_and_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(prefill_chunk=-1)
    with pytest.raises(ValueError, match="prefill_cache_cap"):
        EngineConfig(prefill_cache_cap=0)
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        prefill_chunk=4))
    stats = engine.stats()
    assert stats["prefill_chunk"] == 4
    assert stats["prefilling_slots"] == 0
    engine.admit(Request(list(range(1, 14)), 4))
    assert engine.stats()["prefilling_slots"] == 1
    while engine.active_slots:
        engine.step()
    stats = engine.stats()
    assert stats["prefilling_slots"] == 0
    assert stats["compile_cache"]["chunk_programs"] == 1
