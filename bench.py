"""Benchmark harness — the framework's recorded performance evidence.

Prints ONE JSON line (driver contract): the BASELINE.json primary metric
(MNIST steps/sec/chip, reference hyperparameters batch 100 / hidden 100 /
lr 0.01 — reference ``distributed.py:11-14``) with every secondary metric
under ``"extra"``.  The same payload (pretty) is written to
``BENCH_DETAILS.json``.

Metrics (``--mode`` selects a subset; default ``all``):

- ``mnist``      steps/sec/chip + ``vs_baseline`` ratio against a
                 reference-style per-step protocol emulated on the same
                 hardware (fresh host feed, separate accuracy forward,
                 blocking per-step fetch — ``distributed.py:137-153``).
- ``transformer`` GPT train-step time at an MXU-loading size (hidden 2048,
                 8 layers, 16 heads, intermediate 8192, seq 1024, bf16),
                 achieved model TFLOP/s and MFU against the chip's peak.
- ``flash``      pallas flash attention vs dense XLA, fwd+bwd, S=2048/8192
                 (the Mosaic compile path on real TPU; PARITY.md's speedup
                 claim as a recorded number).
- ``ln``         fused pallas LayerNorm vs nn.LayerNorm, fwd+bwd.
- ``scanned``    --steps_per_call dispatch-amortization ablation (1 vs 16).
- ``converge``   wall-clock/steps to validation-accuracy convergence on the
                 reference workload (its implicit convergence-as-test), with
                 the projected time under the reference's per-step protocol.
- ``profile``    per-op device-time breakdown of the flagship GPT step
                 (utils/xplane trace parse): matmul vs attention kernel vs
                 elementwise vs data movement + device idle.
- ``mfu_ladder`` end-to-end train MFU at S=4096/8192/8192+window (S=1024
                 lives in ``transformer``).
- ``serve``      the serving tier's continuous-batching engine under a
                 2-tenant load: tokens/s over the slot batch, TTFT/TPOT
                 percentiles, the int8-weight/fp8-KV arm's speedup, and
                 the mixed long-prompt/short-decode arm (tpot_p99 +
                 prefill_stall_ms, chunked vs whole-bucket prefill —
                 docs/serving.md).
- ``router``     the serving FLEET: N in-process replicas behind the
                 statz-routed frontend (serving/router.py) under a
                 zipfian multi-tenant load — QPS + TTFT p99 vs replica
                 count, plus a kill-one-replica arm recording the
                 failover gap and post-failover tail (docs/serving.md,
                 "Fleet").
- ``quant_fused`` the pallas fused-epilogue quant-matmul's isolated vs
                 in-step ratio against the unfused-pallas composition
                 (the BENCH_r04 regression class, pinned).
- ``scaling``    sync-replica weak-scaling efficiency 1->N devices
                 (BASELINE.md target >=90%).  n=1 is measured in this
                 process; the 1..8 ladder runs as CPU virtual-mesh
                 subprocesses (a correctness/weak-scaling proxy, NOT a
                 device number — the parent holds the chip, so a child
                 could not take it; ROADMAP S3 measures it on hardware).

Timing discipline: dispatch is asynchronous, so every measurement chains
its iterations on-device (donated state or a ``lax.scan``), ends with a
scalar fetch of the chain's last value (the fetch cannot return before
the whole chain has run), and reports the median of several trials.

The ``router``, ``autotune`` and ``scaling`` legs start child processes
that are held to the CPU (one process owns the chip); their figures are
host numbers whatever backend the parent reports.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


class BenchLegTimeout(BaseException):
    """A bench leg overran its per-leg wall-clock limit (a hung device or
    a wedged compile); the leg is recorded as failed and the suite —
    and crucially the final headline JSON line — continues.  Deliberately
    a BaseException: the legs' own broad ``except Exception`` handlers
    (per-shape/per-arm error recording) must NOT swallow it — the alarm
    fires once, and a swallowed timeout would leave the rest of the leg
    running with no timer at all."""


@contextlib.contextmanager
def _leg_timeout(seconds: float):
    """SIGALRM-based per-leg timeout (main thread, POSIX).  0 disables."""
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def handler(signum, frame):
        raise BenchLegTimeout(f"leg exceeded its {seconds:.0f}s limit")

    old = signal.signal(signal.SIGALRM, handler)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


def _injected_leg_fault(name: str) -> str | None:
    """Test hook: ``BENCH_INJECT_FAULT=crash:<leg>`` raises at the leg's
    entry, ``hang:<leg>`` sleeps past the per-leg timeout — both must
    still end in a parseable headline line (tests/test_bench_headline.py).
    """
    spec = os.environ.get("BENCH_INJECT_FAULT", "")
    if not spec:
        return None
    kind, _, leg = spec.partition(":")
    return kind if leg == name else None

# bf16 peak TFLOP/s per chip by device kind (dense); used for MFU. Sources:
# public TPU spec sheets. Unknown kinds report tflops without MFU.
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def _peak_tflops() -> float | None:
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in PEAK_TFLOPS.items():
        if key in kind:
            return peak
    return None


def _sync(x) -> float:
    """Device->host scalar fetch: returns once the value, and so the whole
    dependent chain behind it, has been computed (see module docstring)."""
    import jax
    return float(jax.tree.leaves(x)[0])


def _median_rate(run_once, iters: int, trials: int) -> float:
    """Median iterations/sec over trials; run_once(iters) must block until
    the work is done (scalar fetch)."""
    rates = []
    for _ in range(trials):
        t0 = time.perf_counter()
        run_once(iters)
        rates.append(iters / (time.perf_counter() - t0))
    return float(np.median(rates))


# ---------------------------------------------------------------- mnist


def build_mnist(batch_size=100, hidden=100, lr=0.01, num_devices=None):
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models.mlp import (
        MnistMLP, accuracy, cross_entropy_loss)
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib
    from distributed_tensorflow_tpu.parallel.sharding import replicate_tree
    from distributed_tensorflow_tpu.training.state import (
        TrainState, gradient_descent)

    # The declarative layout entry point (docs/autotune.md): a pure-DP
    # ParallelConfig over a device prefix — same path train.py and the
    # autotuner build through.
    mesh = mesh_lib.ParallelConfig(
        data=num_devices if num_devices else -1).build_mesh()
    model = MnistMLP(hidden_units=hidden)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))["params"]
    apply_fn = lambda p, x: model.apply({"params": p}, x)
    state = TrainState.create(apply_fn, params, gradient_descent(lr))
    state = state.replace(
        params=replicate_tree(mesh, state.params),
        opt_state=replicate_tree(mesh, state.opt_state),
        global_step=replicate_tree(mesh, state.global_step),
    )

    def loss_fn(p, batch):
        x, y = batch
        logits = apply_fn(p, x)
        return cross_entropy_loss(logits, y), {"accuracy": accuracy(logits, y)}

    step = sync_lib.build_sync_train_step(mesh, loss_fn)
    sharding = mesh_lib.data_sharded(mesh)

    rng = np.random.default_rng(0)
    xs = rng.random((batch_size, 784), np.float32)
    ys = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch_size)]
    return mesh, state, step, apply_fn, sharding, loss_fn, (xs, ys)


def bench_framework(state, step, sharding, host_batch, iters=200, trials=5,
                    sync_every=0):
    """``sync_every`` > 0 fetches a scalar every that many steps, bounding
    the async in-flight queue (XLA:CPU's in-process collective rendezvous
    deadlocks past ~100 queued all-reduces; irrelevant on TPU)."""
    import jax
    batch = tuple(jax.device_put(a, sharding) for a in host_batch)
    for _ in range(5):
        state, metrics = step(state, batch)
    _sync(metrics)
    holder = {"state": state}

    def run(n):
        st = holder["state"]
        for i in range(n):
            st, metrics = step(st, batch)
            if sync_every and (i + 1) % sync_every == 0:
                _sync(metrics)
        holder["state"] = st
        _sync(metrics)

    return _median_rate(run, iters, trials)


def bench_reference_style(state, apply_fn, sharding, host_batch, lr=0.01,
                          iters=40, trials=3):
    """The reference's per-step protocol, faithfully: feed, train op, then a
    *separate* accuracy forward on the same batch, blocking on both
    (``distributed.py:137-153``)."""
    import jax
    import optax

    from distributed_tensorflow_tpu.models.mlp import (
        accuracy, cross_entropy_loss)

    tx = optax.sgd(lr)
    opt_state = tx.init(state.params)
    params = state.params

    @jax.jit
    def train_op(params, opt_state, x, y):
        def loss_fn(p):
            return cross_entropy_loss(apply_fn(p, x), y)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def acc_op(params, x, y):
        return accuracy(apply_fn(params, x), y)

    xs, ys = host_batch
    for _ in range(3):
        params, opt_state, loss = train_op(
            params, opt_state, jax.device_put(xs, sharding),
            jax.device_put(ys, sharding))
        float(loss)
    holder = {"params": params, "opt": opt_state}

    def run(n):
        p, o = holder["params"], holder["opt"]
        for _ in range(n):
            # fresh host feed each step (feed_dict, distributed.py:137-138)
            x = jax.device_put(xs, sharding)
            y = jax.device_put(ys, sharding)
            p, o, loss = train_op(p, o, x, y)
            float(loss)            # blocking fetch (per-step print)
            float(acc_op(p, x, y))  # 2nd forward (distributed.py:148)
        holder["params"], holder["opt"] = p, o

    return _median_rate(run, iters, trials)


def run_mnist(results):
    import jax
    n_chips = len(jax.devices())
    mesh, state, step, apply_fn, sharding, loss_fn, host_batch = build_mnist()
    ref = bench_reference_style(state, apply_fn, sharding, host_batch)
    fw = bench_framework(state, step, sharding, host_batch)
    results["mnist_steps_per_sec_per_chip"] = round(fw / n_chips, 2)
    results["mnist_reference_protocol_steps_per_sec"] = round(ref, 2)
    results["mnist_vs_reference_protocol"] = round(fw / ref, 3)
    return fw / n_chips, fw / ref


def run_feed(results):
    """Fresh host→device feed every step (the reference's feed_dict path,
    ``distributed.py:137-138``): float32 vs uint8 image transfer
    (--feed_dtype=uint8 — 4x fewer bytes, /255 on device)."""
    import jax

    bs = 1024
    mesh, state, step, apply_fn, sharding, loss_fn, _ = build_mnist(
        batch_size=bs)
    rng = np.random.default_rng(0)
    xs_f = rng.random((bs, 784), np.float32)
    xs_u = np.rint(xs_f * 255).astype(np.uint8)
    ys = np.eye(10, dtype=np.float32)[rng.integers(0, 10, bs)]

    holder = {"state": state}

    def rate_for(host_images, iters=60, trials=3):
        def run(n):
            st = holder["state"]
            for _ in range(n):
                batch = (jax.device_put(host_images, sharding),
                         jax.device_put(ys, sharding))
                st, metrics = step(st, batch)
            holder["state"] = st
            _sync(metrics)
        run(5)  # warm both compiles
        return _median_rate(run, iters, trials)

    f_rate = rate_for(xs_f)
    u_rate = rate_for(xs_u)
    results["feed_float32_steps_per_sec"] = round(f_rate, 2)
    results["feed_uint8_steps_per_sec"] = round(u_rate, 2)
    results["feed_uint8_speedup"] = round(u_rate / f_rate, 3)
    results["feed_batch_bytes"] = {"float32": xs_f.nbytes,
                                   "uint8": xs_u.nbytes}


def run_scanned(results):
    """--steps_per_call ablation: K optimizer steps per dispatch vs 1."""
    import jax

    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib

    K = 16
    mesh, state, step, apply_fn, sharding, loss_fn, host_batch = build_mnist()
    plain = bench_framework(state, step, sharding, host_batch,
                            iters=128, trials=3)

    mesh2, state2, _, _, _, loss_fn2, host_batch2 = build_mnist()
    scanned = sync_lib.build_scanned_sync_train_step(
        mesh2, loss_fn2, num_steps=K)
    stacked = tuple(np.broadcast_to(a, (K,) + a.shape) for a in host_batch2)
    sh = mesh_lib.stacked_batch_sharding(mesh2)
    batch = tuple(jax.device_put(a, sh) for a in stacked)
    for _ in range(3):
        state2, metrics = scanned(state2, batch)
    _sync(metrics)
    holder = {"state": state2}

    def run(n):
        st = holder["state"]
        for _ in range(n):
            st, metrics = scanned(st, batch)
        holder["state"] = st
        _sync(metrics)

    chunk_rate = _median_rate(run, 16, 3)  # dispatches/sec
    results["scanned_steps_per_call"] = K
    results["scanned_steps_per_sec"] = round(chunk_rate * K, 2)
    results["plain_steps_per_sec"] = round(plain, 2)
    results["scanned_speedup"] = round(chunk_rate * K / plain, 3)


def run_converge(results):
    """Wall-clock-to-convergence on the reference workload.

    The reference's only test is convergence-as-test (SURVEY §4): watch
    loss/accuracy while training 100000 steps at batch 100
    (``distributed.py:11-14,140-165``).  This records how fast the
    framework's step loop saturates the same-shaped job — steps and seconds
    to the validation-accuracy threshold, final test accuracy — plus the
    *projected* time for the same number of steps under the reference's
    per-step protocol measured on this same hardware (run_mnist's
    ``mnist_reference_protocol_steps_per_sec``).  The dataset is whatever
    ``read_data_sets`` resolves (real MNIST IDX files when present, the
    deterministic synthetic stand-in otherwise — recorded in
    ``converge_dataset``; absolute accuracies are only comparable across
    runs of the same dataset).
    """
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.data.datasets import read_data_sets

    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib

    mesh, state, _, apply_fn, _, loss_fn, _ = build_mnist()
    ds = read_data_sets("/nonexistent")   # synthetic fallback (zero egress)
    threshold, cap, bs, K = 0.97, 3000, 100, 50   # K = --steps_per_call
    scanned = sync_lib.build_scanned_sync_train_step(
        mesh, loss_fn, num_steps=K)
    st_sharding = mesh_lib.stacked_batch_sharding(mesh)

    eval_fn = jax.jit(
        lambda p, x, y: jnp.mean(
            (jnp.argmax(apply_fn(p, x), -1) == jnp.argmax(y, -1))
            .astype(jnp.float32)))

    def stacked_batch():
        xs, ys = zip(*(ds.train.next_batch(bs) for _ in range(K)))
        return tuple(
            jax.device_put(np.stack(a), st_sharding) for a in (xs, ys))

    # Device-resident eval splits, uploaded once outside the timed region.
    val = tuple(jnp.asarray(a) for a in (ds.validation.images,
                                         ds.validation.labels))
    tst = tuple(jnp.asarray(a) for a in (ds.test.images, ds.test.labels))

    # Warm the jit dispatch caches outside the timed region: the scanned
    # step donates its input state, so the warm call runs on a throwaway
    # copy and the timed loop starts from the genuine step-0 state.
    warm = stacked_batch()
    _sync(scanned(jax.tree.map(jnp.copy, state), warm)[1])
    _sync(eval_fn(state.params, *val))
    holder = {"state": state}
    steps_done, reached = 0, None
    t0 = time.perf_counter()
    while steps_done < cap:
        holder["state"], metrics = scanned(
            holder["state"], warm if steps_done == 0 else stacked_batch())
        _sync(metrics)
        steps_done += K
        if float(eval_fn(holder["state"].params, *val)) >= threshold:
            reached = steps_done
            break
    elapsed = time.perf_counter() - t0
    test_acc = float(eval_fn(holder["state"].params, *tst))

    results["converge_dataset"] = "synthetic" if ds.synthetic else "mnist"
    results["converge_threshold_validation_acc"] = threshold
    results["converge_steps_per_call"] = K
    results["converge_steps"] = reached if reached is not None else steps_done
    results["converge_reached"] = reached is not None
    results["converge_seconds"] = round(elapsed, 2)
    results["converge_final_test_acc"] = round(test_acc, 4)
    # Projection against the reference per-step protocol rate: prefer this
    # run's measurement, else the recorded artifact's; drop (None) both keys
    # when neither exists so stale projections never outlive their inputs.
    ref_rate = results.get("mnist_reference_protocol_steps_per_sec")
    if not ref_rate:
        try:
            with open(os.path.join(REPO, "BENCH_DETAILS.json")) as fh:
                ref_rate = json.load(fh)["extra"].get(
                    "mnist_reference_protocol_steps_per_sec")
        except Exception:
            ref_rate = None
    proj = ((reached or steps_done) / ref_rate) if ref_rate else None
    results["converge_reference_protocol_projected_seconds"] = (
        round(proj, 1) if proj else None)
    results["converge_speedup_vs_reference_protocol"] = (
        round(proj / max(elapsed, 1e-9), 1) if proj else None)


# ---------------------------------------------------------- transformer


#: run_transformer stashes its compiled flagship step here so run_profile
#: can trace it without paying a second multi-minute compile.
_GPT_STEP_CACHE: dict = {}


def _gpt_train_rate(backend: str, B: int, S: int = 1024, window: int = 0,
                    num_layers: int = 8, iters: int = 20,
                    out_cache: dict | None = None,
                    matmul_int8: bool = False,
                    attn_int8: bool = False):
    """One GPT train-step measurement; returns (rate, tflops, n_params, cfg).

    ``out_cache`` (a dict) receives ``{step, holder, batch}`` so a later
    bench arm can reuse the compiled step (e.g. the profiler)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib
    from distributed_tensorflow_tpu.parallel.sharding import replicate_tree
    from distributed_tensorflow_tpu.training.optimizers import make_optimizer
    from distributed_tensorflow_tpu.training.state import TrainState

    cfg = dataclasses.replace(
        gpt_lib.mini(), hidden_size=2048, num_layers=num_layers,
        num_heads=16, intermediate_size=8192, max_position=S,
        dtype="bfloat16", attention_backend=backend,
        attention_window=window, matmul_int8=matmul_int8,
        attn_int8=attn_int8)
    model = gpt_lib.GptLM(cfg)
    mesh = mesh_lib.data_parallel_mesh()

    tokens = jnp.asarray(
        gpt_lib.synthetic_lm_batch(0, B, S, cfg)["tokens"])
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]
    apply_fn = lambda p, t: model.apply({"params": p}, t)
    state = TrainState.create(apply_fn, params, make_optimizer("adam", 3e-4))
    state = state.replace(
        params=replicate_tree(mesh, state.params),
        opt_state=replicate_tree(mesh, state.opt_state),
        global_step=replicate_tree(mesh, state.global_step))

    def loss_fn(p, batch):
        loss, acc = gpt_lib.lm_loss(apply_fn(p, batch), batch)
        return loss, {"accuracy": acc}

    step = sync_lib.build_sync_train_step(mesh, loss_fn)
    batch = jax.device_put(tokens, mesh_lib.data_sharded(mesh))
    for _ in range(3):
        state, metrics = step(state, batch)
    _sync(metrics)
    holder = {"state": state}

    def run(n):
        st = holder["state"]
        for _ in range(n):
            st, metrics = step(st, batch)
        holder["state"] = st
        _sync(metrics)

    rate = _median_rate(run, iters, 5)  # steps/sec
    if out_cache is not None:
        out_cache.update(step=step, holder=holder, batch=batch, cfg=cfg, B=B)

    # Analytic matmul FLOPs per forward pass (dense layers + attention;
    # standard MFU convention — full S x S attention work credited
    # identically for both backends; a sliding window caps each query's
    # key length at window+1, so windowed runs are credited only the work
    # the band actually does).
    H, L, I, V = cfg.hidden_size, cfg.num_layers, cfg.intermediate_size, \
        cfg.vocab_size
    kv_len = min(S, window + 1) if window else S
    per_layer = (2 * B * S * H * 3 * H          # qkv proj
                 + 2 * B * S * H * H            # out proj
                 + 2 * 2 * B * S * kv_len * H   # scores + values
                 + 2 * 2 * B * S * H * I)       # mlp in + out
    fwd = L * per_layer + 2 * B * S * H * V  # + lm head
    tflops = 3 * fwd * rate / 1e12           # bwd ~= 2x fwd
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    return rate, tflops, n_params, cfg


def run_decode(results):
    """KV-cached GPT decode rate, bf16 weights vs int8 weight-only.

    Decode is HBM-bandwidth-bound: every token re-reads the full weight set,
    so halving the weight bytes (`ops/quant.py`, ``--gen_quantize=int8``) is
    the decode-rate lever this measures.  (The int8 path re-quantizes inside
    the jitted call — a ~2% conservative penalty against itself.)
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    cfg = dataclasses.replace(
        gpt_lib.mini(), hidden_size=2048, num_layers=8, num_heads=16,
        intermediate_size=8192, max_position=256, dtype="bfloat16")
    model = gpt_lib.GptLM(cfg)
    B, P, T = 8, 16, 64
    prompt = jnp.asarray(gpt_lib.synthetic_lm_batch(0, B, P, cfg)["tokens"])
    # flax init leaves params float32 (param_dtype default); cast so the
    # baseline arm really reads 2-byte weights — the honest comparison.
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        model.init(jax.random.PRNGKey(0), prompt[:1, :8])["params"])

    def seconds_per_call(mdl, p_tree, pr, gen_tokens, quantize, kv_dtype,
                         iters, trials=3):
        """Median wall seconds per generate_cached call — ONE timing
        protocol for every decode arm (jit, warm call, chained runs,
        scalar-fetch barrier)."""
        fn = jax.jit(lambda p, q: gpt_lib.generate_cached(
            mdl, p, q, gen_tokens, quantize=quantize,
            kv_dtype=kv_dtype)[:, -1].sum())
        _sync(fn(p_tree, pr))  # compile + warm

        def run(n):
            out = None
            for _ in range(n):
                out = fn(p_tree, pr)
            _sync(out)

        return 1.0 / _median_rate(run, iters, trials)

    def bench(quantize, kv_dtype=""):
        sec = seconds_per_call(model, params, prompt, T, quantize, kv_dtype,
                               iters=5)
        return B * T / sec   # generated tokens/sec

    bf16 = bench("")
    int8 = bench("int8")
    int8_fp8 = bench("int8", kv_dtype="float8")
    results["decode_config"] = (f"L={cfg.num_layers} H={cfg.hidden_size} "
                                f"I={cfg.intermediate_size} B={B} prompt={P} "
                                f"gen={T} bf16 weights+activations+kv vs "
                                "int8 weights (+float8 kv)")
    results["decode_bf16_tokens_per_sec"] = round(bf16, 1)
    results["decode_int8_tokens_per_sec"] = round(int8, 1)
    results["decode_int8_speedup"] = round(int8 / bf16, 3)
    results["decode_int8_fp8kv_tokens_per_sec"] = round(int8_fp8, 1)
    results["decode_int8_fp8kv_speedup"] = round(int8_fp8 / bf16, 3)

    # Long-context arm: at prompt 1984 the KV cache reads rival the (int8)
    # weight reads, so the float8 cache's halved bytes become visible.
    cfgL = dataclasses.replace(cfg, max_position=2048)
    modelL = gpt_lib.GptLM(cfgL)
    BL, PL, TL = 4, 1984, 32
    promptL = jnp.asarray(
        gpt_lib.synthetic_lm_batch(1, BL, PL, cfgL)["tokens"])
    # Fresh init: the short-arm params carry a 256-entry position table.
    paramsL = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        modelL.init(jax.random.PRNGKey(1), promptL[:1, :8])["params"])

    def bench_long(kv_dtype, mdl=None, p_tree=None):
        """Pure DECODE tokens/sec at long context: the (arm-identical)
        prefill cost is subtracted by differencing a short-gen and a
        long-gen run of the same program shape.

        Differencing is noise-sensitive: when the
        decode delta isn't clearly above the timing noise (10% of the
        long run AND 10 ms absolute), retry with a 3x longer generation
        (decode then dominates); a still-unreliable measurement returns
        None rather than publishing a garbage ratio (a near-zero
        denominator once produced a fictitious 25x)."""
        mdl = modelL if mdl is None else mdl
        p_tree = paramsL if p_tree is None else p_tree
        for gen in (TL, min(3 * TL, 2048 - PL)):
            t_short = seconds_per_call(mdl, p_tree, promptL, 4, "int8",
                                       kv_dtype, iters=3)
            t_long = seconds_per_call(mdl, p_tree, promptL, gen, "int8",
                                      kv_dtype, iters=3)
            delta = t_long - t_short
            if delta > max(0.1 * t_long, 0.010):
                return BL * (gen - 4) / delta
        return None

    long_bf16kv = bench_long("")
    long_fp8kv = bench_long("float8")
    results["decode_long_config"] = (f"int8 weights, B={BL} prompt={PL} "
                                     f"gen={TL}: bf16 kv vs float8 kv "
                                     "(prefill cost differenced out; "
                                     "noise-guarded, None = unreliable)")
    results["decode_long_bf16kv_tokens_per_sec"] = (
        round(long_bf16kv, 1) if long_bf16kv else None)
    results["decode_long_fp8kv_tokens_per_sec"] = (
        round(long_fp8kv, 1) if long_fp8kv else None)
    results["decode_long_fp8kv_speedup"] = (
        round(long_fp8kv / long_bf16kv, 3)
        if long_bf16kv and long_fp8kv else None)

    # GQA arm: 4 kv heads (of 16) + float8 cache — the cache-bytes levers
    # compounded (a different model, so it carries its own params; the
    # comparison is against the MHA bf16-kv rate above at identical shapes).
    cfgG = dataclasses.replace(cfgL, kv_heads=4)
    modelG = gpt_lib.GptLM(cfgG)
    paramsG = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        modelG.init(jax.random.PRNGKey(2), promptL[:1, :8])["params"])

    gqa_fp8 = bench_long("float8", mdl=modelG, p_tree=paramsG)
    results["decode_long_gqa4_fp8kv_tokens_per_sec"] = (
        round(gqa_fp8, 1) if gqa_fp8 else None)
    results["decode_long_gqa4_fp8kv_vs_mha_bf16kv"] = (
        round(gqa_fp8 / long_bf16kv, 3)
        if gqa_fp8 and long_bf16kv else None)

    # Sliding-window ring-cache arm: with --attention_window=1024 the
    # decode cache is a 1024-entry ring instead of 2016 rows, so every
    # step's cache reads (and its bytes resident) halve at this prompt —
    # and stay CONSTANT for longer ones.  Different model (banded
    # attention), same shapes; compare against the full-cache MHA bf16
    # rate above.
    cfgW = dataclasses.replace(cfgL, attention_window=1024)
    modelW = gpt_lib.GptLM(cfgW)
    paramsW = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        modelW.init(jax.random.PRNGKey(3), promptL[:1, :8])["params"])
    ring = bench_long("", mdl=modelW, p_tree=paramsW)
    results["decode_long_w1024_ring_tokens_per_sec"] = (
        round(ring, 1) if ring else None)
    results["decode_long_w1024_ring_vs_full_cache"] = (
        round(ring / long_bf16kv, 3) if ring and long_bf16kv else None)


def run_transformer(results):
    """GPT train step at an MXU-loading size: step time, TFLOP/s, MFU.

    Flagship: the pallas flash backend, which both fits a 2x larger batch
    than dense attention (no [B, heads, S, S] scores saved for the backward
    — dense OOMs at B=8 on this chip) and outruns it end-to-end with the
    512-wide kernel blocks.  The dense-attention path at its own largest
    batch is recorded alongside as the baseline.
    """
    import jax

    peak = _peak_tflops()
    for tag, backend, B in (("gpt", "pallas", 8), ("gpt_dense", "xla", 4)):
        cache = _GPT_STEP_CACHE if backend == "pallas" else None
        rate, tflops, n_params, cfg = _gpt_train_rate(backend, B, iters=10,
                                                      out_cache=cache)
        results[f"{tag}_bench_config"] = (
            f"L={cfg.num_layers} H={cfg.hidden_size} "
            f"I={cfg.intermediate_size} B={B} S={cfg.max_position} bf16 "
            f"attn={backend} params={n_params/1e6:.1f}M")
        results[f"{tag}_step_ms"] = round(1000.0 / rate, 2)
        results[f"{tag}_tokens_per_sec"] = round(
            rate * B * cfg.max_position, 0)
        results[f"{tag}_model_tflops_per_sec"] = round(tflops, 2)
        if peak:
            results[f"{tag}_mfu_pct"] = round(100.0 * tflops / peak, 2)
    if peak:
        results["chip_peak_bf16_tflops"] = peak
    results["device_kind"] = jax.devices()[0].device_kind


def run_transformer_long(results):
    """Long-context model-level arm: the GPT family at S=8192 (B=1, 4
    layers to fit), full causal flash vs --attention_window=1024 — the
    model-level record of the banded kernel's win (the kernel-level one
    lives under --mode flash)."""
    # Derived keys default to None (dropped by the merge) so a failed arm
    # can never leave a stale speedup next to fresh step times.
    results["gpt_long_window_speedup"] = None
    results["gpt_long_config"] = None
    for tag, window in (("gpt_long", 0), ("gpt_long_w1024", 1024)):
        try:
            rate, tflops, n_params, cfg = _gpt_train_rate(
                "pallas", 1, S=8192, window=window, num_layers=4, iters=5)
            results[f"{tag}_step_ms"] = round(1000.0 / rate, 2)
            results[f"{tag}_tokens_per_sec"] = round(rate * 8192, 0)
            results[f"{tag}_error"] = None     # clear a prior run's failure
        except Exception as e:
            results[f"{tag}_error"] = repr(e)[:200]
    if "gpt_long_step_ms" in results and "gpt_long_w1024_step_ms" in results:
        results["gpt_long_window_speedup"] = round(
            results["gpt_long_step_ms"] / results["gpt_long_w1024_step_ms"],
            2)
        results["gpt_long_config"] = ("L=4 H=2048 I=8192 B=1 S=8192 bf16 "
                                      "flash full vs window=1024")


def run_profile(results):
    """Per-op device-time profile of the flagship GPT train step.

    Captures a real jax.profiler trace (parsed by ``utils.xplane`` — no
    tensorboard needed) and records where the step's device time goes:
    matmul vs attention-kernel vs elementwise vs data movement, plus the
    device's intra-module idle.  This is the evidence behind the MFU
    number — and the map for the next optimization (VERDICT r2 miss #2).
    """
    from distributed_tensorflow_tpu.utils.xplane import profile_breakdown

    cache = dict(_GPT_STEP_CACHE)
    # Whatever happens below, the cached flagship state (params + Adam
    # slots + batch — several GB of HBM) must not outlive this arm.
    _GPT_STEP_CACHE.clear()
    if not cache:
        _gpt_train_rate("pallas", 8, iters=3, out_cache=cache)
    step, holder, batch = cache["step"], cache["holder"], cache["batch"]

    def one_step():
        holder["state"], metrics = step(holder["state"], batch)
        _sync(metrics)

    # Keep the raw trace on disk and record its path in the artifact, so
    # the BENCH numbers point at the profile of the exact run that
    # produced them (previously the trace lived in an unnamed temp dir and
    # the breakdown below was the only survivor).  A fresh mkdtemp per
    # run: concurrent/multi-user bench runs never clobber each other's
    # evidence, and the artifact names exactly the dir THIS run wrote.
    import tempfile
    trace_dir = tempfile.mkdtemp(prefix="dtf_bench_gpt_profile_")
    prof = profile_breakdown(one_step, warmup=1, iters=4, logdir=trace_dir)
    import glob
    xplane_files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    n = prof["iters"]  # buckets/top_ops are totals over the traced calls
    results["gpt_step_profile"] = {
        "buckets_pct": prof["buckets_pct"],
        "buckets_ms_per_step": {k: round(v / n, 3)
                                for k, v in prof["buckets_ms"].items()},
        "device_ms_per_step": prof["module_ms_per_call"],
        "intra_module_idle_pct": prof["intra_module_idle_pct"],
        "top_ops_ms_per_step": [[name[:48], round(ms / n, 3)]
                                for name, ms in prof["top_ops"][:6]],
        "config": "flagship pallas GPT step (run_transformer's gpt arm)",
        "trace_dir": prof["trace_dir"],
        "xplane_files": xplane_files,
    }


def run_mfu_ladder(results):
    """End-to-end train MFU over sequence length (VERDICT r2: one MFU point
    is not a perf story).  S=1024 comes from ``transformer``'s flagship
    arm; this arm adds S=4096 and S=8192 full-causal vs window=1024 (the
    shapes where the long-context kernels matter).  Windowed rungs are
    credited only the attention work the band does, so their MFU is
    comparable, not inflated."""
    peak = _peak_tflops()
    ladder = (("mfu_s4096", 4096, 2, 0, 8),
              ("mfu_s8192", 8192, 1, 0, 4),
              ("mfu_s8192_w1024", 8192, 1, 1024, 4))
    by_seq = {}
    for tag, S, B, window, L in ladder:
        try:
            rate, tflops, n_params, cfg = _gpt_train_rate(
                "pallas", B, S=S, window=window, num_layers=L, iters=5)
            entry = {
                "step_ms": round(1000.0 / rate, 2),
                "tokens_per_sec": round(rate * B * S, 0),
                "model_tflops_per_sec": round(tflops, 2),
                "config": (f"L={L} H=2048 I=8192 B={B} S={S} bf16 pallas"
                           + (f" window={window}" if window else "")),
            }
            if peak:
                entry["mfu_pct"] = round(100.0 * tflops / peak, 2)
            by_seq[tag] = entry
        except Exception as e:
            by_seq[tag] = {"error": repr(e)[:200]}
    results["mfu_by_seq"] = by_seq


def run_async_exchange(results):
    """Cross-process async exchange bandwidth at transformer scale.

    Publishes parameter trees through the real coordination service +
    logdir binary side-channel (``cluster/param_sync.py``) and peers read
    them back — the reference-PS "move the full model" operation
    (``distributed.py:145``) measured end to end, host-side (no chip).

    Three sub-arms (VERDICT r3 #5):

    - 108 MB float32, 2 workers / 1 peer — continuity with the r3 record
      (``async_exchange_mb_per_sec``);
    - the SAME 27M parameters as bf16 — payloads now travel in the params'
      own dtype, so the model-level exchange should take ~half the time
      (``async_exchange_bf16_model_speedup``);
    - a >=1 GB bf16 tree across 3 workers — 2 live peers publish, then the
      measured worker's full exchange (publish + read both peers +
      average) is timed (``async_exchange_1gb_*``);
    - overlap (r5, VERDICT r4 #5): device-side training throughput WHILE
      the same 1 GB exchange runs in the OverlappedAverager background
      thread, as a ratio over the no-exchange rate
      (``async_overlap_train_ratio`` — the >=0.8 bar).  This host is a
      SINGLE-core VM (the config string records it), so running the three
      exchanges in threads would only time-slice one core and triple the
      wall-clock without exercising anything extra; the measured worker's
      exchange against 2 live publications is the honest per-worker cost.
    """
    import os as _os
    import tempfile
    import time as _time

    import ml_dtypes

    from distributed_tensorflow_tpu.cluster.coordination import (
        CoordinationClient, CoordinationServer)
    from distributed_tensorflow_tpu.cluster.param_sync import ParamAverager

    bf16 = np.dtype(ml_dtypes.bfloat16)
    rng = np.random.default_rng(0)
    base = rng.standard_normal((27_000_000,)).astype(np.float32)

    def big_tree(n, dtype):
        """n-element array at memcpy speed: tiled random megablock (content
        doesn't matter to the IO path — the binary channel doesn't
        compress; generating 550M true randoms costs ~20 s of pure CPU)."""
        tile = rng.standard_normal(1 << 20).astype(np.float32).astype(dtype)
        reps = -(-n // tile.size)
        return np.tile(tile, reps)[:n]

    def timed_pair_exchange(tree):
        """2 workers, 1 measured exchange; returns (seconds, peers, pub)."""
        server = CoordinationServer(port=0, num_tasks=2)
        server.start()
        tmp = tempfile.mkdtemp(prefix="dtf_async_bench_")
        try:
            clients = [CoordinationClient("127.0.0.1", server.port, t)
                       for t in range(2)]
            for c in clients:
                c.register()
            avgs = [ParamAverager(c, t, 2, exchange_dir=tmp)
                    for t, c in enumerate(clients)]
            avgs[0].exchange(tree)
            t0 = _time.perf_counter()
            _, peers = avgs[1].exchange(tree)
            dt = _time.perf_counter() - t0
            pub = avgs[1].last_publish_mb_per_sec
            transport = avgs[1].last_publish_transport
            for c in clients:
                c.close()
            return dt, peers, pub, transport
        finally:
            server.stop()
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)

    # --- 108 MB float32 (r3-comparable record) ---
    mb = base.nbytes / 1e6
    f32_s, peers, pub, transport = timed_pair_exchange({"w": base})
    results["async_exchange_config"] = (
        f"{mb:.0f} MB float32 tree, coordination service + logdir "
        f"binary side-channel, transport={transport}")
    results["async_exchange_peers"] = peers
    results["async_publish_mb_per_sec"] = round(pub, 1)
    # Full exchange = publish + read peer + average, both directions of
    # data touched once.
    results["async_exchange_mb_per_sec"] = round(2 * mb / f32_s, 1)

    # --- same 27M params, bf16: the native-dtype win at model level ---
    bf = {"w": base.astype(bf16)}
    bf_s, _, _, _ = timed_pair_exchange(bf)
    results["async_exchange_bf16_seconds"] = round(bf_s, 2)
    results["async_exchange_bf16_model_speedup"] = round(f32_s / bf_s, 2)

    # --- >=1 GB bf16 tree, 3 workers exchanging concurrently ---
    big = {"w": big_tree(550_000_000, bf16)}
    gb = big["w"].nbytes / 1e9
    server = CoordinationServer(port=0, num_tasks=3)
    server.start()
    # Single-host multi-process workers (this rig's topology) exchange
    # through any local dir — use tmpfs so the measurement is the
    # protocol, not this VM's ~120 MB/s disk.  Cross-host deployments put
    # exchange_dir on the shared FS and ride its bandwidth instead; the
    # 108 MB arm above stays disk-backed as that record.
    shm = "/dev/shm"
    base_dir = shm if os.path.isdir(shm) else None
    tmp = tempfile.mkdtemp(prefix="dtf_async_bench_1gb_", dir=base_dir)
    try:
        clients = [CoordinationClient("127.0.0.1", server.port, t)
                   for t in range(3)]
        for c in clients:
            c.register()
        avgs = [ParamAverager(c, t, 3, exchange_dir=tmp)
                for t, c in enumerate(clients)]
        avgs[1].exchange(big)          # both peers publish first
        avgs[2].exchange(big)
        t0 = _time.perf_counter()      # measured: full exchange, 2 peers in
        _, peers = avgs[0].exchange(big)
        dt = _time.perf_counter() - t0
        results["async_exchange_1gb_config"] = (
            f"{gb:.2f} GB bf16 tree, 3 workers (2 live peers averaged), "
            f"binary side-channel on "
            f"{'tmpfs (single-host)' if base_dir else 'disk'}, "
            f"{_os.cpu_count()}-core host")
        results["async_exchange_1gb_peers"] = peers
        results["async_exchange_1gb_seconds"] = round(dt, 2)
        # Payload bytes the measured worker touched: its publish plus one
        # read per averaged peer.
        results["async_exchange_1gb_mb_per_sec"] = round(
            (1 + peers) * gb * 1000 / dt, 1)

        # --- overlap (VERDICT r4 #5): device training throughput WHILE
        # the same 1 GB exchange runs in the background thread
        # (OverlappedAverager) vs with no exchange in flight.  The
        # exchange is host I/O; the step is device compute — they should
        # overlap to >=0.8x.  TPU only (on CPU the step and the exchange
        # would time-slice one core and measure the scheduler).
        import jax
        import jax.numpy as jnp
        if jax.default_backend() == "tpu":
            from distributed_tensorflow_tpu.cluster.param_sync import (
                OverlappedAverager)
            k = jax.random.PRNGKey(0)
            w = jax.random.normal(k, (4096, 4096), jnp.bfloat16)
            x0 = jax.random.normal(k, (4096, 4096), jnp.bfloat16)

            @jax.jit
            def step_chain(x):
                def body(c, _):
                    c = jnp.tanh(c @ w)
                    return c, None
                c, _ = jax.lax.scan(body, x, None, length=8)
                return c

            def rate(seconds):
                """steps/sec over ~`seconds`, pipelined (queue 4, one
                scalar fetch) — the pipelined protocol from BASELINE.md."""
                nonlocal x0
                n = 0
                t0 = _time.perf_counter()
                while _time.perf_counter() - t0 < seconds:
                    for _ in range(4):
                        x0 = step_chain(x0)
                    float(jnp.sum(x0[0, :8]))
                    n += 4
                return n / (_time.perf_counter() - t0)

            _sync(step_chain(x0))            # compile + warm
            base_rate = rate(4.0)
            ov = OverlappedAverager(avgs[0],
                                    print_fn=lambda *_: None)
            ov.step_period(big)              # launch the 1 GB exchange
            n = 0
            t0 = _time.perf_counter()
            got = None
            while got is None:
                for _ in range(4):
                    x0 = step_chain(x0)
                float(jnp.sum(x0[0, :8]))
                n += 4
                got = ov.drain(timeout=0.001)
                if _time.perf_counter() - t0 > 180:
                    break
            inflight = _time.perf_counter() - t0
            during_rate = n / inflight
            ov.close()
            if got is None:
                # The exchange never finished inside the cap: recording a
                # ratio over a truncated window would claim an overlap
                # measurement that didn't happen.
                results["async_overlap_note"] = (
                    f"background exchange still running after "
                    f"{inflight:.0f}s cap — no ratio recorded")
            else:
                results["async_overlap_exchange_seconds"] = round(
                    inflight, 2)
                results["async_overlap_train_ratio"] = round(
                    during_rate / base_rate, 3)
                results["async_overlap_config"] = (
                    f"{gb:.2f} GB background exchange ({got[2]} peers) vs "
                    "4096^2 bf16 matmul-chain steps on the chip; ratio = "
                    "steps/sec during in-flight exchange / baseline")
        else:
            results["async_overlap_note"] = (
                "overlap sub-arm needs the TPU (device compute vs host IO;"
                " on CPU both time-slice one core)")
        for c in clients:
            c.close()
    finally:
        server.stop()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)


def run_param_exchange(results):
    """Compressed sharded exchange vs fp32 full-state: 2 local workers
    against a REAL coordinator, same MLP workload, same seeds — measuring
    exchange latency, bytes-on-wire, compression ratio, and convergence
    parity (ISSUE 5 acceptance: >=4x fewer wire bytes at loss within 2%).

    Host-side like run_async_exchange (the exchange is control-plane +
    host math; no chip involved): each arm trains two local-SGD model
    copies on disjoint data shards and exchanges every ``period`` steps
    through ``cluster/param_sync.py`` — the fp32 arm via ParamAverager
    (full-state mirroring), the compressed arm via
    CompressedShardedAverager (delta + error-feedback int8 + sharded
    reduce over the same KV plane).
    """
    import tempfile
    import time as _time

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.cluster.coordination import (
        CoordinationClient, CoordinationServer)
    from distributed_tensorflow_tpu.cluster.param_sync import (
        CompressedShardedAverager, ParamAverager)

    rng = np.random.default_rng(0)
    w_true = rng.standard_normal((64, 8)).astype(np.float32)

    def make_data(n, offset):
        x = rng.standard_normal((n, 64)).astype(np.float32) + offset
        y = np.argmax(x @ w_true, axis=1)
        return x, y

    data = [make_data(512, -0.1), make_data(512, 0.1)]
    x_test, y_test = make_data(1024, 0.0)

    def init_params():
        k1, k2 = jax.random.split(jax.random.PRNGKey(7))
        # ~0.6M params: big enough that wire bytes dominate KV framing.
        return {"w1": np.asarray(jax.random.normal(k1, (64, 2048)) * 0.05),
                "b1": np.zeros((2048,), np.float32),
                "w2": np.asarray(jax.random.normal(k2, (2048, 8)) * 0.05),
                "b2": np.zeros((8,), np.float32)}

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(logp[jnp.arange(x.shape[0]), y])

    grad = jax.jit(jax.grad(loss_fn))
    loss_jit = jax.jit(loss_fn)

    def run_arm(factory, steps=60, period=5):
        server = CoordinationServer(port=0, num_tasks=2)
        server.start()
        tmp = tempfile.mkdtemp(prefix="dtf_param_exchange_bench_")
        try:
            clients = [CoordinationClient("127.0.0.1", server.port, t)
                       for t in range(2)]
            for c in clients:
                c.register()
            avgs = [factory(c, t, tmp) for t, c in enumerate(clients)]
            params = [init_params(), init_params()]
            exchange_s = []
            for step in range(steps):
                for t in (0, 1):
                    x, y = data[t]
                    lo = (step * 64) % 448
                    g = grad(params[t], x[lo:lo + 64], y[lo:lo + 64])
                    params[t] = jax.tree.map(
                        lambda p, gg: np.asarray(p - 0.2 * gg),
                        params[t], g)
                if (step + 1) % period == 0:
                    for t in (0, 1):
                        t0 = _time.perf_counter()
                        out, _ = avgs[t].exchange(params[t])
                        exchange_s.append(_time.perf_counter() - t0)
                        params[t] = jax.tree.map(np.asarray, out)
            final = jax.tree.map(
                lambda a, b: (np.asarray(a, np.float32)
                              + np.asarray(b, np.float32)) / 2, *params)
            loss = float(loss_jit(final, x_test, y_test))
            wire = sum(a.total_bytes_out + a.total_bytes_in for a in avgs)
            rounds = max(getattr(a, "rounds_completed", 0) for a in avgs)
            stages = dict(getattr(avgs[0], "last_stage_ms", {}) or {})
            for c in clients:
                c.close()
            return {"loss": loss, "wire_bytes": wire,
                    "exchange_s_mean": sum(exchange_s) / len(exchange_s),
                    "periods": len(exchange_s), "rounds": rounds,
                    "stages": stages}
        finally:
            server.stop()
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)

    fp32 = run_arm(lambda c, t, d: ParamAverager(
        c, t, 2, exchange_dir=d, binary_threshold=1 << 20))
    comp = run_arm(lambda c, t, d: CompressedShardedAverager(
        c, t, 2, exchange_dir=d, binary_threshold=1 << 20,
        epoch_fn=None))
    results["param_exchange_stage_ms"] = comp.get("stages") or None

    reduction = (fp32["wire_bytes"] / comp["wire_bytes"]
                 if comp["wire_bytes"] else 0.0)
    results["param_exchange_config"] = (
        "150k-param (0.6 MB f32) MLP, 2 local workers + real coordinator, "
        "12 exchange periods (every 5 local steps), fp32-full vs "
        "delta-int8-sharded")
    results["param_exchange_fp32_mb"] = round(fp32["wire_bytes"] / 1e6, 3)
    results["param_exchange_int8_mb"] = round(comp["wire_bytes"] / 1e6, 3)
    results["param_exchange_bytes_reduction_x"] = round(reduction, 2)
    results["param_exchange_fp32_latency_ms"] = round(
        fp32["exchange_s_mean"] * 1e3, 2)
    results["param_exchange_int8_latency_ms"] = round(
        comp["exchange_s_mean"] * 1e3, 2)
    results["param_exchange_fp32_loss"] = round(fp32["loss"], 5)
    results["param_exchange_int8_loss"] = round(comp["loss"], 5)
    results["param_exchange_loss_ratio"] = round(
        comp["loss"] / fp32["loss"], 4) if fp32["loss"] else None
    results["param_exchange_int8_rounds"] = comp["rounds"]
    # The acceptance bar, asserted here so a protocol regression fails
    # the leg (and the suite headline) rather than shipping silently.
    assert reduction >= 4.0, (
        f"bytes-on-wire reduction {reduction:.2f}x < 4x "
        f"({fp32['wire_bytes']} vs {comp['wire_bytes']})")
    assert comp["loss"] <= fp32["loss"] * 1.02 + 1e-3, (
        f"convergence parity broken: int8 {comp['loss']:.5f} vs "
        f"fp32 {fp32['loss']:.5f}")

    # ---- scaling arm (ISSUE 13): inter-host wire bytes + exchange
    # latency vs worker count N in {2, 8, 32}, flat int8 vs hierarchical
    # (slices simulated as sibling workers on the CI CPU; intra-slice
    # records stand in for the ICI hop and are accounted separately),
    # and the hierarchical N=8 arm once more over a 2-instance sharded
    # coordination plane (CoordinationRouter).
    from distributed_tensorflow_tpu.cluster.coordination import (
        CoordinationRouter)
    from distributed_tensorflow_tpu.cluster.param_sync import (
        HierarchicalCompressedAverager)

    scale_rng = np.random.default_rng(11)
    scale_base = scale_rng.standard_normal(40_000).astype(np.float32)

    def scale_drift():
        g = scale_rng.standard_normal(scale_base.size).astype(np.float32)
        return 0.01 * g * (scale_rng.random(scale_base.size) < 0.1)

    def scale_arm(n, hier_slice, nshards=1, periods=8):
        """Drift workload over ``n`` real workers against a real (possibly
        sharded) coordination plane; returns inter/intra bytes + mean
        per-worker exchange latency (+ an exporter's stage split).
        ``hier_slice``: None = the flat protocol; an int = the
        hierarchical protocol with that slice size (so even a
        single-slice N=2 datapoint really exercises the two-level
        member/exporter machinery, not a relabeled flat run)."""
        import shutil
        servers = [CoordinationServer(port=0, num_tasks=n,
                                      shard=i, nshards=nshards)
                   for i in range(nshards)]
        for s in servers:
            s.start()
        tmp = tempfile.mkdtemp(prefix="dtf_px_scale_")
        try:
            spec = ",".join(f"127.0.0.1:{s.port}" for s in servers)
            if nshards > 1:
                clients = [CoordinationRouter(spec, t) for t in range(n)]
            else:
                clients = [CoordinationClient("127.0.0.1", servers[0].port,
                                              t) for t in range(n)]
            if hier_slice is not None:
                avgs = [HierarchicalCompressedAverager(
                    c, t, n, exchange_dir=tmp, binary_threshold=1 << 20,
                    slice_size=hier_slice) for t, c in enumerate(clients)]
            else:
                avgs = [CompressedShardedAverager(
                    c, t, n, exchange_dir=tmp, binary_threshold=1 << 20)
                    for t, c in enumerate(clients)]
            params = [{"w": scale_base.copy()} for _ in range(n)]
            lat = []
            for _ in range(periods):
                for t in range(n):
                    params[t]["w"] = params[t]["w"] + scale_drift()
                    t0 = _time.perf_counter()
                    params[t], _ = avgs[t].exchange(params[t])
                    lat.append(_time.perf_counter() - t0)
            inter = sum(a.total_bytes_out + a.total_bytes_in for a in avgs)
            intra = sum(a.total_intra_bytes for a in avgs)
            rounds = max(a.rounds_completed for a in avgs)
            stages = next((dict(a.last_stage_ms) for a in avgs
                           if getattr(a, "last_is_exporter", True)
                           and a.last_stage_ms), {})
            for c in clients:
                c.close()
            return {"inter_bytes": inter, "intra_bytes": intra,
                    "latency_ms": 1e3 * sum(lat) / len(lat),
                    "rounds": rounds, "stages": stages}
        finally:
            for s in servers:
                s.stop()
            shutil.rmtree(tmp, ignore_errors=True)

    scale = {}
    slice_for = {2: 2, 8: 4, 32: 8}
    for n in (2, 8, 32):
        flat_n = scale_arm(n, hier_slice=None)
        hier_n = scale_arm(n, hier_slice=slice_for[n])
        scale[n] = (flat_n, hier_n)
        results[f"param_exchange_flat_inter_mb_n{n}"] = round(
            flat_n["inter_bytes"] / 1e6, 3)
        results[f"param_exchange_hier_inter_mb_n{n}"] = round(
            hier_n["inter_bytes"] / 1e6, 3)
        results[f"param_exchange_hier_intra_mb_n{n}"] = round(
            hier_n["intra_bytes"] / 1e6, 3)
        results[f"param_exchange_flat_latency_ms_n{n}"] = round(
            flat_n["latency_ms"], 3)
        results[f"param_exchange_hier_latency_ms_n{n}"] = round(
            hier_n["latency_ms"], 3)
    results["param_exchange_hier_stage_ms_n32"] = \
        scale[32][1]["stages"] or None
    hier_vs_flat_n8 = (scale[8][1]["inter_bytes"]
                       / max(scale[8][0]["inter_bytes"], 1))
    results["param_exchange_hier_vs_flat_bytes_n8"] = round(
        hier_vs_flat_n8, 3)
    lat_growth = (scale[32][1]["latency_ms"]
                  / max(scale[2][1]["latency_ms"], 1e-9))
    results["param_exchange_hier_latency_growth_2_to_32"] = round(
        lat_growth, 2)

    # Convergence parity at N=8 (2 slices): the hierarchical arm must
    # train the MLP workload to within 3% of flat int8's loss.
    def mlp_arm(factory, n=8, steps=60, period=3):
        rng8 = np.random.default_rng(21)
        w_true8 = rng8.standard_normal((16, 4)).astype(np.float32)

        def mk(nrows, offset):
            x = rng8.standard_normal((nrows, 16)).astype(np.float32) \
                + offset
            return x, np.argmax(x @ w_true8, axis=1)

        shards = [mk(128, (t - n / 2) * 0.05) for t in range(n)]
        x_t, y_t = mk(512, 0.0)

        def init8():
            k1, k2 = jax.random.split(jax.random.PRNGKey(3))
            return {"w1": np.asarray(jax.random.normal(k1, (16, 64))
                                     * 0.1),
                    "b1": np.zeros((64,), np.float32),
                    "w2": np.asarray(jax.random.normal(k2, (64, 4))
                                     * 0.1),
                    "b2": np.zeros((4,), np.float32)}

        def loss8(p, x, y):
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(logp[jnp.arange(x.shape[0]), y])

        grad8 = jax.jit(jax.grad(loss8))
        loss8_j = jax.jit(loss8)
        server = CoordinationServer(port=0, num_tasks=n)
        server.start()
        tmp = tempfile.mkdtemp(prefix="dtf_px_mlp_")
        try:
            clients = [CoordinationClient("127.0.0.1", server.port, t)
                       for t in range(n)]
            avgs = [factory(c, t, n, tmp)
                    for t, c in enumerate(clients)]
            params = [init8() for _ in range(n)]
            for step in range(steps):
                for t in range(n):
                    x, y = shards[t]
                    lo = (step * 32) % 96
                    g = grad8(params[t], x[lo:lo + 32], y[lo:lo + 32])
                    params[t] = jax.tree.map(
                        lambda p, gg: np.asarray(p - 0.2 * gg),
                        params[t], g)
                if (step + 1) % period == 0:
                    for t in range(n):
                        out, _ = avgs[t].exchange(params[t])
                        params[t] = jax.tree.map(np.asarray, out)
            stacked = [jax.tree.map(np.asarray, p) for p in params]
            final = jax.tree.map(
                lambda *xs: np.mean(np.stack(
                    [np.asarray(x, np.float32) for x in xs]), axis=0),
                *stacked)
            for c in clients:
                c.close()
            return float(loss8_j(final, x_t, y_t))
        finally:
            server.stop()
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)

    flat_loss8 = mlp_arm(lambda c, t, n, d: CompressedShardedAverager(
        c, t, n, exchange_dir=d, binary_threshold=1 << 20))
    hier_loss8 = mlp_arm(lambda c, t, n, d: HierarchicalCompressedAverager(
        c, t, n, exchange_dir=d, binary_threshold=1 << 20, slice_size=4))
    results["param_exchange_n8_flat_loss"] = round(flat_loss8, 5)
    results["param_exchange_n8_hier_loss"] = round(hier_loss8, 5)

    # 1-vs-2 coordinator shards: the same hierarchical N=8 arm over a
    # sharded coordination plane through the CoordinationRouter.
    sharded8 = scale_arm(8, hier_slice=4, nshards=2)
    results["param_exchange_hier_router2_latency_ms_n8"] = round(
        sharded8["latency_ms"], 3)
    results["param_exchange_hier_router2_inter_mb_n8"] = round(
        sharded8["inter_bytes"] / 1e6, 3)
    results["param_exchange_hier_router2_rounds_n8"] = sharded8["rounds"]

    # Acceptance bars (ISSUE 13): hierarchical inter-host bytes <= 0.6x
    # flat int8 at N=8 (2 slices) at convergence parity (loss within 3%),
    # and hierarchical exchange latency sublinear in N across {2, 8, 32}.
    assert hier_vs_flat_n8 <= 0.6, (
        f"hierarchical inter bytes {hier_vs_flat_n8:.3f}x of flat int8 "
        f"at N=8 (bar: <= 0.6x)")
    assert hier_loss8 <= flat_loss8 * 1.03 + 1e-3, (
        f"hierarchical convergence parity broken at N=8: "
        f"{hier_loss8:.5f} vs flat {flat_loss8:.5f}")
    assert lat_growth < 16.0, (
        f"hierarchical exchange latency grew {lat_growth:.1f}x from N=2 "
        f"to N=32 (bar: sublinear, < 16x)")
    assert sharded8["rounds"] >= 2, (
        "consensus chain never advanced over the 2-instance sharded "
        "coordination plane")


def run_serve_decode(results):
    """Served long-prompt decode rate through the exported KV-cached pair.

    VERDICT r3 #1's done-bar: a served >=1984-token-prompt decode within
    ~2x of the in-framework cached rate.  Builds the run_decode-class
    model (H=2048/L=8), exports the ``prefill``+``decode_k`` pair
    (serialize -> deserialize, the artifact boundary), and times
    ``examples/serve.py::decode_batch_cached`` — the exact function the
    HTTP shim calls — against ``generate_cached`` at the same shapes.
    Also records the old forward-path serving rate (O(S²) per token) at a
    reduced token budget, as the measured gap the cached export closes.
    """
    import dataclasses
    import importlib.util

    import jax
    import jax.numpy as jnp
    from jax import export as jax_export

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.tools.export_model import (
        build_gpt_decode_fns)

    spec = importlib.util.spec_from_file_location(
        "dtf_bench_serve", os.path.join(REPO, "examples", "serve.py"))
    serve_lib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve_lib)

    # H=1024/L=4 (~48M params): the artifact bakes the weights as
    # CONSTANTS, so the run_decode-class H=2048/L=8 model would
    # serialize ~800 MB into the program; that size has not been
    # compiled on the chip.  The within-2x comparison below is
    # same-model, so the bar is unchanged.
    # chunk == T (r5, VERDICT r4 #4): the r4 gap to the in-framework rate
    # (0.725) was DISPATCH COUNT — generate_cached is one device call,
    # the chunked loop was three; a serving operator sizes the chunk to
    # the typical generation length, so the honest shim config does too.
    B, P, T, chunk, cap = 4, 1984, 64, 64, 2048
    cfg = dataclasses.replace(
        gpt_lib.mini(), hidden_size=1024, num_layers=4, num_heads=16,
        intermediate_size=4096, max_position=cap, dtype="bfloat16")
    model = gpt_lib.GptLM(cfg)
    prompt = np.asarray(
        gpt_lib.synthetic_lm_batch(0, B, P, cfg)["tokens"], np.int32)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        model.init(jax.random.PRNGKey(0), jnp.asarray(prompt[:1, :8]))
        ["params"])
    tree = jax.tree.map(np.asarray, params)

    def export_set(window=0):
        """(cached dict, boundary label) for a full or ring pair."""
        wcfg = dataclasses.replace(cfg, attention_window=window)
        prefill, decode_k, _ = build_gpt_decode_fns(
            wcfg, tree, capacity=cap, chunk=chunk)
        cache_len = min(cap, window) if window else cap
        try:  # the faithful path: through jax.export serialization
            plat = jax.default_backend()
            b, p = jax_export.symbolic_shape("b, p",
                                             constraints=[f"p <= {cap}"])
            pre_specs = [jax.ShapeDtypeStruct((b, p), jnp.int32)]
            if window:
                pre_specs.append(jax.ShapeDtypeStruct((b,), jnp.int32))
            pre_exp = jax_export.export(jax.jit(prefill),
                                        platforms=[plat])(*pre_specs)
            (b2,) = jax_export.symbolic_shape("b")
            cs = (b2, cache_len, wcfg.num_kv_heads, wcfg.head_dim)
            dt = jnp.dtype(wcfg.dtype)
            dec_exp = jax_export.export(jax.jit(decode_k),
                                        platforms=[plat])(
                jax.ShapeDtypeStruct((b2,), jnp.int32),
                jax.ShapeDtypeStruct((b2,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((b2,), jnp.bool_),
                [(jax.ShapeDtypeStruct(cs, dt), jax.ShapeDtypeStruct(cs, dt))
                 for _ in range(wcfg.num_layers)])
            pre_call = jax.jit(
                jax_export.deserialize(pre_exp.serialize()).call)
            dec_call = jax.jit(
                jax_export.deserialize(dec_exp.serialize()).call)
            boundary = "jax.export artifact"
        except Exception:  # non-standard backend name: measure the fns
            pre_call, dec_call = jax.jit(prefill), jax.jit(decode_k)
            boundary = "jitted pair (export serialize unsupported here)"
        return {"prefill": pre_call, "decode": dec_call, "capacity": cap,
                "chunk": chunk, "window": window}, boundary

    cached, boundary = export_set()
    prompts = [r.tolist() for r in prompt]

    def serve_rate(c):
        def once():
            return serve_lib.decode_batch_cached(c, prompts, [T] * B)
        once()                          # compile (prefill + decode chunk)
        rates = []
        for _ in range(3):
            t0 = time.perf_counter()
            once()
            rates.append(B * T / (time.perf_counter() - t0))
        return max(rates)

    served = serve_rate(cached)

    # In-framework reference at the same shapes (prefill incl.).
    fn = jax.jit(lambda pr: gpt_lib.generate_cached(
        model, params, pr, T)[:, -1].sum())
    pr_dev = jnp.asarray(prompt)
    _sync(fn(pr_dev))
    in_rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(fn(pr_dev))
        in_rates.append(B * T / (time.perf_counter() - t0))
    in_frame = max(in_rates)

    # The boundary this replaces: O(S²) forward-path serving (16 tokens is
    # plenty to establish the per-token rate).
    fwd = jax.jit(lambda toks: model.apply({"params": params}, toks))
    T_fwd = 16
    serve_lib.decode_batch(fwd, prompts, [T_fwd] * B, cap)  # compile+warm
    t0 = time.perf_counter()
    serve_lib.decode_batch(fwd, prompts, [T_fwd] * B, cap)
    fwd_rate = B * T_fwd / (time.perf_counter() - t0)

    results["serve_decode_config"] = (
        f"L={cfg.num_layers} H={cfg.hidden_size} B={B} prompt={P} gen={T} "
        f"capacity={cap} chunk={chunk} bf16, {boundary}")
    results["serve_decode_tokens_per_sec"] = round(served, 1)
    results["serve_decode_in_framework_tokens_per_sec"] = round(in_frame, 1)
    results["serve_decode_vs_in_framework"] = round(served / in_frame, 3)
    results["serve_decode_forward_path_tokens_per_sec"] = round(fwd_rate, 1)
    results["serve_decode_vs_forward_path"] = round(served / fwd_rate, 1)

    # Windowed ring pair (VERDICT r4 #3): the same checkpoint served as a
    # sliding-window model — O(window) cache reads per token instead of
    # O(capacity); the rate is recorded against the full-cache shim.
    wcached, _ = export_set(window=512)
    w_served = serve_rate(wcached)
    results["serve_decode_windowed_tokens_per_sec"] = round(w_served, 1)
    results["serve_decode_windowed_vs_full"] = round(w_served / served, 3)
    results["serve_decode_windowed_config"] = (
        "window=512 ring cache (512 slots vs the full pair's 2048), same "
        "model/prompt/gen")


def _train_byte_lm(cfg, corpus, steps, batch, seq, lr):
    """Adam-train a GptLM on a byte corpus; returns (model, np params).
    ONE training recipe shared by the serve and speculative legs — the
    two benches must measure the same kind of trained model, not drift
    apart."""
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.data.lm import ByteLmStream
    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    stream = ByteLmStream(corpus, seq_len=seq, seed=0)
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32), jnp.int32))["params"]
    tx = optax.adam(lr)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, tokens):
        def loss_fn(p):
            loss, _ = gpt_lib.lm_loss(
                model.apply({"params": p}, tokens), tokens)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    for _ in range(steps):
        params, opt, _ = step(params, opt,
                              jnp.asarray(stream.next_batch(batch)["tokens"]))
    return model, jax.tree.map(np.asarray, params)


def run_serve(results):
    """Serving-tier leg (--mode serve, docs/serving.md): the continuous-
    batching engine under a 2-tenant synthetic load — tokens/s across the
    slot batch, TTFT/TPOT percentiles per request, and the int8+fp8
    weight/KV arm's speedup on the SAME workload.  In-process (no HTTP):
    this measures the engine + fair scheduler, not socket overhead."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                           EngineConfig)
    from distributed_tensorflow_tpu.serving.scheduler import (FairScheduler,
                                                              Request)

    cfg = dataclasses.replace(gpt_lib.mini(), dtype="float32")
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 32), jnp.int32))["params"]
    N_REQ, PROMPT, GEN = 24, 12, 24

    def drive(quantize, kv_dtype):
        """Admit a 2-tenant request stream through the fair scheduler and
        engine; returns (tokens/s, ttfts, tpots, overlap_admissions,
        spec accepted/round or None)."""
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=8, page_size=16, num_pages=128, max_pages_per_seq=4,
            quantize=quantize, kv_dtype=kv_dtype))
        sched = FairScheduler()
        # Warm the two resident programs (prefill bucket + decode step)
        # outside the timed window.
        warm = Request([1] * PROMPT, 2)
        engine.admit(warm)
        while engine.active_slots:
            engine.step()
        # Budgets staggered (GEN .. GEN+12) so completions — and the
        # admissions that backfill them — interleave with mid-decode
        # lanes instead of arriving in synchronized waves.
        requests = [
            Request(list(range(1 + i, 1 + i + PROMPT)), GEN + 3 * (i % 5),
                    tenant=("search" if i % 2 else "ads"))
            for i in range(N_REQ)
        ]
        overlap = 0
        t0 = time.perf_counter()
        for req in requests:
            sched.submit(req)
        pending = len(requests)
        while pending:
            admitted = 0
            while engine.free_slots > 0:
                req = sched.next_request(engine.can_admit)
                if req is None:
                    break
                engine.admit(req)
                admitted += 1
            if admitted and engine.active_slots > admitted:
                overlap += admitted  # joined while others were mid-decode
            pending -= len(engine.step(queue_depth=sched.depth()))
        elapsed = time.perf_counter() - t0
        total_tokens = sum(len(r.tokens) for r in requests)
        ttfts = [r.ttft_ms for r in requests if r.ttft_ms is not None]
        tpots = [r.tpot_ms for r in requests if r.tpot_ms is not None]
        rounds = sum(r.spec_rounds for r in requests)
        acc = round(total_tokens / rounds, 2) if rounds else None
        return total_tokens / elapsed, ttfts, tpots, overlap, acc

    # One percentile definition for the serving tier: the BENCH artifact
    # must agree with summarize_run's report on identical data.
    from distributed_tensorflow_tpu.tools.summarize_run import _quantile

    def pct(values, q):
        return round(_quantile(values, q), 2)

    rate, ttfts, tpots, overlap, _ = drive("", "")

    # Trace artifact (mirrors run_profile's xplane recording): a SEPARATE
    # drive of the same workload with the tracer installed, exported to a
    # Perfetto-loadable trace in a stable dir whose path the BENCH
    # details record.  Kept apart from the timed arms above so no
    # measured number pays span-emission overhead the other arms don't.
    import tempfile

    from distributed_tensorflow_tpu.tools import export_trace
    from distributed_tensorflow_tpu.utils import tracing
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
    from distributed_tensorflow_tpu.utils.telemetry import Telemetry

    trace_dir = tempfile.mkdtemp(prefix="dtf_bench_serve_trace_")
    stream_path = os.path.join(trace_dir, "serve.jsonl")
    trace_file = os.path.join(trace_dir, "trace.json")
    trace_logger = MetricsLogger(stream_path)
    tracing.install(tracing.Tracer(Telemetry(trace_logger),
                                   run_id="bench-serve"))
    try:
        drive("", "")                      # artifact only, not timed
    except Exception:  # noqa: BLE001 — the bench numbers still stand
        pass
    finally:
        tracing.clear()
        trace_logger.close()
    try:
        exported = export_trace.main([stream_path, "--output", trace_file])
    except Exception:  # noqa: BLE001
        exported = 1
    results["serve_config"] = (
        f"gpt-mini f32, 8 slots, 128 pages x 16, {N_REQ} requests x "
        f"{GEN} tokens (prompt {PROMPT}), 2 tenants")
    results["serve_tokens_per_sec"] = round(rate, 1)
    results["serve_ttft_ms_p50"] = pct(ttfts, 0.50)
    results["serve_ttft_ms_p95"] = pct(ttfts, 0.95)
    results["serve_ttft_ms_p99"] = pct(ttfts, 0.99)
    results["serve_tpot_ms_p50"] = pct(tpots, 0.50)
    results["serve_tpot_ms_p95"] = pct(tpots, 0.95)
    results["serve_tpot_ms_p99"] = pct(tpots, 0.99)
    results["serve_overlap_admissions"] = overlap
    results["serve_trace_dir"] = trace_dir
    results["serve_trace_file"] = trace_file if exported == 0 else None

    q_rate, _, q_tpots, _, _ = drive("int8", "float8")
    results["serve_int8_fp8_tokens_per_sec"] = round(q_rate, 1)
    results["serve_int8_fp8_tpot_ms_p50"] = pct(q_tpots, 0.50)
    results["serve_int8_fp8_tpot_ms_p99"] = pct(q_tpots, 0.99)
    results["serve_int8_fp8_vs_f32"] = round(q_rate / rate, 3)

    # --- mixed long-prompt/short-decode arm (ISSUE 11): one LONG prompt
    # admitted mid-run among short decoders — and its length is NEW to
    # the server, the production event the ROADMAP names ("a long
    # prompt's prefill stalls every live decode lane for a full
    # compile-bucket step").  Whole-bucket prefill compiles and runs a
    # fresh per-bucket program at admission, stalling every live lane
    # for the whole of it; chunked prefill has no per-bucket program at
    # all — the one resident chunk program advances the prompt
    # `prefill_chunk` tokens per step while the short lanes keep
    # decoding.  Both arms warm what a short-traffic server would have
    # resident (decode step, short bucket, chunk program); the long
    # bucket arrives cold BY CONSTRUCTION in both.  Pinned fields: the
    # short decoders' tpot_p99 (the tail the stall lands in) and a
    # prefill_stall_ms decomposition (engine-accumulated time producing
    # prompt K/V, bucket compile included).
    LONGP, N_SHORT = 96, 8

    def drive_mixed(prefill_chunk):
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=4, page_size=16, num_pages=128, max_pages_per_seq=8,
            prefill_chunk=prefill_chunk))
        # Steady short-traffic state: decode step + short-prompt path
        # warm (which on the chunked engine includes the chunk program —
        # the only prompt program it will ever need).
        warm = Request([1] * PROMPT, 2)
        engine.admit(warm)
        while engine.active_slots:
            engine.step()
        engine.prefill_ms_total = 0.0
        sched = FairScheduler()
        shorts = [
            Request(list(range(1 + i, 1 + i + PROMPT)), GEN,
                    tenant=("search" if i % 2 else "ads"))
            for i in range(N_SHORT)
        ]
        long_req = Request(list(range(1, LONGP + 1)), 8, tenant="search")
        for req in shorts:
            sched.submit(req)
        pending = len(shorts) + 1
        steps = 0
        t0 = time.perf_counter()
        while pending and steps < 10_000:
            if steps == 4:
                sched.submit(long_req)   # arrives mid-decode
            while engine.free_slots > 0:
                req = sched.next_request(engine.can_admit)
                if req is None:
                    break
                engine.admit(req)
            pending -= len(engine.step(queue_depth=sched.depth()))
            steps += 1
        elapsed = time.perf_counter() - t0
        tpots = [r.tpot_ms for r in shorts if r.tpot_ms is not None]
        total = sum(len(r.tokens) for r in shorts) + len(long_req.tokens)
        return {
            "tpot_p99": pct(tpots, 0.99),
            "tpot_p50": pct(tpots, 0.50),
            "stall_ms": round(engine.prefill_ms_total, 2),
            "long_ttft_ms": round(long_req.ttft_ms or 0.0, 2),
            "tokens_per_sec": round(total / elapsed, 1),
        }

    whole = drive_mixed(0)
    chunked = drive_mixed(GEN // 2)      # decode-round-sized chunks
    results["serve_mixed_config"] = (
        f"gpt-mini f32, 4 slots; {N_SHORT} short decoders (prompt "
        f"{PROMPT}, gen {GEN}) + ONE long prompt ({LONGP} tokens, gen 8) "
        f"of a length NEW to the server admitted mid-run (cold bucket "
        f"both arms — the whole-bucket arm pays its fresh per-bucket "
        f"compile, the chunked arm structurally has none); whole-bucket "
        f"vs prefill_chunk={GEN // 2}; tpot percentiles over the SHORT "
        f"requests only")
    results["serve_mixed_whole_tpot_ms_p99"] = whole["tpot_p99"]
    results["serve_mixed_chunked_tpot_ms_p99"] = chunked["tpot_p99"]
    results["serve_mixed_chunked_vs_whole_tpot_p99"] = round(
        whole["tpot_p99"] / chunked["tpot_p99"], 3) \
        if chunked["tpot_p99"] else None
    results["serve_mixed_whole_prefill_stall_ms"] = whole["stall_ms"]
    results["serve_mixed_chunked_prefill_stall_ms"] = chunked["stall_ms"]
    results["serve_mixed_whole_long_ttft_ms"] = whole["long_ttft_ms"]
    results["serve_mixed_chunked_long_ttft_ms"] = chunked["long_ttft_ms"]
    results["serve_mixed_whole_tokens_per_sec"] = whole["tokens_per_sec"]
    results["serve_mixed_chunked_tokens_per_sec"] = \
        chunked["tokens_per_sec"]

    # --- speculative arm (ISSUE 8): the same continuous-batching drive
    # with every request opted into the paged speculative arm, against
    # the identical workload served plain.  Greedy both sides
    # (speculation is greedy-only), on a mini QUICKLY TRAINED on a
    # periodic byte stream and served repetitive prompts from it — the
    # regime speculation is for; acceptance and the rate ratio below are
    # the serving engine's own draft->chunk-verify->accept loop, pages
    # and continuous batching included.
    corpus = np.tile(np.frombuffer(b"abcdefgh ", np.uint8), 160)
    scfg = dataclasses.replace(gpt_lib.mini(), dtype="float32",
                               pos_encoding="rope")
    smodel, sparams = _train_byte_lm(scfg, corpus, 120, 32, 32, 3e-3)

    def drive_spec(spec_k, speculative):
        engine = DecodeEngine(smodel, sparams, EngineConfig(
            num_slots=8, page_size=16, num_pages=128, max_pages_per_seq=4,
            spec_k=spec_k))
        sched = FairScheduler()
        warm = Request(list(corpus[:18]), 2, speculative=speculative)
        engine.admit(warm)
        while engine.active_slots:
            engine.step()
        requests = [
            Request(list(corpus[9 * (i % 3):9 * (i % 3) + 18]),
                    GEN + 3 * (i % 5),
                    tenant=("search" if i % 2 else "ads"),
                    speculative=speculative)
            for i in range(N_REQ)
        ]
        t0 = time.perf_counter()
        for req in requests:
            sched.submit(req)
        pending = len(requests)
        while pending:
            while engine.free_slots > 0:
                req = sched.next_request(engine.can_admit)
                if req is None:
                    break
                engine.admit(req)
            pending -= len(engine.step(queue_depth=sched.depth()))
        elapsed = time.perf_counter() - t0
        total_tokens = sum(len(r.tokens) for r in requests)
        tpots = [r.tpot_ms for r in requests if r.tpot_ms is not None]
        rounds = sum(r.spec_rounds for r in requests)
        acc = round(total_tokens / rounds, 2) if rounds else None
        return total_tokens / elapsed, tpots, acc

    results["serve_spec_config"] = (
        f"mini f32 trained 120 steps on a period-9 byte loop; {N_REQ} "
        f"repetitive-prompt requests (prompt 18, gen {GEN}..{GEN + 12}), "
        "2 tenants, greedy; spec arm = per-request opt-in, engine "
        "spec_k=8 paged chunk verify vs the SAME workload served plain")
    base_rate, _, _ = drive_spec(0, False)
    spec_rate, spec_tpots, acc = drive_spec(8, True)
    results["serve_spec_tokens_per_sec"] = round(spec_rate, 1)
    results["serve_spec_plain_tokens_per_sec"] = round(base_rate, 1)
    results["serve_spec_accepted_per_round"] = acc
    results["serve_spec_tpot_ms_p50"] = pct(spec_tpots, 0.50)
    results["serve_spec_tpot_ms_p99"] = pct(spec_tpots, 0.99)
    results["serve_spec_vs_plain"] = round(spec_rate / base_rate, 3)


def run_router(results):
    """Fleet-router leg (--mode router, docs/serving.md "Fleet"): N REAL
    replica subprocesses (``tools/serve.py`` on CPU — one process, one
    GIL, one engine each; in-process replicas would serialize on jax
    dispatch and hide the scaling) behind the statz-routed frontend,
    under a zipfian multi-tenant load — QPS and TTFT p99 vs replica
    count N in {1, 2, 3}, plus a kill-one-replica arm (SIGKILL) that
    records the failover gap and the post-failover tail."""
    import signal as signal_mod
    import socket
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import optax

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.serving.client import ServeClient
    from distributed_tensorflow_tpu.tools.summarize_run import _quantile
    from distributed_tensorflow_tpu.training.state import TrainState
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    N_REQ, PROMPT, GEN, WORKERS = 48, 12, 16, 16

    # A real checkpoint for the replicas to restore (a few actual train
    # steps, the pattern of the serving e2e tests).
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
    for _ in range(4):
        state = step_fn(state, batch)
    logdir = tempfile.mkdtemp(prefix="dtf_bench_router_")
    sv = Supervisor(is_chief=True, logdir=logdir, init_fn=lambda: state)
    assert sv.maybe_save(state, force=True)
    sv.close()

    # Zipfian tenant mix over 6 tenants (rank-r tenant with weight 1/r):
    # a couple of heavy tenants plus a long tail — the regime where
    # tenant-affinity routing with spill either pays or collapses onto
    # one replica.
    rng = np.random.default_rng(0)
    ranks = np.minimum(rng.zipf(1.4, N_REQ), 6)
    tenants = [f"t{r}" for r in ranks]

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    # Boot ALL THREE replicas once (parallel restore+compile, ~spawn
    # cost paid a single time); arms route over subsets of them.
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    replicas = []   # (rid, url, proc)
    for i in range(3):
        port = free_port()
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "distributed_tensorflow_tpu.tools.serve",
             "--logdir", logdir, "--port", str(port),
             "--platform", "cpu", "--replica_id", f"r{i}",
             "--slots", "4", "--page_size", "16", "--num_pages", "128",
             "--max_pages_per_seq", "4"],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        replicas.append((f"r{i}", f"http://127.0.0.1:{port}", proc))

    def wait_and_warm(url):
        client = ServeClient(url, timeout_s=300.0, retries=0)
        deadline = time.time() + 240.0
        while time.time() < deadline:
            try:
                client.health()
                break
            except Exception:
                time.sleep(1.0)
        else:
            raise RuntimeError(f"replica at {url} never became healthy")
        client.generate([1] * PROMPT, 2)   # compile outside timed arms

    warmers = [threading.Thread(target=wait_and_warm, args=(u,))
               for _, u, _ in replicas]
    for t in warmers:
        t.start()
    for t in warmers:
        t.join()

    def drive(members, kill_proc=None):
        """One arm: a fresh ROUTER PROCESS (serve_fleet --adopt) over
        ``members`` — the router must not share the caller process's
        GIL or the measurement caps at the bench process, not the
        fleet; optionally SIGKILL ``kill_proc`` after a third of the
        load completed."""
        fleet = subprocess.Popen(
            [sys.executable, "-m",
             "distributed_tensorflow_tpu.tools.serve_fleet",
             "--adopt", ",".join(u for _, u, _ in members),
             "--replicas", "0", "--port", "0", "--poll_s", "0.2",
             "--fail_after", "1"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        try:
            banner = fleet.stdout.readline()
            port = int(banner.split(" on :")[1].split(" ")[0].strip())
            url = f"http://127.0.0.1:{port}"
            probe = ServeClient(url, timeout_s=30.0, retries=3)
            deadline = time.time() + 60.0
            while time.time() < deadline:
                try:
                    if (probe.fleetz()["router"]["healthy"]
                            >= len(members)):
                        break
                except Exception:
                    pass
                time.sleep(0.2)
            done: list[tuple[float, dict]] = []
            failed: list[Exception] = []
            done_lock = threading.Lock()
            kill_after = N_REQ // 3
            killed = [0.0]

            def worker(requests):
                client = ServeClient(url, timeout_s=120.0, retries=0)
                for tenant in requests:
                    try:
                        out = client.generate(
                            list(range(1, 1 + PROMPT)), GEN,
                            tenant=tenant)
                    except Exception as e:  # noqa: BLE001 — kill arm counts
                        with done_lock:
                            failed.append(e)
                        continue
                    kill_now = False
                    with done_lock:
                        done.append((time.perf_counter(), out))
                        if (kill_proc is not None and not killed[0]
                                and len(done) >= kill_after):
                            killed[0] = time.perf_counter()
                            kill_now = True
                    if kill_now:
                        kill_proc.send_signal(signal_mod.SIGKILL)

            shards = [tenants[i::WORKERS] for i in range(WORKERS)]
            threads = [threading.Thread(target=worker, args=(s,))
                       for s in shards if s]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            stats = probe.fleetz()["router"]
        finally:
            # The router process must die even when the arm aborts
            # (banner parse failure, leg timeout) — a surviving
            # fail_after=1 poll loop would hammer replicas later arms
            # reuse.
            fleet.terminate()
            try:
                fleet.wait(timeout=20.0)
            except subprocess.TimeoutExpired:
                fleet.kill()
        ttfts = [out["ttft_ms"] for _, out in done
                 if out.get("ttft_ms")]
        # killed[0] stays 0.0 when the kill threshold was never reached
        # (replica too overloaded to complete kill_after requests); post
        # empty means nothing completed AFTER the kill.  Either way the
        # kill metrics report None — never a sentinel-math figure.
        post = [(t, out) for t, out in done if t > killed[0]] \
            if killed[0] else []
        return {
            "qps": round(len(done) / elapsed, 2),
            "ttft_p99": round(_quantile(ttfts, 0.99), 2),
            "failed": len(failed),
            "failovers": stats["failovers"],
            "max_failover_ms": stats["max_failover_ms"],
            "gap_ms": round((min(t for t, _ in post) - killed[0]) * 1e3,
                            1) if post else None,
            "post_ttft_p99": round(_quantile(
                [o["ttft_ms"] for _, o in post if o.get("ttft_ms")],
                0.99), 2) if post else None,
        }

    try:
        results["router_config"] = (
            f"3 real serve.py subprocess replicas (gpt-mini, CPU, 4 "
            f"slots, 128 pages x 16) behind the statz router; {N_REQ} "
            f"requests x {GEN} tokens (prompt {PROMPT}), zipf(1.4) over "
            f"6 tenants, {WORKERS} concurrent callers; kill arm at N=2: "
            f"one replica SIGKILLed after {N_REQ // 3} completions")
        rates = {}
        for n in (1, 2, 3):
            arm = drive(replicas[:n])
            rates[n] = arm["qps"]
            results[f"router_qps_n{n}"] = arm["qps"]
            results[f"router_ttft_ms_p99_n{n}"] = arm["ttft_p99"]
            results[f"router_failed_n{n}"] = arm["failed"]
        results["router_scaling_n2_vs_n1"] = round(rates[2] / rates[1], 3)
        results["router_scaling_n3_vs_n1"] = round(rates[3] / rates[1], 3)
        # Kill arm LAST: it costs replica r1 (SIGKILL mid-decode).
        kill = drive(replicas[:2], kill_proc=replicas[1][2])
        results["router_kill_failed_requests"] = kill["failed"]
        results["router_kill_failovers"] = kill["failovers"]
        results["router_kill_failover_gap_ms"] = kill["gap_ms"]
        results["router_kill_max_failover_ms"] = kill["max_failover_ms"]
        results["router_kill_post_ttft_ms_p99"] = kill["post_ttft_p99"]
        results["router_kill_qps"] = kill["qps"]
    finally:
        for _, _, proc in replicas:
            if proc.poll() is None:
                proc.send_signal(signal_mod.SIGTERM)
        for _, _, proc in replicas:
            try:
                proc.wait(timeout=15.0)
            except subprocess.TimeoutExpired:
                proc.kill()


def run_speculative(results):
    """Speculative decoding's honest operating envelope (VERDICT r3 #6).

    Trains the mini GPT on periodic byte text (the regime prompt-lookup
    drafting is FOR), then measures acceptance and tokens/sec on BOTH
    regimes with the same trained model:

    - repetitive text: multi-token acceptance, the speedup mechanism;
    - random bytes: acceptance degrades toward 1/round, the auto-fallback
      engages (``fallback_at_round``), and the recorded rate shows what
      the fallback saves vs plain cached decode.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    phrase = np.frombuffer(b"the quick brown fox jumps over the lazy dog. ",
                           np.uint8)
    corpus = np.tile(phrase, 120)

    # H=512/L=4 (not mini's H=128): at mini scale every variant costs ~one
    # dispatch and the wall-clock ratio measures dispatch latency, not the
    # mechanism; at this size a 256-token generation is ~100s of ms of
    # device time, so the rates below mean something.
    cfg = dataclasses.replace(gpt_lib.mini(), hidden_size=512, num_layers=4,
                              num_heads=8, intermediate_size=2048,
                              dtype="float32", pos_encoding="rope")
    model, params = _train_byte_lm(cfg, corpus, 150, 32, 32, 3e-3)
    T = 256
    SPEC_K = 16

    def timed(fn):
        fn()                     # compile + warm
        t0 = time.perf_counter()
        out = fn()
        return out, T / (time.perf_counter() - t0)

    # --- cost decomposition (ISSUE 8): ONE K-wide decode_chunk vs ONE
    # decode_step, measured on this backend at this model size — the
    # acceptance x cost identity that explains every vs_plain ratio
    # below (vs_plain ~= accepted_per_round / spec_round_cost_vs_step).
    total = 96 + T
    caches = gpt_lib.init_kv_cache(cfg, 1, total)
    warm_prompt = jnp.asarray(corpus[None, :96].astype(np.int32))
    _, caches = model.apply({"params": params}, warm_prompt, caches,
                            method=gpt_lib.GptLM.prefill)

    @jax.jit
    def one_step(tok, caches, pos):
        return model.apply({"params": params}, tok, caches, pos,
                           method=gpt_lib.GptLM.decode_step)

    @jax.jit
    def one_chunk(toks, caches, pos):
        return model.apply({"params": params}, toks, caches, pos,
                           method=gpt_lib.GptLM.decode_chunk)

    def bench_call(fn, *args, n=20):
        out = fn(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.tree.map(lambda x: x.block_until_ready(), out)
        return (time.perf_counter() - t0) / n

    step_s = bench_call(one_step, jnp.zeros((1,), jnp.int32), caches,
                        jnp.int32(96))
    chunk_s = bench_call(one_chunk, jnp.zeros((1, SPEC_K), jnp.int32),
                         caches, jnp.full((1,), 96, jnp.int32))
    results["spec_step_ms"] = round(step_s * 1e3, 3)
    results["spec_chunk_ms"] = round(chunk_s * 1e3, 3)
    results["spec_chunk_cost_vs_step"] = round(chunk_s / step_s, 2)
    del caches

    prompts = {
        "repetitive": jnp.asarray(corpus[None, :96].astype(np.int32)),
        "random": jnp.asarray(
            np.random.default_rng(7).integers(0, 256, (1, 96)), jnp.int32),
    }
    results["spec_config"] = (
        f"H=512/L=4 GPT trained 150 steps on periodic bytes; prompt=96 "
        f"gen={T}. spec_* = host-loop variant (one dispatch PER ROUND — "
        f"the instrumented reference, spec_k=8 + auto-fallback); "
        f"spec_device_* = the one-dispatch on-device variant "
        f"(spec_k={SPEC_K}, tree branch 3, adaptive K, cached compiled "
        "program), whose vs_plain ratio is the mechanism's real "
        "wall-clock effect.  spec_chunk_cost_vs_step / "
        "spec_overhead_vs_chunk decompose a round's cost: vs_plain ~= "
        "accepted_per_round / (chunk_cost_vs_step * overhead)")
    for regime, prompt in prompts.items():
        stats_box = {}

        def spec(prompt=prompt, box=stats_box):
            out, stats = gpt_lib.generate_cached_speculative(
                model, params, prompt, T, spec_k=8)
            box.update(stats)
            return out

        dev_box = {}

        def spec_dev(prompt=prompt, box=dev_box):
            out, stats = gpt_lib.generate_cached_speculative_device(
                model, params, prompt, T, spec_k=SPEC_K, spec_branch=3)
            box.update(stats)
            return np.asarray(out)

        def plain(prompt=prompt):
            return np.asarray(gpt_lib.generate_cached(
                model, params, prompt, T))

        _, spec_rate = timed(spec)
        _, dev_rate = timed(spec_dev)
        dev_wall = T / dev_rate
        _, plain_rate = timed(plain)
        results[f"spec_{regime}_accepted_per_round"] = stats_box[
            "mean_accepted_per_round"]
        results[f"spec_{regime}_fallback_round"] = stats_box[
            "fallback_at_round"] if stats_box[
            "fallback_at_round"] is not None else -1
        results[f"spec_{regime}_tokens_per_sec"] = round(spec_rate, 1)
        results[f"spec_{regime}_plain_tokens_per_sec"] = round(plain_rate, 1)
        results[f"spec_{regime}_vs_plain"] = round(spec_rate / plain_rate, 2)
        # The on-device variant: ONE dispatch like plain, so this ratio
        # measures the MECHANISM (chunk rounds vs sequential steps), not
        # the link.
        results[f"spec_device_{regime}_tokens_per_sec"] = round(dev_rate, 1)
        results[f"spec_device_{regime}_vs_plain"] = round(
            dev_rate / plain_rate, 2)
        results[f"spec_device_{regime}_accepted_per_round"] = dev_box[
            "mean_accepted_per_round"]
        results[f"spec_device_{regime}_rounds_small"] = dev_box[
            "rounds_small"]
        results[f"spec_device_{regime}_branch_hits"] = dev_box[
            "branch_hits"]
        # Measured per-round overhead of the WHOLE speculative round
        # (draft + tree verify + accept + compaction + index update)
        # over the bare chunk — the machinery cost, measured not
        # guessed.  Only meaningful when every round ran full-width:
        # adaptive small rounds cost ~a step, and averaging them in
        # would report a fictitious sub-chunk "overhead".
        rounds = max(dev_box["rounds"], 1)
        results[f"spec_device_{regime}_round_ms"] = round(
            dev_wall / rounds * 1e3, 2)
        if dev_box["rounds_small"] == 0:
            results[f"spec_{regime}_overhead_vs_chunk"] = round(
                (dev_wall / rounds) / chunk_s, 2)

    # --- at-scale arm (VERDICT r4 #2): the memory-bound regime the
    # docstring claims the mechanism was designed for — the decode
    # bench's L=8/H=2048 class, where a K-wide verify chunk reads the
    # same weights one decode_step does, so the chunk is nearly free.
    # Measured HERE, with the same trained-on-repetitive-text protocol;
    # the recorded ratio either demonstrates the win regime or retires
    # the claim with the number that killed it.
    if jax.default_backend() == "tpu":
        big_cfg = dataclasses.replace(
            gpt_lib.mini(), hidden_size=2048, num_layers=8, num_heads=16,
            intermediate_size=8192, max_position=384, dtype="bfloat16",
            pos_encoding="rope")
        big_model, big_params = _train_byte_lm(big_cfg, corpus, 120, 16, 64,
                                               3e-4)
        import ml_dtypes
        big_params = jax.tree.map(
            lambda x: np.asarray(x).astype(ml_dtypes.bfloat16)
            if np.asarray(x).dtype == np.float32 else np.asarray(x),
            big_params)
        prompt = jnp.asarray(corpus[None, :96].astype(np.int32))

        def plain_big():
            return np.asarray(gpt_lib.generate_cached(
                big_model, big_params, prompt, T))

        big_box = {}

        def spec_big():
            out, stats = gpt_lib.generate_cached_speculative_device(
                big_model, big_params, prompt, T, spec_k=SPEC_K,
                spec_branch=3)
            big_box.update(stats)
            return np.asarray(out)

        _, plain_rate = timed(plain_big)
        _, dev_rate = timed(spec_big)
        results["spec_scale_config"] = (
            "L=8 H=2048 I=8192 bf16 (the decode bench's memory-bound "
            "class), trained 120 steps on periodic bytes; B=1 prompt=96 "
            f"gen={T} spec_k={SPEC_K} tree branch 3 adaptive, on-device "
            "one-dispatch variant")
        results["spec_scale_plain_tokens_per_sec"] = round(plain_rate, 1)
        results["spec_scale_device_tokens_per_sec"] = round(dev_rate, 1)
        results["spec_scale_device_vs_plain"] = round(
            dev_rate / plain_rate, 2)
        results["spec_scale_accepted_per_round"] = big_box[
            "mean_accepted_per_round"]
    else:
        results["spec_scale_note"] = (
            "at-scale arm needs the TPU (the 406M model's decode is "
            "minutes-per-call on CPU)")


def run_int8_train(results):
    """Quantized-training arm (VERDICT r3 #2): the flagship GPT step with
    its MLP matmuls on the MXU's int8 path (ops/quant_train.py;
    int8 fwd + dgrad, f32 wgrad) vs the bf16 arm at identical shapes.
    MFU is reported in bf16-equivalent model FLOPs (same formula as the
    bf16 arm), so >100%-of-bf16-peak readings would be the int8 path
    visibly exceeding what bf16 could ever reach.  The convergence-parity
    evidence lives in tests/test_int8_train.py (loss-delta bound).

    r5: the fused pallas MLP (epilogue/prologue fusion + the NT
    scale-folding backward, ops/quant_train.int8_gelu_mlp) turned the
    r4 regression (0.84-0.96x) into a measured 1.017x win over bf16 —
    see ``gpt_int8_note`` and BASELINE.md's int8 section for the full
    experiment record.  Convergence parity holds (~2%% loss delta,
    test_int8_train)."""
    peak = _peak_tflops()
    rate, tflops, n_params, cfg = _gpt_train_rate("pallas", 8, iters=10,
                                                  matmul_int8=True)
    results["gpt_int8_bench_config"] = (
        f"L={cfg.num_layers} H={cfg.hidden_size} I={cfg.intermediate_size} "
        f"B=8 S={cfg.max_position} bf16+int8-MLP attn=pallas "
        f"params={n_params/1e6:.1f}M")
    results["gpt_int8_step_ms"] = round(1000.0 / rate, 2)
    results["gpt_int8_tokens_per_sec"] = round(rate * 8 * cfg.max_position, 0)
    results["gpt_int8_model_tflops_per_sec"] = round(tflops, 2)
    if peak:
        results["gpt_int8_mfu_pct_bf16_equiv"] = round(100.0 * tflops / peak,
                                                       2)
    if results.get("gpt_step_ms"):
        results["gpt_int8_speedup_vs_bf16"] = round(
            results["gpt_step_ms"] / results["gpt_int8_step_ms"], 3)
    # The attention-projection arm (--gpt_attn_int8), so the flag's
    # recorded "wash" verdict stays reproducible from the shipped bench.
    rate_a, _, _, _ = _gpt_train_rate("pallas", 8, iters=10,
                                      matmul_int8=True, attn_int8=True)
    results["gpt_int8_attn_step_ms"] = round(1000.0 / rate_a, 2)
    results["gpt_int8_attn_vs_mlp_only"] = round(
        results["gpt_int8_step_ms"] / results["gpt_int8_attn_step_ms"], 3)
    results["gpt_int8_note"] = (
        "r5: the fused MLP composition now WINS — bias+gelu in the fwd "
        "epilogue, gelu-bwd in the dgrad prologue, and an NT backward "
        "that reuses the fwd's quantized weight (per-col scale folded "
        "into the gradient) so the bwd does zero weight re-quantization "
        "and zero transposes. Measured 1.017x over bf16 at the flagship "
        "step (164.0 vs 166.8 ms A/B best-of-2), up from 0.84x (r4 "
        "naive) and 0.96x (XLA formulation). Default ON for the gelu "
        "MLP (quant_train.FUSED_MLP_IN_STEP); losing variants recorded "
        "in BASELINE.md. Convergence parity ~2% (test_int8_train)")


def run_quant_fused(results):
    """Fused-epilogue quant-matmul arm (ISSUE 11): the isolated-vs-in-step
    ratio of the pallas fused-quantize kernel, PINNED as bench fields.

    BENCH_r04's finding was that the kernel won isolated (264/322
    TFLOP/s) yet lost in-step (0.84-0.96x) because each opaque pallas
    call forfeited XLA's bias/gelu epilogue fusions.  This arm measures
    the fix the way the regression was found: the SAME kernel with its
    epilogue fused in VMEM vs with the epilogue split back out to XLA
    (the unfused-pallas composition), both as one isolated matmul and as
    the full two-matmul MLP chain a model layer runs per step
    (`FUSED_KERNEL_IN_STEP`'s composition boundary).  The acceptance bar
    is `qmm_fused_in_step_ratio >= 1.0` — the fused program must not be
    slower than paying the epilogue outside.  On CPU the kernels run
    under the pallas interpreter at reduced shapes (ratio recorded with
    `qmm_fused_backend = interpret`); the TPU refresh overwrites both.
    """
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops.pallas.quant_matmul import (
        quantize_cols, quantized_matmul)

    on_tpu = jax.default_backend() == "tpu"
    interp = not on_tpu
    if on_tpu:
        M, H, I = 8192, 2048, 8192      # the flagship GPT MLP shapes
        dtype = jnp.bfloat16
        iters, trials = 8, 3
    else:
        M, H, I = 256, 128, 256         # interpreter: prove the wiring
        dtype = jnp.float32
        iters, trials = 2, 2
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (M, H), dtype)
    w_in = jax.random.normal(jax.random.PRNGKey(1), (H, I),
                             jnp.float32) * 0.05
    b_in = jax.random.normal(jax.random.PRNGKey(2), (I,),
                             jnp.float32) * 0.1
    w_out = jax.random.normal(jax.random.PRNGKey(3), (I, H),
                              jnp.float32) * 0.05
    b_out = jax.random.normal(jax.random.PRNGKey(4), (H,),
                              jnp.float32) * 0.1
    qwi, swi = quantize_cols(w_in)
    qwo, swo = quantize_cols(w_out)
    bm = 256 if on_tpu else 128  # two-output VMEM budget (quant_train)

    # Every arm ends in a scalar reduce (the _sync fetch barrier); the
    # reduce is identical across arms so the ratios are unaffected.
    # --- isolated: ONE matmul, epilogue in-kernel vs handed to XLA ----
    @jax.jit
    def fused_one(x):
        y = quantized_matmul(x, qwi, swi, b_in, activation="gelu",
                             block_m=bm, interpret=interp)
        return y.astype(jnp.float32).sum()

    @jax.jit
    def unfused_one(x):
        y = quantized_matmul(x, qwi, swi, block_m=bm, interpret=interp)
        a = jax.nn.gelu(y + b_in.astype(y.dtype), approximate=True)
        return a.astype(jnp.float32).sum()

    # --- in-step: the MLP chain a model layer runs (both matmuls + the
    # epilogues + the preact emit the backward needs), per dispatch ----
    # Both arms MATERIALIZE the pre-activation (the backward's residual
    # capture) so the comparison is the honest step composition, not a
    # fused arm paying an output block the unfused arm skips.
    @jax.jit
    def fused_mlp(x):
        a, pre = quantized_matmul(x, qwi, swi, b_in, activation="gelu",
                                  want_preact=True, block_m=bm,
                                  interpret=interp)
        z = quantized_matmul(a, qwo, swo, b_out, interpret=interp)
        return (z.astype(jnp.float32).sum()
                + pre.astype(jnp.float32).sum())

    @jax.jit
    def unfused_mlp(x):
        y = quantized_matmul(x, qwi, swi, block_m=bm, interpret=interp)
        pre = (y + b_in.astype(y.dtype)).astype(x.dtype)
        a = jax.nn.gelu(pre.astype(jnp.float32),
                        approximate=True).astype(x.dtype)
        z = quantized_matmul(a, qwo, swo, interpret=interp)
        return ((z + b_out.astype(z.dtype)).astype(jnp.float32).sum()
                + pre.astype(jnp.float32).sum())

    def timed(fn):
        _sync(fn(x))                     # compile + warm
        times = []
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(x)
            _sync(out)
            times.append((time.perf_counter() - t0) / iters)
        return float(np.median(times))

    t_fused_one = timed(fused_one)
    t_unfused_one = timed(unfused_one)
    t_fused_mlp = timed(fused_mlp)
    t_unfused_mlp = timed(unfused_mlp)

    flops_one = 2.0 * M * H * I
    results["qmm_fused_config"] = (
        f"M={M} H={H} I={I} {jnp.dtype(dtype).name}, "
        f"{'tpu-mosaic' if on_tpu else 'interpret'}; isolated = one "
        f"matmul+bias+gelu, in-step = the two-matmul MLP chain with "
        f"preact emit")
    results["qmm_fused_backend"] = ("tpu-mosaic" if on_tpu
                                    else "interpret")
    results["qmm_fused_isolated_ms"] = round(t_fused_one * 1e3, 3)
    results["qmm_unfused_isolated_ms"] = round(t_unfused_one * 1e3, 3)
    results["qmm_fused_isolated_ratio"] = round(
        t_unfused_one / t_fused_one, 3)
    results["qmm_fused_isolated_tflops"] = round(
        flops_one / t_fused_one / 1e12, 2)
    results["qmm_fused_in_step_ms"] = round(t_fused_mlp * 1e3, 3)
    results["qmm_unfused_in_step_ms"] = round(t_unfused_mlp * 1e3, 3)
    results["qmm_fused_in_step_ratio"] = round(
        t_unfused_mlp / t_fused_mlp, 3)
    results["qmm_fused_note"] = (
        "in_step_ratio = unfused-pallas MLP chain time / fused-epilogue "
        "MLP chain time at identical shapes — >= 1.0 means the fused "
        "program won back the XLA epilogue fusions the r4 composition "
        "forfeited (gradient parity lives in tests/test_int8_train.py)")


# --------------------------------------------------------------- flash


def _bench_attention(attn_fn, B, S, H, D, iters, trials):
    """fwd+bwd time per call via an on-device scan chained through q."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, H, D), jnp.bfloat16)

    # k/v ride as jit ARGUMENTS (not closure constants): baked-in constants
    # at long S blow up the serialized program and hide the HBM traffic
    # being measured.
    @jax.jit
    def scan_n(q, k, v, n):
        def one(q):
            return attn_fn(q, k, v).astype(jnp.float32).sum()

        def body(carry, _):
            loss, dq = jax.value_and_grad(one)(carry)
            # Chain iterations through q so nothing is DCE'd or overlapped.
            return carry + 0.001 * dq.astype(carry.dtype), loss
        q, losses = jax.lax.scan(body, q, None, length=iters)
        return q, losses[-1] + 0.0 * n

    _, l = scan_n(q, k, v, 0)
    _sync(l)
    times = []
    for t in range(trials):
        t0 = time.perf_counter()
        _, l = scan_n(q, k, v, t + 1)
        _sync(l)
        times.append((time.perf_counter() - t0) / iters)
    return float(np.median(times))


def run_flash(results):
    import jax

    from distributed_tensorflow_tpu.ops.attention import dot_product_attention
    from distributed_tensorflow_tpu.ops.pallas.flash_attention import (
        flash_attention)

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        # Interpreter-mode pallas timing is meaningless (and glacial); the
        # CPU run only proves the harness wires up.  Use tiny shapes.
        sizes = ((256, 1, 2, 2),)
    else:
        sizes = ((2048, 4, 8, 8), (8192, 1, 4, 4))
    for S, B, H, iters in sizes:
        D = 64
        try:
            t_flash = _bench_attention(
                lambda q, k, v: flash_attention(q, k, v, causal=True),
                B, S, H, D, iters, 3)
            results[f"flash_attn_s{S}_ms"] = round(t_flash * 1000, 3)
        except Exception as e:  # record, don't kill the whole bench
            results[f"flash_attn_s{S}_error"] = repr(e)[:200]
            continue
        try:
            t_dense = _bench_attention(
                lambda q, k, v: dot_product_attention(
                    q, k, v, causal=True, backend="xla"),
                B, S, H, D, iters, 3)
            results[f"dense_attn_s{S}_ms"] = round(t_dense * 1000, 3)
            results[f"flash_vs_dense_s{S}"] = round(t_dense / t_flash, 2)
        except Exception as e:
            results[f"dense_attn_s{S}_error"] = repr(e)[:200]
    # Sliding window (banded-grid kernel): the long-context local-attention
    # lever — skipped blocks are never fetched, so cost is O(S * window).
    win_sizes = (((8192, 1024, 4, 8, 6), (32768, 1024, 4, 8, 3))
                 if on_tpu else ((256, 64, 1, 2, 2),))
    for S, W, B, H, iters in win_sizes:
        D = 64
        try:
            t_win = _bench_attention(
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                window=W),
                B, S, H, D, iters, 3)
            results[f"flash_attn_s{S}_w{W}_ms"] = round(t_win * 1000, 3)
            # Full-causal at the SAME shape, so the ratio is apples-to-apples.
            t_full = _bench_attention(
                lambda q, k, v: flash_attention(q, k, v, causal=True),
                B, S, H, D, iters, 3)
            results[f"flash_attn_s{S}_full_ms"] = round(t_full * 1000, 3)
            results[f"window_vs_full_s{S}_w{W}"] = round(t_full / t_win, 2)
        except Exception as e:
            results[f"flash_attn_s{S}_w{W}_error"] = repr(e)[:200]
    results["flash_backend_compiled"] = "tpu-mosaic" if on_tpu else "interpret"


def run_ln(results):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.ops.pallas.layer_norm import (
        make_layer_norm)

    B, S, H = 16, 1024, 1024
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H), jnp.bfloat16)

    def bench(module):
        params = module.init(jax.random.PRNGKey(1), x)

        def one(x):
            return module.apply(params, x).astype(jnp.float32).sum()
        grad_fn = jax.value_and_grad(one)

        @jax.jit
        def scan_n(x):
            def body(carry, _):
                loss, dx = grad_fn(carry)
                return carry + 0.001 * dx.astype(carry.dtype), loss
            x, losses = jax.lax.scan(body, x, None, length=16)
            return x, losses[-1]

        _, l = scan_n(x)
        _sync(l)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            _, l = scan_n(x)
            _sync(l)
            times.append((time.perf_counter() - t0) / 16)
        return float(np.median(times))

    t_fused = bench(make_layer_norm(True))
    t_plain = bench(make_layer_norm(False))
    results["fused_ln_ms"] = round(t_fused * 1000, 3)
    results["xla_ln_ms"] = round(t_plain * 1000, 3)
    results["fused_ln_vs_xla"] = round(t_plain / t_fused, 2)


# ------------------------------------------------------------- scaling


def scaling_probe(n_devices: int, per_device_batch: int = 256,
                  iters: int = 25, steps_per_call: int = 8) -> None:
    """Child process: three probes on an n-device mesh, one JSON line out.

    Weak scaling: global batch = n * per_device_batch; every probe runs the
    framework's recommended dispatch mode (``--steps_per_call`` scanned
    steps).  The three probes decompose where a rung's throughput goes:

    - ``sync_eps``   — the real sync step (psum per optimizer step): the
      number the retention ladder reports.
    - ``local_eps``  — the SAME per-device compute with ZERO collectives
      (per-replica SGD, no merge): on a shared-core virtual mesh its drop
      vs n=1 is pure host contention + sharded dispatch, so
      ``1 - sync/local`` at a rung is what the AllReduce itself costs.
    - ``psum_ms``    — K chained grad-tree psums alone (the collective the
      sync step adds), directly timing the AllReduce.

    ``loadavg`` (1-min, captured before the timed runs) records external
    host pressure so a contended driver host is visible in the artifact.
    """
    # The image may import jax at startup pinned to the attached accelerator
    # (env vars alone don't repoint it); the proxy probe wants the virtual
    # CPU mesh the parent sized via XLA_FLAGS.
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.parallel import (
        async_replicas as async_lib)
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib
    from distributed_tensorflow_tpu.parallel.mesh import DATA_AXIS

    bs = n_devices * per_device_batch
    K = steps_per_call
    loadavg = os.getloadavg()[0]
    mesh, state, _, _, _, loss_fn, host_batch = build_mnist(batch_size=bs)
    stacked = sync_lib.stack_microbatches([host_batch] * K)
    batch = jax.tree.map(
        lambda a: jax.device_put(a, mesh_lib.stacked_batch_sharding(mesh)),
        stacked)

    def timed_eps(step, st0, trials=3):
        holder = {"state": st0}
        for _ in range(3):
            holder["state"], metrics = step(holder["state"], batch)
        _sync(metrics)

        def run(n):
            st = holder["state"]
            for i in range(n):
                st, m = step(st, batch)
                if (i + 1) % 5 == 0:
                    _sync(m)  # bound the in-flight queue (XLA:CPU rendezvous)
            holder["state"] = st
            _sync(m)

        return _median_rate(run, iters, trials) * K * bs

    # Build the collective-free variant and the psum probe's grad tree
    # BEFORE the sync probe runs: the sync step donates its input state.
    # merge=False: the same scan of per-replica SGD updates with NO
    # collective anywhere — per-device compute identical to the sync step
    # minus the psum.
    local_step_fn, astate = async_lib.build_scanned_async_train_step(
        mesh, loss_fn, state, sync_period=K, merge=False)
    # The async state stacks params/opt fresh but aliases the scalar
    # global_step buffer — copy it so the donation doesn't invalidate it.
    astate = astate.replace(global_step=astate.global_step + 0)
    grads = jax.tree.map(jnp.ones_like, state.params)

    sync_step = sync_lib.build_scanned_sync_train_step(mesh, loss_fn,
                                                       num_steps=K)
    sync_eps = timed_eps(sync_step, state, trials=5)
    local_eps = timed_eps(local_step_fn, astate)

    # The AllReduce alone: K chained psums of a grad-sized tree (each
    # iteration consumes the last, so the K collectives serialize exactly
    # like the scanned sync step's do).
    def psum_k(tree):
        def body(c, _):
            c = jax.tree.map(
                lambda g: jax.lax.psum(g, DATA_AXIS) / n_devices, c)
            return c, None
        c, _ = jax.lax.scan(body, tree, None, length=K)
        return c

    psum_mapped = jax.jit(jax.shard_map(
        psum_k, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))
    np.asarray(jax.tree.leaves(psum_mapped(grads))[0])

    def run_psum(n):
        t = grads
        for i in range(n):
            t = psum_mapped(t)
            if (i + 1) % 5 == 0:
                # Each call queues K chained psums; fetch regularly to stay
                # under the XLA:CPU in-flight rendezvous bound.
                np.asarray(jax.tree.leaves(t)[0])
        np.asarray(jax.tree.leaves(t)[0])  # non-scalar leaf: full fetch barrier

    psum_calls_per_sec = _median_rate(run_psum, 20, 3) * K

    # Decompose the collective cost (VERDICT r3 #4): a 4-byte psum chain
    # times the pure cross-device RENDEZVOUS (on this virtual mesh, N
    # threads synchronizing on one core); the difference to the full
    # grad-tree psum is PAYLOAD movement.  On real ICI the rendezvous
    # floor is hardware signaling and the payload overlaps with backward
    # compute via XLA's async collectives — the floor measured here is a
    # host-proxy artifact, which is why the framework keeps GSPMD's
    # combined AllReduce instead of hand-bucketing (measured: explicit
    # shard_map flat-bucket step 0.54x GSPMD throughput, bf16-compressed
    # psum 1.29x SLOWER than f32 at these sizes — see BASELINE.md).
    tiny = [jnp.ones((1,), jnp.float32)]
    tiny_mapped = jax.jit(jax.shard_map(
        psum_k, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))
    np.asarray(jax.tree.leaves(tiny_mapped(tiny))[0])

    def run_tiny(n):
        t = tiny
        for i in range(n):
            t = tiny_mapped(t)
            if (i + 1) % 5 == 0:
                np.asarray(jax.tree.leaves(t)[0])
        np.asarray(jax.tree.leaves(t)[0])

    floor_calls_per_sec = _median_rate(run_tiny, 20, 3) * K
    psum_ms = 1000.0 / psum_calls_per_sec
    floor_ms = 1000.0 / floor_calls_per_sec
    print(json.dumps({
        "devices": n_devices,
        "examples_per_sec": sync_eps,
        "local_examples_per_sec": local_eps,
        "psum_ms": round(psum_ms, 4),
        "psum_rendezvous_floor_ms": round(floor_ms, 4),
        "psum_payload_ms": round(max(psum_ms - floor_ms, 0.0), 4),
        "loadavg": round(loadavg, 2),
    }))


def run_autotune(results):
    """Autotune leg (--mode autotune, docs/autotune.md): run the
    parallelism tuner CLI as a subprocess on an 8-device virtual CPU mesh
    (the CI MLP workload), and pin the whole contract — the cost-model
    pruning measures <= 40% of the enumerated space, and the measured
    winner beats the naive all-devices-DP default by >= 1.15x.  A
    subprocess for two reasons: the tuner's per-trial SIGALRM would fight
    this harness's per-leg alarm, and the virtual mesh size must be set
    before jax initializes."""
    import tempfile

    out_dir = tempfile.mkdtemp(prefix="dtf_bench_autotune_")
    profile_path = os.path.join(out_dir, "profile.json")
    trials_path = os.path.join(out_dir, "trials.jsonl")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m",
         "distributed_tensorflow_tpu.tools.autotune",
         "--workload", "mlp", "--steps", "8", "--warmup", "2",
         "--microbatches", "1,2", "--measure_fraction", "0.4",
         "--out", profile_path, "--metrics_file", trials_path],
        env=env, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"autotune subprocess rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    headline = json.loads(lines[-1])
    results["autotune_workload"] = headline["workload"]
    results["autotune_searched"] = headline["searched"]
    results["autotune_pruned"] = headline["pruned"]
    results["autotune_measured"] = headline["measured"]
    results["autotune_winner"] = headline["winner"]
    results["autotune_winner_step_ms"] = headline["winner_step_ms"]
    results["autotune_default_step_ms"] = headline["default_step_ms"]
    results["autotune_best_vs_default"] = headline["best_vs_default"]
    results["autotune_profile"] = profile_path
    measured_frac = headline["measured"] / max(headline["searched"], 1)
    assert measured_frac <= 0.4 + 1e-9, (
        f"pruning measured {measured_frac:.0%} of the space (> 40%)")
    ratio = headline["best_vs_default"]
    assert ratio is not None and ratio >= 1.15, (
        f"autotuned layout only {ratio}x the default (bar 1.15x)")
    # The emitted artifact must load as a valid run profile — the thing
    # train.py --profile consumes.
    from distributed_tensorflow_tpu.parallel.mesh import load_run_profile
    profile = load_run_profile(profile_path)
    results["autotune_profile_layout"] = profile["parallel"]


def run_scaling(results, max_devices: int = 8):
    """1->N weak-scaling ladder.  Measures every n this process's backend can
    host; when the attached accelerator is single-chip, runs the ladder as
    CPU virtual-mesh subprocesses (proxy measurement, labeled as such)."""
    import jax

    have = len(jax.devices())
    ladder = [n for n in (1, 2, 4, 8) if n <= max_devices]

    if have >= max(ladder) and jax.default_backend() == "tpu":
        # Real multi-chip rig: measure each rung in-process on a
        # device-prefix mesh — this is the BASELINE.md hardware number.
        probes = {}
        for n in ladder:
            bs = n * 256
            mesh, state, step, _, sharding, _, host_batch = build_mnist(
                batch_size=bs, num_devices=n)
            rate = bench_framework(state, step, sharding, host_batch,
                                   iters=100, trials=3)
            probes[n] = rate * bs
        _record_scaling(results, probes)
        results["scaling_measurement"] = "tpu hardware weak-scaling"
        return

    def probe_once(n):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}")
        env["PYTHONPATH"] = REPO
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--mode", "scaling_probe", "--devices", str(n)],
            env=env, capture_output=True, text=True, timeout=600)
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout else ""
        try:
            obs = json.loads(line)
            # A stray last line can parse as JSON without being the probe
            # payload; degrade to a failed probe, not a KeyError upstream.
            keys = ("examples_per_sec", "local_examples_per_sec",
                    "psum_ms", "psum_rendezvous_floor_ms",
                    "psum_payload_ms", "loadavg")
            if not (isinstance(obs, dict) and all(k in obs for k in keys)):
                return None
            return obs
        except Exception:
            return None

    probes, details = {}, {}
    for n in ladder:
        # Two probes per rung; per-metric best (max throughput, min psum
        # time): the shared-core proxy's noise is one-sided (external
        # interference only slows a rung), so the best observation is the
        # least-interference estimate.
        obs = [o for o in (probe_once(n), probe_once(n)) if o]
        if not obs:
            probes[n] = None
            continue
        best = {
            "sync_eps": max(o["examples_per_sec"] for o in obs),
            "local_eps": max(o["local_examples_per_sec"] for o in obs),
            # floor/payload must come from the SAME observation as the
            # psum they decompose, or floor + payload != psum_ms.
            **(lambda p: {"psum_ms": p["psum_ms"],
                          "psum_floor_ms": p["psum_rendezvous_floor_ms"],
                          "psum_payload_ms": p["psum_payload_ms"]})(
                min(obs, key=lambda o: o["psum_ms"])),
            "loadavg": max(o["loadavg"] for o in obs),
        }
        probes[n] = best["sync_eps"]
        details[n] = best
    _record_scaling(results, probes, hardware=False)
    base = details.get(1)
    if base:
        # Multiplicative decomposition of a rung's retention:
        #   sync_n/sync_1 = (local_n/local_1) * (sync_n/local_n) / (sync_1/local_1)
        # local_n/local_1 has zero collectives -> host contention + sharded
        # dispatch; 1 - sync_n/local_n -> what the AllReduce costs at n.
        results["scaling_overhead_breakdown"] = {
            str(n): {
                "sync_examples_per_sec": round(d["sync_eps"], 1),
                "local_examples_per_sec": round(d["local_eps"], 1),
                "host_contention_retention_pct": round(
                    100 * d["local_eps"] / base["local_eps"], 1),
                "collective_overhead_pct": round(
                    100 * (1 - d["sync_eps"] / d["local_eps"]), 1),
                "psum_ms_per_step": d["psum_ms"],
                # rendezvous floor: a 4-byte psum chain — on the proxy,
                # N threads synchronizing on one core; payload = the rest,
                # which real-TPU async collectives overlap with backward.
                "psum_rendezvous_floor_ms": d["psum_floor_ms"],
                "psum_payload_ms": d["psum_payload_ms"],
                "host_loadavg_1min": d["loadavg"],
            } for n, d in details.items()}
    results["scaling_measurement"] = (
        "cpu-virtual-mesh weak-scaling proxy: virtual devices share the "
        "host's cores, so ideal weak scaling holds TOTAL throughput flat "
        "(retention = collective/sharding overhead + host contention; the "
        "breakdown separates the two via a zero-collective variant of the "
        "same step and a psum-only probe); on a real pod slice this same "
        "harness reports throughput_n/(n*throughput_1) vs the BASELINE.md "
        ">=90% target")


def _record_scaling(results, probes, hardware=True):
    base = probes.get(1)
    results["scaling_examples_per_sec"] = {
        str(n): round(v, 1) if v else None for n, v in probes.items()}
    if not base:
        return
    if hardware:
        eff = {n: (v / base / n) if v else None for n, v in probes.items()}
        key = "scaling_efficiency_pct"
    else:
        # Shared-core proxy: ideal = flat total throughput; the ratio
        # isolates what the framework adds per extra mesh device
        # (AllReduce, sharded dispatch), not hardware speedup.
        eff = {n: (v / base) if v else None for n, v in probes.items()}
        key = "scaling_proxy_throughput_retention_pct"
    results[key] = {
        str(n): round(100 * e, 1) if e else None for n, e in eff.items()}
    worst = min((e for n, e in eff.items() if e and n > 1), default=None)
    if worst is not None:
        results[key + "_worst"] = round(100 * worst, 1)


# ---------------------------------------------------------------- main


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--mode", default="all",
                        help="comma list of all|extended|mnist|converge|"
                             "transformer|profile|mfu_ladder|"
                             "transformer_long|flash|ln|scanned|"
                             "feed|scaling|decode|async_exchange|"
                             "param_exchange|serve_decode|serve|"
                             "router|speculative|int8_train|"
                             "quant_fused|autotune|scaling_probe")
    parser.add_argument("--devices", type=int, default=1,
                        help="scaling_probe child: mesh size")
    args = parser.parse_args()

    if args.mode == "scaling_probe":
        scaling_probe(args.devices)
        return

    modes = set(args.mode.split(","))
    if "extended" in modes:
        modes = {"mnist", "transformer", "profile", "mfu_ladder",
                 "transformer_long", "flash", "ln", "scanned", "feed",
                 "scaling", "decode", "converge", "async_exchange",
                 "param_exchange", "serve_decode", "serve", "router",
                 "speculative", "int8_train", "quant_fused", "autotune"}
    elif "all" in modes:
        modes = {"mnist", "transformer", "profile", "mfu_ladder", "flash",
                 "ln", "scanned", "feed", "scaling", "decode", "converge",
                 "async_exchange", "param_exchange", "serve_decode",
                 "serve", "router", "speculative", "int8_train",
                 "quant_fused", "autotune"}

    # The full suite takes tens of minutes (compiles dominate);
    # a driver-invoked run must emit its JSON line before any outer timeout.
    # Modes run in priority order under a wall-clock budget: once it is
    # spent, the rest are recorded as skipped and the artifact merge keeps
    # their previously committed values.  BENCH_BUDGET_S=0 removes the cap
    # (the full-suite refresh used when committing BENCH_DETAILS.json).
    budget = float(os.environ.get("BENCH_BUDGET_S", "480"))
    t_start = time.perf_counter()

    # An unavailable backend raises here and ends the run non-zero before
    # any artifact is written: a bench that carried on on another backend
    # would record its numbers next to a device it never touched.
    import jax

    from distributed_tensorflow_tpu.utils.backend import configure_backend
    configure_backend()
    results: dict = {"backend": jax.default_backend(),
                     "n_devices": len(jax.devices())}

    # Rough per-mode costs (seconds, from the last v5e pass) so the budget
    # check can refuse a mode it cannot finish, not just stop late.
    est = {"mnist": 55, "converge": 40, "transformer": 150, "profile": 30,
           "mfu_ladder": 170, "transformer_long": 180, "flash": 60,
           "ln": 35, "scanned": 30, "feed": 100, "scaling": 180,
           "decode": 330, "async_exchange": 150, "param_exchange": 300,
           "serve_decode": 150, "serve": 150, "router": 120,
           "speculative": 420, "int8_train": 220, "quant_fused": 60,
           "autotune": 120}

    primary_value = primary_ratio = None
    failed_legs: list[str] = []
    skipped_legs: list[str] = []
    suite_error = None
    # Per-leg wall-clock limit: generous multiple of the measured cost so
    # a wedged compile or hung device fails ONE leg, not the headline
    # (five rounds of BENCH_r*.json had no parseable headline because a
    # crash exited before the final print).  BENCH_LEG_TIMEOUT_S overrides;
    # 0 disables.
    leg_timeout_env = os.environ.get("BENCH_LEG_TIMEOUT_S", "")
    # Priority order == the driver's 480s-budget window: the round's fresh
    # evidence (profile, scaling breakdown, async exchange) must land
    # before the long-tail arms that a carried artifact already covers.
    try:
        for name, fn in (("mnist", None), ("transformer", run_transformer),
                         ("profile", run_profile),
                         ("serve", run_serve),
                         ("router", run_router),
                         ("serve_decode", run_serve_decode),
                         ("async_exchange", run_async_exchange),
                         ("param_exchange", run_param_exchange),
                         ("speculative", run_speculative),
                         ("int8_train", run_int8_train),
                         ("quant_fused", run_quant_fused),
                         ("autotune", run_autotune),
                         ("scaling", run_scaling),
                         ("mfu_ladder", run_mfu_ladder),
                         ("converge", run_converge),
                         ("flash", run_flash), ("ln", run_ln),
                         ("scanned", run_scanned), ("feed", run_feed),
                         ("decode", run_decode),
                         ("transformer_long", run_transformer_long)):
            if name not in modes:
                continue
            elapsed = time.perf_counter() - t_start
            cost = est.get(name, 60)
            if name == "profile" and not _GPT_STEP_CACHE:
                cost = 180  # cold path recompiles the flagship step itself
            if budget and name != "mnist" and elapsed + cost > budget:
                results[f"{name}_skipped_for_budget"] = round(elapsed, 1)
                skipped_legs.append(name)
                if name == "profile":
                    # Profile is the cache's only consumer: once it is
                    # skipped the transformer arm's parked GB of HBM must
                    # not survive into the remaining arms.
                    _GPT_STEP_CACHE.clear()
                continue
            leg_limit = (float(leg_timeout_env) if leg_timeout_env
                         else max(4.0 * cost, 300.0))
            try:
                fault = _injected_leg_fault(name)
                with _leg_timeout(leg_limit):
                    if fault == "crash":
                        raise RuntimeError(f"injected crash in leg {name!r}")
                    if fault == "hang":
                        time.sleep(leg_limit + 3600)
                    if name == "mnist":
                        primary_value, primary_ratio = run_mnist(results)
                    else:
                        fn(results)
                # A succeeding re-run clears the mode's stale error/skip
                # marker from the merged artifact (None values drop below).
                results[f"{name}_error"] = None
                results[f"{name}_skipped_for_budget"] = None
            except (BenchLegTimeout, Exception) as e:
                results[f"{name}_error"] = repr(e)[:300]
                failed_legs.append(name)
            if name == "transformer" and "profile" not in modes:
                # Profile (the cache's only consumer) will never run in
                # this invocation — drop the parked flagship state before
                # the next arm rather than pinning GB of HBM through all
                # of them.
                _GPT_STEP_CACHE.clear()
    except BaseException as e:  # noqa: BLE001 — device loss, SIGINT:
        # the suite is over, but the headline contract below still holds.
        suite_error = repr(e)[:300]
        results["suite_error"] = suite_error

    # --- headline: ALWAYS emitted, even when a leg or the suite died ----
    # Provenance: stamp which keys THIS run measured, so the merged
    # artifact can never silently present carried-over values as current
    # (see BASELINE.md "Artifact provenance").
    results["fresh_keys"] = sorted(
        k for k, v in results.items() if v is not None)
    results["fresh_run_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())

    # Merge into the existing artifact: a partial --mode run updates only
    # the metrics it measured and keeps the recorded primary value, so a
    # feed-only (or flash-only) invocation never clobbers the report.
    details_path = os.path.join(REPO, "BENCH_DETAILS.json")
    prior = {}
    try:
        with open(details_path) as fh:
            prior = json.load(fh)
    except Exception:
        pass
    merged = dict(prior.get("extra", {}))
    merged.update(results)
    merged = {k: v for k, v in merged.items() if v is not None}
    if primary_value is None:
        primary_value = prior.get("value", 0.0)
        primary_ratio = prior.get("vs_baseline", 0.0)

    payload = {
        "metric": "mnist_mlp_steps_per_sec_per_chip",
        "value": round(primary_value or 0.0, 2),
        "unit": "steps/sec/chip",
        "vs_baseline": round(primary_ratio or 0.0, 3),
        "extra": merged,
    }
    try:
        with open(details_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:
        # A read-only checkout must not cost the run its headline.
        results["artifact_write_error"] = repr(e)[:200]
    # The driver captures only the last ~2000 bytes of stdout: the final
    # line must stay compact (the full payload lives in BENCH_DETAILS.json)
    # and it must ALWAYS parse — ok:false names what died instead of the
    # crash eating the line entirely.
    ok = suite_error is None and not failed_legs
    headline = {
        "metric": payload["metric"],
        "value": payload["value"],
        "unit": payload["unit"],
        "vs_baseline": payload["vs_baseline"],
        "details": "BENCH_DETAILS.json",
        "fresh_keys": len(results["fresh_keys"]),
        "ok": ok,
        "failed_legs": failed_legs,
        "skipped_legs": skipped_legs,
    }
    if suite_error is not None:
        headline["suite_error"] = suite_error
    print(json.dumps(headline), flush=True)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
