"""Telemetry-driven parallelism autotuner (docs/autotune.md).

Device Placement Optimization with RL (PAPERS.md, 1706.04972) argues the
parallelism layout should be *searched with measured runtime as the
reward*, not hand-picked.  This tool is that search for the framework's
declarative layouts (``parallel.mesh.ParallelConfig``):

1. **enumerate** — mesh shape x (DP, TP, SP, PP) x microbatch x
   quantization arms over the attached device topology (submeshes use a
   device prefix, so an 8-device host searches 1/2/4/8-device layouts in
   one process);
2. **prune** — score every arm with the analytic cost model
   (``tools.cost_model.estimate_config_cost``: roofline + per-axis comm
   terms on TPU, the rendezvous-dominated host proxy on CPU) and keep
   only ``--measure_fraction`` of the space (default 40%), the naive
   default layout always included as the comparison baseline;
3. **measure** — each survivor runs a short timed trial through the
   framework's own step builders (``parallel.sync``), compile time and
   steady-state step time recorded SEPARATELY so a one-off compile never
   poisons the reward; every trial is crash/timeout-guarded (SIGALRM +
   exception containment — a layout the backend cannot run is a
   ``crash`` verdict, not a dead tuner);
4. **emit** — the winner becomes a reusable run profile
   (``parallel.mesh.save_run_profile``) that ``train.py
   --profile=<file>`` consumes, and every trial lands on the telemetry
   bus as a ``kind="autotune_trial"`` record that ``summarize_run``
   (``--check`` contract included) rolls into the report.

``--mode serving`` runs the same trial loop over the serving engine's
knobs (``num_slots``, ``page_size``, ``spec_k``, ``prefill_chunk``),
scored against SLO objectives (``serving.slo.parse_slos`` grammar): the
winner is the arm with the fewest violated objectives, throughput
breaking ties.

Usage::

    python -m distributed_tensorflow_tpu.tools.autotune \
        --workload mlp --steps 8 --out profile.json \
        --metrics_file trials.jsonl
    python -m distributed_tensorflow_tpu.train --profile profile.json ...

Prints ONE final JSON line (searched/pruned/measured counts, winner,
best-vs-default ratio, profile path) — the CI gate's machine contract.
SIGALRM-based trial timeouts assume the main thread; run the tuner as
its own process (ci.sh's autotune gate does).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import signal
import sys
import time
from typing import Any, Callable

from . import cost_model
from ..parallel.mesh import ParallelConfig, save_run_profile


class TrialTimeout(BaseException):
    """A tuner trial overran its wall-clock budget (a wedged compile or a
    deadlocked collective); BaseException so the trial's own broad
    exception containment cannot swallow it."""


@contextlib.contextmanager
def _trial_timeout(seconds: float):
    """SIGALRM per-trial timeout (main thread, POSIX; 0 disables)."""
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def handler(signum, frame):
        raise TrialTimeout(f"trial exceeded its {seconds:.0f}s limit")

    old = signal.signal(signal.SIGALRM, handler)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


# ------------------------------------------------------------ workloads


@dataclasses.dataclass
class Workload:
    """One tunable training workload: identity, cost-model dims, and a
    trial assembler that interprets a ParallelConfig into (state, step,
    device batch) through the framework's own builders."""

    name: str
    batch_size: int
    dims: dict[str, int]              # n_params/tokens_per_step/+transformer
    supports: tuple[str, ...]         # searchable axes: data/model/seq/pipe
    quant_arms: tuple[str, ...]       # ("off",) or ("off", "int8")
    make_trial: Callable[["Workload", ParallelConfig], tuple]
    seq_len: int = 0
    #: Extra workload keys written into the emitted profile (knobs the
    #: trials pinned that train.py --profile must reproduce, e.g. dtype).
    profile_workload: dict[str, Any] = dataclasses.field(
        default_factory=dict)

    def invalid_reason(self, cfg: ParallelConfig) -> str | None:
        """Static feasibility gate (free pruning; never counts as a
        measured trial)."""
        b, m, dp = self.batch_size, cfg.microbatch, max(cfg.data, 1)
        if b % m:
            return f"batch {b} not divisible by microbatch {m}"
        if (b // m) % dp:
            return f"microbatch size {b // m} not divisible by dp {dp}"
        if cfg.seq > 1 and self.seq_len and self.seq_len % cfg.seq:
            return f"seq_len {self.seq_len} not divisible by sp {cfg.seq}"
        if cfg.pipe > 1:
            layers = self.dims.get("num_layers", 0)
            if not layers or layers % cfg.pipe:
                return f"{layers} layers not divisible by pp {cfg.pipe}"
            if cfg.microbatch < 2:
                return "pipeline layouts need microbatch >= 2"
            if cfg.quantize != "off":
                # Mirrors train.py: the int8 arm is not plumbed through
                # the pipeline bundles — measuring the combination would
                # silently time the unquantized step under an int8 label.
                return f"{cfg.quantize} arm not wired into pipeline layouts"
        return None


def _mlp_trial(wl: Workload, cfg: ParallelConfig):
    """Assemble one MLP trial: replicated data-parallel layout."""
    import jax
    import numpy as np

    from ..models.registry import build_mnist_mlp
    from ..parallel import sync as sync_lib

    mesh = cfg.build_mesh()
    bundle = build_mnist_mlp(wl.dims["hidden_units"], 0.1)
    state = cfg.place_state(mesh, bundle.state, bundle.sharding_rules)
    if cfg.microbatch > 1:
        step = sync_lib.build_accumulating_sync_train_step(
            mesh, bundle.loss_fn, accum_steps=cfg.microbatch)
    else:
        step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn)
    rng = np.random.default_rng(0)
    b = wl.batch_size // cfg.microbatch
    xs = rng.random((b, 784), np.float32)
    ys = np.eye(10, dtype=np.float32)[rng.integers(0, 10, b)]
    batch = (xs, ys)
    if cfg.microbatch > 1:
        batch = tuple(np.stack([a] * cfg.microbatch) for a in batch)
    sharding = cfg.batch_sharding(mesh, stacked=cfg.microbatch > 1)
    batch = tuple(jax.device_put(a, sharding) for a in batch)
    return mesh, state, step, batch


def _gpt_trial(wl: Workload, cfg: ParallelConfig):
    """Assemble one GPT-mini trial: DP x TP x SP x PP through the same
    bundles train.py uses (pipeline layouts ride the bundle's own
    place_state + train_step_builder)."""
    import jax
    import numpy as np

    from ..models import gpt as gpt_lib
    from ..models import registry
    from ..ops.attention import attention_mesh
    from ..parallel import sync as sync_lib

    mesh = cfg.build_mesh()
    seq = wl.seq_len
    # Model init traces attention (flax init runs the forward): the ring
    # backend needs its mesh for the whole build, exactly as train.py
    # wraps registry.build.
    with attention_mesh(mesh):
        if cfg.pipe > 1:
            # dtype pinned to float32 like every other arm: one dtype
            # across the whole space, or the comparison is meaningless
            # (and it is recorded in the profile's workload section so
            # train.py --profile reproduces the measured configuration).
            bundle = registry.build_gpt_pipeline(
                1e-3, mesh, seq_len=seq, n_micro=cfg.microbatch,
                dtype="float32")
            state = bundle.place_state(mesh, bundle.state)
            if bundle.train_step_builder is not None:   # 1f1b/interleaved
                step = bundle.train_step_builder(mesh)
            else:                                       # gpipe: AD via scan
                step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn)
            stacked = False
        else:
            bundle = registry.build_gpt_mini(
                1e-3, seq_len=seq,
                attention_backend=cfg.resolved_attention(),
                dtype="float32", matmul_int8=cfg.quantize == "int8")
            state = cfg.place_state(mesh, bundle.state,
                                    bundle.sharding_rules)
            if cfg.microbatch > 1:
                step = sync_lib.build_accumulating_sync_train_step(
                    mesh, bundle.loss_fn, accum_steps=cfg.microbatch)
            else:
                step = sync_lib.build_sync_train_step(mesh, bundle.loss_fn)
            stacked = cfg.microbatch > 1
    b = wl.batch_size // (cfg.microbatch if stacked else 1)
    tokens = np.asarray(gpt_lib.synthetic_lm_batch(
        0, b, seq, gpt_lib.mini())["tokens"])
    batch = {"tokens": tokens}
    if stacked:
        batch = {"tokens": np.stack([tokens] * cfg.microbatch)}
    sharding = cfg.batch_sharding(mesh, stacked=stacked)
    batch = jax.tree.map(lambda a: jax.device_put(a, sharding), batch)
    return mesh, state, step, batch


def mlp_workload(batch_size: int = 256, hidden: int = 128) -> Workload:
    n_params = 784 * hidden + hidden + hidden * 10 + 10
    return Workload(
        name="mnist_mlp", batch_size=batch_size,
        dims={"n_params": n_params, "tokens_per_step": batch_size,
              "hidden_units": hidden},
        supports=("data",), quant_arms=("off",), make_trial=_mlp_trial)


def gpt_mini_workload(batch_size: int = 8, seq_len: int = 64) -> Workload:
    from ..models import gpt as gpt_lib
    cfg = gpt_lib.mini()
    # Parameter count from the config dims (embedding + blocks + head);
    # close enough for the ranking cost model.
    h, L, v = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    n_params = v * h * 2 + L * (12 * h * h)
    return Workload(
        name="gpt_mini", batch_size=batch_size, seq_len=seq_len,
        dims={"n_params": n_params, "tokens_per_step": batch_size * seq_len,
              "num_layers": L, "hidden_size": h, "seq_len": seq_len},
        supports=("data", "model", "seq", "pipe"),
        quant_arms=("off", "int8"), make_trial=_gpt_trial,
        # Knobs every gpt trial PINS (one dtype across the space; the
        # registry defaults for schedule/remat/window/kv_heads) — recorded
        # so train.py --profile reproduces the measured configuration
        # even against a stale command line.
        profile_workload={"bert_dtype": "float32",
                          "pipeline_schedule": "gpipe", "remat": False,
                          "attention_window": 0, "kv_heads": 0})


WORKLOADS = {"mlp": mlp_workload, "gpt_mini": gpt_mini_workload}


# ------------------------------------------------------------ the space


def default_config(n_devices: int) -> ParallelConfig:
    """The naive default layout: pure DP over every device — what a
    plain ``train.py`` launch builds.  Every search measures it as the
    reward baseline."""
    return ParallelConfig(data=n_devices)


def enumerate_space(n_devices: int, workload: Workload, *,
                    microbatches: tuple[int, ...] = (1, 2),
                    quant_arms: tuple[str, ...] | None = None,
                    device_counts: tuple[int, ...] | None = None,
                    ) -> list[ParallelConfig]:
    """Every statically feasible layout of the search space.

    Device counts default to the powers of two up to ``n_devices`` (plus
    ``n_devices`` itself); each count fans out into the axis
    factorizations the workload supports, crossed with the microbatch
    and quantization arms.  The naive default layout is always element 0.
    """
    if device_counts is None:
        device_counts = tuple(
            sorted({min(2 ** k, n_devices)
                    for k in range(0, 1 + max(0, int(
                        math.log2(max(n_devices, 1)))))}
                   | {n_devices}))
    if quant_arms is not None:
        # Strict like ParallelConfig.from_dict: a typo'd or unsupported
        # arm must never silently degrade to an off-only search the user
        # reads as "the quantized arm lost".
        bad = [q for q in quant_arms if q not in workload.quant_arms]
        if bad:
            raise ValueError(
                f"quant arm(s) {bad} not supported by workload "
                f"{workload.name!r} (supported: {workload.quant_arms})")
    arms = tuple(quant_arms) if quant_arms else workload.quant_arms
    space: list[ParallelConfig] = []
    seen = set()

    def _add(cfg: ParallelConfig):
        key = tuple(sorted(cfg.to_dict().items()))
        if key not in seen and workload.invalid_reason(cfg) is None:
            seen.add(key)
            space.append(cfg)

    _add(default_config(n_devices))
    for n in device_counts:
        for tp in ([1, 2, 4] if "model" in workload.supports else [1]):
            for sp in ([1, 2] if "seq" in workload.supports else [1]):
                for pp in ([1, 2] if "pipe" in workload.supports else [1]):
                    if [tp, sp, pp].count(1) < 2:
                        # One non-trivial inner axis at a time: the
                        # nested-shard_map combinations train.py itself
                        # rejects stay out of the space.
                        continue
                    inner = tp * sp * pp
                    if n % inner:
                        continue
                    dp = n // inner
                    for m in microbatches:
                        for q in arms:
                            with contextlib.suppress(ValueError):
                                _add(ParallelConfig(
                                    data=dp, model=tp, seq=sp, pipe=pp,
                                    microbatch=m, quantize=q))
    return space


def score_space(space: list[ParallelConfig], workload: Workload, *,
                cost_profile: str) -> list[dict]:
    """Analytic cost per layout, index-aligned with ``space``."""
    return [cost_model.estimate_config_cost(
        cfg.to_dict(), cost_profile=cost_profile, **{
            k: workload.dims.get(k, 0)
            for k in ("n_params", "tokens_per_step", "num_layers",
                      "hidden_size", "seq_len")})
        for cfg in space]


def select_for_measurement(space: list[ParallelConfig],
                           scores: list[dict],
                           measure_fraction: float,
                           default: ParallelConfig
                           ) -> list[ParallelConfig]:
    """Cost-model pruning: the measured set is at most
    ``measure_fraction`` of the space (floor, min 1), cheapest-estimated
    first, with the default layout always occupying one slot (it is the
    reward baseline — a search that never measures the default cannot
    report a speedup).  A default the feasibility filter rejected from
    the space (e.g. batch not divisible by the device count) is NOT
    forced in: measuring a doomed trial would burn budget for a null
    baseline anyway."""
    budget = max(1, int(measure_fraction * len(space)))
    ranked = [cfg for _, cfg in sorted(
        zip(scores, space), key=lambda p: p[0]["est_step_ms"])]
    keep = ranked[:budget]
    if default not in keep and default in space:
        if len(keep) == budget and budget > 1:
            keep = keep[:-1]
        elif len(keep) == budget:          # budget == 1: default IS the set
            keep = []
        keep.append(default)
    return keep


# -------------------------------------------------------------- trials


def run_trial(cfg: ParallelConfig, workload: Workload, *, steps: int = 8,
              warmup: int = 2, timeout_s: float = 120.0) -> dict:
    """One guarded measured trial; never raises.

    Returns ``{config, describe, verdict, compile_ms, step_ms, mfu,
    error}`` — ``verdict`` is ``ok``, ``crash``, or ``timeout``; on a
    non-ok verdict the timing fields are None (keys always present: the
    telemetry contract).  Compile cost is the first call minus the
    steady-state median, so recompiles never poison the reward.
    """
    result = {"config": cfg.to_dict(), "describe": cfg.describe(),
              "verdict": "ok", "compile_ms": None, "step_ms": None,
              "mfu": None, "error": None}
    try:
        with _trial_timeout(timeout_s):
            timing = _run_trial_inner(cfg, workload, steps=steps,
                                      warmup=warmup)
        result.update(timing)
    except TrialTimeout as e:
        result.update(verdict="timeout", error=str(e))
    except Exception as e:  # noqa: BLE001 — containment is the feature
        result.update(verdict="crash", error=repr(e)[:300])
    return result


def _run_trial_inner(cfg: ParallelConfig, workload: Workload, *,
                     steps: int, warmup: int) -> dict:
    import jax
    import numpy as np

    from ..ops.attention import attention_mesh

    cfg = cfg.resolve(len(jax.devices()))
    t_build = time.perf_counter()
    mesh, state, step, batch = workload.make_trial(workload, cfg)
    with attention_mesh(mesh):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        float(jax.tree.leaves(metrics)[0])          # full completion barrier
        first_ms = (time.perf_counter() - t0) * 1000.0
        for _ in range(warmup):
            state, metrics = step(state, batch)
        float(jax.tree.leaves(metrics)[0])
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch)
            float(jax.tree.leaves(metrics)[0])
            times.append((time.perf_counter() - t0) * 1000.0)
    step_ms = float(np.median(times))
    peak = cost_model.peak_flops_per_chip()
    mfu = None
    if peak:
        flops = cost_model.train_step_flops(
            workload.dims["n_params"], workload.dims["tokens_per_step"],
            num_layers=workload.dims.get("num_layers", 0),
            hidden_size=workload.dims.get("hidden_size", 0),
            seq_len=workload.dims.get("seq_len", 0))
        degree = cfg.total_devices()
        mfu = round(100.0 * flops / (step_ms / 1000.0) / (peak * degree), 2)
    return {"verdict": "ok", "step_ms": round(step_ms, 3),
            "compile_ms": round(max(first_ms - step_ms, 0.0), 1),
            "mfu": mfu, "build_ms": round(
                (time.perf_counter() - t_build) * 1000.0, 1)}


# -------------------------------------------------------------- search


def search(workload: Workload, *, steps: int = 8, warmup: int = 2,
           trial_timeout_s: float = 120.0, measure_fraction: float = 0.4,
           microbatches: tuple[int, ...] = (1, 2),
           quant_arms: tuple[str, ...] | None = None,
           device_counts: tuple[int, ...] | None = None,
           cost_profile: str | None = None, telemetry=None,
           measure_fn: Callable[..., dict] | None = None) -> dict:
    """The full train-mode search; returns the summary dict (winner,
    default, ratio, counts, every trial).  ``measure_fn`` is injectable
    for tests (same signature/return shape as :func:`run_trial`)."""
    import jax

    n_devices = len(jax.devices())
    if cost_profile is None:
        cost_profile = "tpu" if jax.default_backend() == "tpu" else "host"
    default = default_config(n_devices)
    space = enumerate_space(n_devices, workload, microbatches=microbatches,
                            quant_arms=quant_arms,
                            device_counts=device_counts)
    scores = score_space(space, workload, cost_profile=cost_profile)
    est_by_cfg = dict(zip(space, scores))
    chosen = select_for_measurement(space, scores, measure_fraction, default)
    measure = measure_fn or run_trial
    trials = []
    for i, cfg in enumerate(chosen):
        est = est_by_cfg.get(cfg, {}).get("est_step_ms")
        r = measure(cfg, workload, steps=steps, warmup=warmup,
                    timeout_s=trial_timeout_s)
        r["default"] = cfg == default
        r["est_step_ms"] = est
        trials.append(r)
        if telemetry is not None:
            telemetry.emit(
                "autotune_trial", step=i, trial=i, phase="train",
                workload=workload.name, config=r["config"],
                layout=r["describe"], est_step_ms=est,
                compile_ms=r["compile_ms"], step_ms=r["step_ms"],
                mfu=r["mfu"], verdict=r["verdict"], error=r["error"],
                default=r["default"])
        print(f"[autotune] trial {i + 1}/{len(chosen)} {r['describe']}: "
              f"{r['verdict']}"
              + (f" step {r['step_ms']}ms compile {r['compile_ms']}ms"
                 if r["verdict"] == "ok" else f" ({r['error']})"),
              flush=True)
    ok = [r for r in trials if r["verdict"] == "ok"]
    winner = min(ok, key=lambda r: r["step_ms"]) if ok else None
    default_trial = next((r for r in trials if r["default"]), None)
    ratio = None
    if winner and default_trial and default_trial["verdict"] == "ok":
        ratio = round(default_trial["step_ms"] / winner["step_ms"], 3)
    return {
        "mode": "train", "workload": workload.name,
        "n_devices": n_devices, "cost_profile": cost_profile,
        "searched": len(space), "measured": len(chosen),
        "pruned": len(space) - len(chosen),
        "trials": trials, "winner": winner,
        "default_trial": default_trial, "best_vs_default": ratio,
    }


# ------------------------------------------------------- serving knobs


def serving_space(slots=(4, 8), page_sizes=(16,), spec_ks=(0, 6),
                  prefill_chunks=(0,), *, num_pages: int = 128,
                  max_pages_per_seq: int = 4) -> list[dict]:
    """The serving-knob arms (docs/autotune.md): geometry combinations a
    pool of ``num_pages`` pages can actually host."""
    arms = []
    for s in slots:
        if s * max_pages_per_seq > num_pages:
            continue  # admission could never reserve worst-case
        for p in page_sizes:
            for k in spec_ks:
                for c in prefill_chunks:
                    arms.append({"num_slots": s, "page_size": p,
                                 "spec_k": k, "prefill_chunk": c,
                                 "num_pages": num_pages,
                                 "max_pages_per_seq": max_pages_per_seq})
    return arms


def _describe_arm(arm: dict) -> str:
    return (f"slots{arm['num_slots']}-page{arm['page_size']}"
            f"-spec{arm['spec_k']}-chunk{arm['prefill_chunk']}")


def run_serving_trial(arm: dict, setup: dict, *, n_requests: int = 12,
                      prompt_len: int = 8, gen_tokens: int = 16,
                      timeout_s: float = 300.0) -> dict:
    """One guarded serving-knob trial: drive the continuous-batching
    engine in-process (engine + fair scheduler, no sockets) and record
    the request latency
    distribution plus per-engine-step cost."""
    result = {"config": dict(arm), "describe": _describe_arm(arm),
              "verdict": "ok", "compile_ms": None, "step_ms": None,
              "mfu": None, "error": None}
    try:
        with _trial_timeout(timeout_s):
            result.update(_run_serving_trial_inner(
                arm, setup, n_requests=n_requests, prompt_len=prompt_len,
                gen_tokens=gen_tokens))
    except TrialTimeout as e:
        result.update(verdict="timeout", error=str(e))
    except Exception as e:  # noqa: BLE001 — containment is the feature
        result.update(verdict="crash", error=repr(e)[:300])
    return result


def _run_serving_trial_inner(arm: dict, setup: dict, *, n_requests: int,
                             prompt_len: int, gen_tokens: int) -> dict:
    import numpy as np

    from ..serving.engine import DecodeEngine, EngineConfig
    from ..serving.scheduler import FairScheduler, Request

    engine = DecodeEngine(setup["model"], setup["params"], EngineConfig(
        num_slots=arm["num_slots"], page_size=arm["page_size"],
        num_pages=arm["num_pages"],
        max_pages_per_seq=arm["max_pages_per_seq"],
        spec_k=arm["spec_k"], prefill_chunk=arm["prefill_chunk"]))
    t0 = time.perf_counter()
    warm = Request([1] * prompt_len, 2, speculative=arm["spec_k"] >= 2)
    engine.admit(warm)
    while engine.active_slots:
        engine.step()
    warm_ms = (time.perf_counter() - t0) * 1000.0

    sched = FairScheduler()
    requests = [Request(list(range(1 + i, 1 + i + prompt_len)),
                        gen_tokens + 2 * (i % 3),
                        tenant=("search" if i % 2 else "ads"),
                        speculative=arm["spec_k"] >= 2)
                for i in range(n_requests)]
    for req in requests:
        sched.submit(req)
    pending, engine_steps = len(requests), 0
    t0 = time.perf_counter()
    while pending:
        while engine.free_slots > 0:
            req = sched.next_request(engine.can_admit)
            if req is None:
                break
            engine.admit(req)
        pending -= len(engine.step(queue_depth=sched.depth()))
        engine_steps += 1
    elapsed = time.perf_counter() - t0
    total_tokens = sum(len(r.tokens) for r in requests)
    out = {
        "verdict": "ok",
        "compile_ms": round(warm_ms, 1),
        "step_ms": round(elapsed / max(engine_steps, 1) * 1000.0, 3),
        "mfu": None,
        "engine_steps": engine_steps,
        "tokens_per_sec": round(total_tokens / elapsed, 1),
    }
    # Latency distributions merged AND per tenant — tenant-scoped SLO
    # objectives evaluate over their own tenant's stream, exactly like
    # the live engine's windows.
    for metric in ("ttft_ms", "tpot_ms", "e2e_ms"):
        merged: list = []
        by_tenant: dict[str, list] = {}
        for r in requests:
            v = getattr(r, metric)
            if v is not None:
                merged.append(v)
                by_tenant.setdefault(r.tenant, []).append(v)
        out[metric] = merged
        out[f"{metric}_by_tenant"] = by_tenant
    return out


def score_against_slos(trial: dict, objectives) -> tuple[int, list[str]]:
    """(violated objective count, violated labels) for one ok trial.

    Latency objectives (ttft/tpot/e2e) are evaluated at their percentile
    over the trial's measured request latencies — tenant-scoped
    objectives over THAT tenant's stream, ``*`` over the merged stream,
    matching the live SLO engine's per-tenant windows.  Rate objectives
    are trivially met (the in-process drive has no transport errors or
    429s) and skipped.
    """
    from ..serving.slo import LATENCY_METRICS
    from .summarize_run import _quantile
    violated = []
    for obj in objectives:
        if obj.metric not in LATENCY_METRICS:
            continue
        if obj.tenant == "*":
            values = trial.get(obj.metric) or []
        else:
            values = (trial.get(f"{obj.metric}_by_tenant")
                      or {}).get(obj.tenant) or []
        if not values:
            continue
        measured = _quantile(values, obj.target)
        if measured > obj.threshold_ms:
            violated.append(f"{obj.tenant}:{obj.label}"
                            f" (p={measured:.1f}ms)")
    return len(violated), violated


def serving_search(*, slo_spec: str = "", slots=(4, 8), page_sizes=(16,),
                   spec_ks=(0, 6), prefill_chunks=(0,),
                   n_requests: int = 12, prompt_len: int = 8,
                   gen_tokens: int = 16, trial_timeout_s: float = 300.0,
                   telemetry=None,
                   measure_fn: Callable[..., dict] | None = None) -> dict:
    """Serving-knob mode: trial every feasible arm, score against the
    SLO objectives, pick fewest-violations (throughput tiebreak)."""
    import jax
    import jax.numpy as jnp

    from ..models import gpt as gpt_lib
    from ..serving.slo import parse_slos

    objectives = parse_slos(slo_spec)
    cfg = dataclasses.replace(gpt_lib.mini(), dtype="float32")
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    setup = {"model": model, "params": params}
    arms = serving_space(slots, page_sizes, spec_ks, prefill_chunks)
    measure = measure_fn or run_serving_trial
    trials = []
    for i, arm in enumerate(arms):
        r = measure(arm, setup, n_requests=n_requests,
                    prompt_len=prompt_len, gen_tokens=gen_tokens,
                    timeout_s=trial_timeout_s)
        if r["verdict"] == "ok":
            n_viol, labels = score_against_slos(r, objectives)
            r["slo_violations"], r["violated"] = n_viol, labels
        trials.append(r)
        if telemetry is not None:
            telemetry.emit(
                "autotune_trial", step=i, trial=i, phase="serving",
                workload="serve_gpt_mini", config=r["config"],
                layout=r["describe"], compile_ms=r["compile_ms"],
                step_ms=r["step_ms"], mfu=r["mfu"], verdict=r["verdict"],
                error=r["error"],
                tokens_per_sec=r.get("tokens_per_sec"),
                slo_violations=r.get("slo_violations"))
        print(f"[autotune] serving trial {i + 1}/{len(arms)} "
              f"{r['describe']}: {r['verdict']}"
              + (f" {r['tokens_per_sec']} tok/s, "
                 f"{r.get('slo_violations', 0)} SLO violation(s)"
                 if r["verdict"] == "ok" else f" ({r['error']})"),
              flush=True)
    ok = [r for r in trials if r["verdict"] == "ok"]
    winner = min(ok, key=lambda r: (r.get("slo_violations", 0),
                                    -r.get("tokens_per_sec", 0.0))) \
        if ok else None
    return {"mode": "serving", "workload": "serve_gpt_mini",
            "searched": len(arms), "measured": len(arms), "pruned": 0,
            "objectives": [f"{o.tenant}:{o.label}" for o in objectives],
            "trials": trials, "winner": winner}


# ------------------------------------------------------------------ CLI


def _int_list(spec: str) -> tuple[int, ...]:
    return tuple(int(x) for x in spec.split(",") if x.strip())


def emit_profile(path: str, summary: dict, workload: Workload | None
                 ) -> dict | None:
    """Write the winner as a run profile; None when nothing won."""
    winner = summary.get("winner")
    if winner is None:
        return None
    tuning = {"searched": summary["searched"],
              "measured": summary["measured"],
              "pruned": summary["pruned"],
              "step_ms": winner["step_ms"],
              "compile_ms": winner["compile_ms"],
              "mfu": winner["mfu"]}
    if summary.get("best_vs_default") is not None:
        tuning["best_vs_default"] = summary["best_vs_default"]
    if summary["mode"] == "serving":
        tuning["slo_violations"] = winner.get("slo_violations", 0)
        tuning["tokens_per_sec"] = winner.get("tokens_per_sec")
        return save_run_profile(
            path, None, serving=winner["config"],
            workload={"model": "gpt_mini"}, tuning=tuning)
    pcfg = ParallelConfig.from_dict(winner["config"])
    # train.py's grad accumulation feeds batch_size PER microstep, while
    # the trial split the workload's batch ACROSS microsteps (fixed
    # global work, the fair comparison) — so a grad-accum winner records
    # the per-microstep batch, and the replayed run is exactly the
    # measured workload.  Pipeline microbatching splits internally from
    # the full batch, so it keeps the global figure.
    batch = workload.batch_size
    if pcfg.pipe == 1 and pcfg.microbatch > 1:
        batch = workload.batch_size // pcfg.microbatch
    wl = {"model": workload.name, **workload.dims,
          **workload.profile_workload, "batch_size": batch}
    if workload.seq_len:
        wl["seq_len"] = workload.seq_len
    return save_run_profile(path, pcfg, workload=wl, tuning=tuning)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--mode", default="train",
                        choices=("train", "serving"))
    parser.add_argument("--workload", default="mlp",
                        choices=tuple(WORKLOADS))
    parser.add_argument("--batch_size", type=int, default=0,
                        help="0 = the workload's default")
    parser.add_argument("--seq_len", type=int, default=64)
    parser.add_argument("--hidden", type=int, default=128,
                        help="mlp workload hidden units")
    parser.add_argument("--steps", type=int, default=8,
                        help="timed steady-state steps per trial")
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--trial_timeout_s", type=float, default=120.0)
    parser.add_argument("--measure_fraction", type=float, default=0.4)
    parser.add_argument("--microbatches", type=_int_list, default=(1, 2))
    parser.add_argument("--quant", default=None,
                        help="comma list of off,int8 (default: what the "
                             "workload supports)")
    parser.add_argument("--device_counts", type=_int_list, default=None,
                        help="explicit submesh sizes (default: powers of "
                             "two up to the device count)")
    parser.add_argument("--cost_profile", default=None,
                        choices=(None, "tpu", "host"),
                        help="cost model flavor (default: by backend)")
    # serving-mode knobs
    parser.add_argument("--slo", default="",
                        help="serving mode: SLO objectives to score arms "
                             "against (serving/slo.py grammar)")
    parser.add_argument("--slots", type=_int_list, default=(4, 8))
    parser.add_argument("--page_sizes", type=_int_list, default=(16,))
    parser.add_argument("--spec_ks", type=_int_list, default=(0, 6))
    parser.add_argument("--prefill_chunks", type=_int_list, default=(0,))
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--prompt_len", type=int, default=8)
    parser.add_argument("--gen_tokens", type=int, default=16)
    # artifacts
    parser.add_argument("--out", default="autotune_profile.json",
                        help="winning run profile path")
    parser.add_argument("--metrics_file", default=None,
                        help="append kind=autotune_trial telemetry here "
                             "(summarize_run-compatible JSONL)")
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform (cpu/tpu)")
    args = parser.parse_args(argv)

    from ..utils.backend import configure_backend
    configure_backend(args.platform)

    from ..utils.metrics import MetricsLogger
    from ..utils.telemetry import Telemetry
    logger = MetricsLogger(args.metrics_file)
    telemetry = Telemetry(logger) if args.metrics_file else None

    workload = None
    try:
        if args.mode == "serving":
            summary = serving_search(
                slo_spec=args.slo, slots=args.slots,
                page_sizes=args.page_sizes, spec_ks=args.spec_ks,
                prefill_chunks=args.prefill_chunks,
                n_requests=args.requests, prompt_len=args.prompt_len,
                gen_tokens=args.gen_tokens,
                trial_timeout_s=args.trial_timeout_s, telemetry=telemetry)
        else:
            kwargs: dict[str, Any] = {}
            if args.batch_size:
                kwargs["batch_size"] = args.batch_size
            if args.workload == "mlp":
                kwargs["hidden"] = args.hidden
            else:
                kwargs["seq_len"] = args.seq_len
            workload = WORKLOADS[args.workload](**kwargs)
            summary = search(
                workload, steps=args.steps, warmup=args.warmup,
                trial_timeout_s=args.trial_timeout_s,
                measure_fraction=args.measure_fraction,
                microbatches=args.microbatches,
                quant_arms=(tuple(q.strip() for q in args.quant.split(",")
                                  if q.strip())
                            if args.quant else None),
                device_counts=args.device_counts,
                cost_profile=args.cost_profile, telemetry=telemetry)
    finally:
        logger.close()

    profile = emit_profile(args.out, summary, workload)
    winner = summary.get("winner")
    headline = {
        "mode": summary["mode"], "workload": summary["workload"],
        "searched": summary["searched"], "pruned": summary["pruned"],
        "measured": summary["measured"],
        "winner": winner["describe"] if winner else None,
        "winner_step_ms": winner["step_ms"] if winner else None,
        "default_step_ms": (summary.get("default_trial") or {}).get(
            "step_ms"),
        "best_vs_default": summary.get("best_vs_default"),
        "slo_violations": (winner or {}).get("slo_violations"),
        "profile": args.out if profile is not None else None,
        "ok": winner is not None,
    }
    print(json.dumps(headline), flush=True)
    return 0 if winner is not None else 1


if __name__ == "__main__":
    sys.exit(main())
