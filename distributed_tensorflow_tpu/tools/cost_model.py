"""Analytic cost model of a training step: FLOPs, HBM bytes, device peaks.

Two users in the program:

- live MFU.  ``train.py`` prices every optimizer step with
  :func:`train_step_flops` and the attached chips' peak
  (:func:`device_peak_flops`, or ``--peak_tflops``) and hands both to
  ``utils/telemetry.py``, which writes ``mfu`` into each ``train_step``
  record; ``tools/summarize_run.py`` reads it.
- the parallelism autotuner (``tools/autotune.py``, docs/autotune.md)
  prunes its search with :func:`estimate_config_cost` and reports its
  winner's MFU by the same FLOP count.

``--config`` scores a run profile's layout without touching a device::

    python -m distributed_tensorflow_tpu.tools.cost_model \
        --config profile.json [--cost-profile host]

The benchmark's roofline shares do not come from here: ``perfbench/`` counts
its own operations and bytes (``perfbench/costs.py``) against its own peak
table (``perfbench/peaks.py``); ``tests/test_cost_model.py`` holds the v5e
peak of the two tables equal.
"""

from __future__ import annotations

import argparse
import json
import sys

# ----------------------------------------------------------- FLOP model
#
# The shared MFU arithmetic: the live telemetry stream (utils/telemetry.py),
# summarize_run and the autotuner all price work with the same convention,
# so their MFU figures are comparable.

#: bf16 peak TFLOP/s per chip by device kind (dense); public TPU spec
#: sheets.  Unknown kinds (CPU hosts, new chips) report no peak — MFU is
#: then null in telemetry rather than a made-up number.
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def peak_flops_per_chip() -> float | None:
    """Peak FLOP/s of ONE attached chip (None for unknown kinds) — the
    single device-kind matching rule; the aggregate figure and the
    tuner's per-submesh MFU both derive from it."""
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in PEAK_TFLOPS.items():
        if key in kind:
            return peak * 1e12
    return None


def device_peak_flops() -> float | None:
    """Aggregate peak FLOP/s across every device of the run (all hosts),
    or None when the device kind has no table entry."""
    import jax
    per_chip = peak_flops_per_chip()
    return None if per_chip is None else per_chip * jax.device_count()


def train_step_flops(n_params: int, tokens: int, *, num_layers: int = 0,
                     hidden_size: int = 0, seq_len: int = 0,
                     window: int = 0) -> float:
    """Analytic model FLOPs for ONE optimizer step over ``tokens`` examples
    (rows for classifiers, B*S for language models).

    The standard MFU convention: forward matmul work is ``2 * params *
    tokens``; backward costs twice the forward, so a train step is ``3x``
    forward.  Pass the transformer dims to additionally credit attention
    score/value work (``4 * L * tokens * kv_len * H`` per forward), which
    the parameter count misses; a sliding ``window`` caps ``kv_len`` at
    ``window + 1``.
    """
    fwd = 2.0 * n_params * tokens
    if num_layers and hidden_size and seq_len:
        kv_len = min(seq_len, window + 1) if window else seq_len
        fwd += 4.0 * num_layers * tokens * kv_len * hidden_size
    return 3.0 * fwd


def train_step_bytes(n_params: int, tokens: int, *, num_layers: int = 0,
                     hidden_size: int = 0, param_bytes: int = 4,
                     act_bytes: int = 2) -> float:
    """Analytic HBM traffic for one optimizer step (the bytes side of the
    cost model, paired with :func:`train_step_flops`).

    Parameters are read in the forward and the backward and written once
    by the update, and Adam-class optimizer slots add two read+write
    pairs — ~6 param-sized transfers.  Transformer dims additionally
    credit activation traffic (residual stream written/read ~6x per layer
    across forward + backward, a deliberate round number: this model
    ranks layouts, it does not predict wall-clock).
    """
    total = 6.0 * n_params * param_bytes
    if num_layers and hidden_size:
        total += 6.0 * num_layers * tokens * hidden_size * act_bytes * 2
    return total


# ------------------------------------------- parallel-layout cost model
#
# The autotuner's pruning stage (tools/autotune.py, docs/autotune.md):
# score a declarative ParallelConfig analytically so only the promising
# fraction of the search space pays for a measured trial.  Two profiles:
#
# - ``tpu``: roofline-style — per-chip compute vs HBM bytes, plus
#   per-axis collective terms priced at ICI/DCN bandwidth class numbers
#   and the pipeline fill/drain bubble.
# - ``host``: the CPU virtual-mesh proxy CI runs on.  XLA:CPU already
#   threads ONE device's ops across every core, so extra virtual devices
#   buy no compute — they only add collective rendezvous (N threads
#   synchronizing per psum) and per-device dispatch.  This is what makes the
#   model rank dp1 above dp8 on the 2-core CI host, matching the
#   measured order.
#
# All constants are CLASS numbers for ranking, not wall-clock predictors;
# the tuner always measures the survivors.

NOMINAL_PEAK_FLOPS = 100e12       # per chip, when the kind is unknown
HBM_BYTES_PER_SEC = 800e9
ICI_BYTES_PER_SEC = 45e9
DCN_BYTES_PER_SEC = 3e9
HOST_FLOPS = 8e9                  # whole-host matmul class (all cores)
HOST_BYTES_PER_SEC = 10e9
HOST_RENDEZVOUS_S = 8e-4          # per extra participant per collective
DISPATCH_S = 3e-4                 # host dispatch per device call
#: Relative compute scale of the int8 matmul training arm: a class number
#: from the old rig's int8 ladder (BASELINE.md; not measured on this tree,
#: ROADMAP S10); slightly SLOWER on hosts (no int8 matmul unit, quantize
#: overhead).
QUANT_COMPUTE_SCALE = {"tpu": {"off": 1.0, "int8": 0.87},
                       "host": {"off": 1.0, "int8": 1.05}}


def estimate_config_cost(parallel: dict, *, n_params: int,
                         tokens_per_step: int, num_layers: int = 0,
                         hidden_size: int = 0, seq_len: int = 0,
                         window: int = 0,
                         peak_flops_per_sec: float | None = None,
                         cost_profile: str = "tpu",
                         host_cores: int | None = None) -> dict:
    """Analytic step-time estimate for one RESOLVED parallel layout.

    ``parallel`` is a :class:`..parallel.mesh.ParallelConfig`-shaped dict
    (``data`` concrete).  Returns the decomposed estimate::

        {est_step_ms, compute_ms, memory_ms, comm_ms, dispatch_ms,
         bubble, degree, flops_per_step, cost_profile}

    The figure exists to RANK layouts (the tuner measures the survivors);
    absolute accuracy is explicitly not a goal.
    """
    if cost_profile not in ("tpu", "host"):
        raise ValueError(f"cost_profile must be tpu or host, "
                         f"got {cost_profile!r}")
    dp = int(parallel.get("data", 1))
    tp = int(parallel.get("model", 1))
    sp = int(parallel.get("seq", 1))
    pp = int(parallel.get("pipe", 1))
    ep = int(parallel.get("expert", 1))
    dcn = int(parallel.get("dcn_data", 1))
    micro = max(int(parallel.get("microbatch", 1)), 1)
    quant = parallel.get("quantize", "off")
    if dp < 1:
        raise ValueError(f"estimate_config_cost needs a resolved layout "
                         f"(data={dp})")
    degree = dp * tp * sp * pp * ep
    flops = train_step_flops(n_params, tokens_per_step,
                             num_layers=num_layers, hidden_size=hidden_size,
                             seq_len=seq_len, window=window)
    qscale = QUANT_COMPUTE_SCALE[cost_profile].get(quant, 1.0)
    grad_bytes = 4.0 * n_params / (tp * pp * ep)   # per-device grad shard
    bubble = (pp - 1) / micro if pp > 1 else 0.0

    if cost_profile == "host":
        # One virtual device already uses every core; parallel degree
        # only adds synchronization.  Collectives fire once per
        # microbatch backward.
        compute_s = flops / HOST_FLOPS * qscale
        memory_s = 0.0
        comm_s = 0.0
        if degree > 1:
            comm_s += HOST_RENDEZVOUS_S * (degree - 1) * micro
            comm_s += grad_bytes * (dp - 1) / max(dp, 1) / HOST_BYTES_PER_SEC
        dispatch_s = DISPATCH_S * micro * degree
        est_s = compute_s * (1.0 + bubble) + comm_s + dispatch_s
    else:
        peak = peak_flops_per_sec or NOMINAL_PEAK_FLOPS
        compute_s = flops / degree / peak * qscale
        memory_s = train_step_bytes(
            n_params, tokens_per_step, num_layers=num_layers,
            hidden_size=hidden_size) / degree / HBM_BYTES_PER_SEC
        comm_s = 0.0
        if dp > 1:
            # Gradient AllReduce rides the slowest link of the data axis.
            link = DCN_BYTES_PER_SEC if dcn > 1 else ICI_BYTES_PER_SEC
            comm_s += 2.0 * (dp - 1) / dp * grad_bytes / link
        if num_layers and hidden_size:
            act = tokens_per_step / max(dp * sp, 1) * hidden_size * 2.0
            if tp > 1:
                # Two AllReduces per layer forward, two backward.
                comm_s += 4.0 * num_layers * act * (tp - 1) / tp \
                    / ICI_BYTES_PER_SEC
            if sp > 1:
                # Ring attention: (sp-1) K/V block hops per layer,
                # forward + backward.
                comm_s += 2.0 * num_layers * act * (sp - 1) \
                    / ICI_BYTES_PER_SEC
            if pp > 1:
                # Stage-boundary activations, all microbatches, fwd+bwd.
                comm_s += 2.0 * (pp - 1) * (tokens_per_step / max(dp, 1)) \
                    * hidden_size * 2.0 / ICI_BYTES_PER_SEC
        dispatch_s = DISPATCH_S * micro
        est_s = max(compute_s * (1.0 + bubble), memory_s) \
            + comm_s + dispatch_s

    return {
        "est_step_ms": round(est_s * 1000.0, 4),
        "compute_ms": round(compute_s * 1000.0, 4),
        "memory_ms": round(memory_s * 1000.0, 4),
        "comm_ms": round(comm_s * 1000.0, 4),
        "dispatch_ms": round(dispatch_s * 1000.0, 4),
        "bubble": round(bubble, 4),
        "degree": degree,
        "flops_per_step": flops,
        "cost_profile": cost_profile,
    }


def score_profile(profile: dict, *, cost_profile: str = "tpu",
                  peak_flops_per_sec: float | None = None) -> dict:
    """Score a run profile's ``parallel`` section analytically — the
    ``--config`` CLI mode's library form (no devices touched).

    Workload dims come from the profile's ``workload`` section
    (``n_params``/``tokens_per_step`` required; transformer dims
    optional), which the autotuner writes into every profile it emits.
    """
    parallel = profile.get("parallel")
    if not parallel:
        raise ValueError("profile has no 'parallel' section to score")
    wl = profile.get("workload", {})
    missing = [k for k in ("n_params", "tokens_per_step") if not wl.get(k)]
    if missing:
        raise ValueError(f"profile workload section missing {missing} "
                         "(needed by the analytic cost model)")
    return estimate_config_cost(
        parallel, n_params=int(wl["n_params"]),
        tokens_per_step=int(wl["tokens_per_step"]),
        num_layers=int(wl.get("num_layers", 0)),
        hidden_size=int(wl.get("hidden_size", 0)),
        seq_len=int(wl.get("seq_len", 0)),
        window=int(wl.get("window", 0)),
        peak_flops_per_sec=peak_flops_per_sec, cost_profile=cost_profile)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--config", required=True,
                        help="run profile whose parallel layout is scored "
                             "analytically (no devices touched): prints "
                             "the cost-model decomposition as JSON "
                             "(docs/autotune.md)")
    parser.add_argument("--cost-profile", default="tpu",
                        choices=("tpu", "host"),
                        help="cost model flavor: tpu roofline or the CPU "
                             "virtual-mesh host proxy")
    args = parser.parse_args(argv)

    from ..parallel.mesh import load_run_profile
    try:
        profile = load_run_profile(args.config)
        cost = score_profile(profile, cost_profile=args.cost_profile)
    except (OSError, ValueError) as e:
        print(f"[cost_model] --config failed: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"profile": args.config,
                      "parallel": profile["parallel"], **cost},
                     indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
