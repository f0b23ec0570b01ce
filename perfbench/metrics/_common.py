"""What the readers under ``perfbench/metrics/`` share: the window's steps
and requests out of a run's context, and the few reductions that several
metrics are prefixes of.  No JAX.

``ctx`` is what the worker reported (``steps``, ``requests``, ``counters``,
``setup``, ``trace``, ``device``, ``window``) plus the parent's ``client``
records, the cell's ``config`` and ``traffic``, ``chips`` and
``process_start``.  All times are ``time.monotonic()`` seconds, one clock
for both processes.
"""

from __future__ import annotations

from perfbench import costs, peaks, spec, stats

GIB = 2.0 ** 30


def window(ctx):
    return ctx["window"]["t0"], ctx["window"]["t1"]


def window_steps(ctx) -> list[dict]:
    return stats.steps_in(ctx["steps"], *window(ctx))


def window_requests(ctx) -> list[dict]:
    """The parent's record of every request due in the window, joined with
    the engine-side log of the same request (by its index)."""
    out = []
    for r in ctx.get("client", []):
        if r["phase"] == "window":
            out.append({**r, "engine": ctx["requests"].get(str(r["index"]))})
    return out


def attempted_failed(ctx) -> tuple[int, int]:
    if ctx["kind"] == "train":
        return ctx["attempted"], ctx["failed"]
    reqs = window_requests(ctx)
    return len(reqs), sum(1 for r in reqs if not r["ok"])


def setup_s(ctx) -> float:
    return ctx["window"]["t0"] - ctx["process_start"]


def tokens_per_s(ctx) -> float | None:
    return stats.whole_step_rate(ctx["steps"], *window(ctx))


def ttft_ms(ctx) -> list[float]:
    """Due-time to first token for every request due in the window; a failed
    or refused request counts as the worst (the request timeout)."""
    worst = ctx["traffic"]["request_timeout_s"] * 1e3
    out = []
    for r in window_requests(ctx):
        e = r["engine"]
        if r["ok"] and e and e["t_first"] is not None:
            out.append((e["t_first"] - r["due"]) * 1e3)
        else:
            out.append(worst)
    return out


def tpot_mean_ms(ctx) -> float | None:
    """All the time between first and last token of every request due in
    the window over all their token gaps."""
    spans, gaps = [], []
    for r in window_requests(ctx):
        e = r["engine"]
        if r["ok"] and e and e["n_out"] > 1:
            spans.append((e["t_last"] - e["t_first"]) * 1e3)
            gaps.append(e["n_out"] - 1)
    return stats.weighted_mean(spans, gaps)


def gen_late_ms(ctx) -> list[float]:
    return [(r["sent"] - r["due"]) * 1e3 for r in window_requests(ctx)]


def queue_wait_ms(ctx) -> list[float]:
    """Sent by the client to the start of its admission by the engine."""
    return [(r["engine"]["t_admit"] - r["sent"]) * 1e3
            for r in window_requests(ctx) if r["engine"]]


def kv_pages_peak_pct(ctx) -> float | None:
    c = ctx["counters"]
    return 100.0 * c["kv_pages_peak"] / c["kv_pages_total"]


def prefill_share_pct(ctx) -> float | None:
    """Whole-prompt prefill's share of the engine's time over the window's
    turns of the engine loop."""
    steps = window_steps(ctx)
    total = sum(s["t_end"] - s["t_start"] for s in steps)
    return 100.0 * sum(s["prefill_s"] for s in steps) / total if total else None


def decode_step_ms(ctx) -> list[float]:
    return [(s["t_end"] - s["t_decode"]) * 1e3 for s in window_steps(ctx)
            if s["context"]]


def data_wait_pct(ctx) -> float | None:
    t0, t1 = window(ctx)
    rows = [r for r in ctx["train_records"] if t0 <= r["t"] <= t1
            and r["data_wait_ms"] is not None]
    wait = sum(r["data_wait_ms"] for r in rows)
    total = wait + sum(r["compute_ms"] for r in rows)
    return 100.0 * wait / total if total else None


def hbm_peak_gib(ctx) -> float | None:
    peak = ctx["device"].get("memory_peak_bytes") or 0
    return peak / GIB if peak else None


def step_footprint_bytes(ctx) -> int | None:
    """What the compiled training step holds on a chip while it runs, by the
    compiler's own account (``compiled.memory_analysis()``): its arguments,
    its temporaries, its code, and the outputs that do not reuse a donated
    argument."""
    m = ctx["counters"].get("memory_analysis")
    if not m:
        return None
    return (m["argument"] + m["temp"] + m["generated_code"]
            + max(0, m["output"] - m["alias"]))


def train_hbm_peak_gib(ctx) -> float | None:
    """The larger of the step's footprint and the allocator's peak.  On this
    chip ``memory_stats()`` does not count a running program's temporaries
    (it read 6.07 GiB for a step that another 0.28 GB would not have let
    allocate: PERF.md), so alone it cannot move with batch, remat or the
    attention's lowering; the footprint does."""
    got = [b for b in (step_footprint_bytes(ctx),
                       ctx["device"].get("memory_peak_bytes")) if b]
    return max(got) / GIB if got else None


def device_idle_pct(ctx) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def collective_pct(ctx) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s") or not tr.get("collective_s"):
        return None
    return 100.0 * tr["collective_s"] / tr["busy_s"]


def costs_of(cfg: dict):
    """Where a step's operations and bytes are counted: the file the
    configuration names under ``costs`` (``train_step``, ``prefill`` and
    ``decode_step`` as ``perfbench/costs.py`` has them), or that file.  The
    least time and the peaks are shared: a configuration brings its counts,
    never its own peak."""
    return spec.named_module(cfg, "costs") if "costs" in cfg else costs


def traced_least_seconds(ctx) -> tuple[float, dict] | None:
    """The least time the chip could take for the whole pieces of work that
    ran inside the traced slice, and how much of it each bound sets."""
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    pk = peaks.peaks_for(ctx["device"]["kind"])
    cfg, t0, t1 = ctx["config"], tr["t0"], tr["t1"]
    counts = costs_of(cfg)
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}

    def add(cost, chips=1):
        nonlocal total
        least = costs.least_time(cost, pk, chips)
        total += least["seconds"]
        by[least["bound"]] += least["seconds"]

    for s in ctx["steps"]:
        if ctx["kind"] == "train":
            if s["t_start"] >= t0 and s["t_end"] <= t1:
                c = ctx["counters"]
                add(counts.train_step(cfg, c["rows"], c["seq"],
                                      c["n_params"]), ctx["chips"])
            continue
        for a, b, plen in s["admits"]:
            if a >= t0 and b <= t1:
                add(counts.prefill(cfg, plen))
        if s["context"] and s["t_decode"] >= t0 and s["t_end"] <= t1:
            add(counts.decode_step(cfg, s["context"]))
    return (total, by) if total else None


def step_roofline_pct(ctx) -> float | None:
    """Least time for the traced work over the device's busy time in the
    trace.  Nothing to read returns None, never 0."""
    least = traced_least_seconds(ctx)
    if least is None:
        return None
    return 100.0 * least[0] / ctx["trace"]["busy_s"]
