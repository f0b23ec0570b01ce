#!/usr/bin/env python3
"""The benchmark's command:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell, its configuration file and its traffic file are
found by name from ``BENCHMARK.json``; every metric is a reader of its own
under ``perfbench/metrics/<name>.py``.  Nothing here names a cell.

This process never imports JAX: the chip belongs to one process, the worker
it starts (``perfbench/worker.py``).  For a serving cell this process is also
the load generator.  The LAST line on standard output is the result object
and nothing else; what else is worth reading goes to standard error or under
``perfbench/out/``, which the harness creates.

``--rehearse`` runs the same control flow at the tiny sizes of
each data file's ``rehearsal`` entry on the CPU; it prints no metric and exits 4.
"""

from __future__ import annotations

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import check, loadgen, spec, stats  # noqa: E402
from perfbench.metrics import _common  # noqa: E402

CLOCK = time.monotonic


def note(msg: str) -> None:
    print(f"[run {CLOCK() - PROCESS_START:7.2f}] {msg}", file=sys.stderr,
          flush=True)


def worker_env(args, chips: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # The program keeps its compile cache where JAX_COMPILATION_CACHE_DIR
    # says: a fixed path inside this checkout, so two checkouts share
    # nothing and only a checkout's first run of a cell compiles.  Every
    # program goes in, the sub-second ones too.
    env["JAX_COMPILATION_CACHE_DIR"] = spec.CACHE_DIR
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    env.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse:
        # A rehearsal keeps its CPU programs out of the cache of chip runs
        # (and of the program's own tests, which share ``.jax_cache``).
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            spec.OUT_DIR, "rehearsal_cache")
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={chips}"
                            ).strip()
    return env


class Worker:
    def __init__(self, args, chips: int, extra: list[str]):
        cmd = [sys.executable, "-m", "perfbench.worker",
               "--workload", args.workload, "--seed", str(args.seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace),
               *extra]
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=worker_env(args, chips), text=True,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)

    def event(self, want: str) -> dict:
        """The next event of that name; anything else the worker prints on
        its stdout is passed on to standard error."""
        for line in self.proc.stdout:
            try:
                msg = json.loads(line)
            except ValueError:
                msg = None
            if isinstance(msg, dict) and msg.get("event") == want:
                return msg
            sys.stderr.write(line)
        rc = self.proc.wait()
        raise SystemExit(f"the worker ended (exit {rc}) before {want!r}")

    def tell(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def close(self) -> int:
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            return self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait()


def sample_requests(records: list[dict], k: int, seed: int) -> list[dict]:
    """``k`` of the requests the window finished, drawn from the seed, the
    longest always among them."""
    done = [r for r in records if r["phase"] == "window" and r["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["served"]))
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    return [longest] + rest[:max(0, k - 1)]


def serve(args, cell: dict, worker: Worker) -> dict:
    tr = cell["traffic"]
    ready = worker.event("ready")
    lead = float(tr.get("lead_s", 0.0))
    t0 = CLOCK() + lead + 0.25
    extra = float(tr["trace_seconds"]) + 1.0 if args.trace else 0.0
    worker.tell(t0=t0, t1=t0 + args.seconds, trace_for=tr["trace_seconds"])
    note(f"load: {tr['loop']} for {args.seconds}s after {lead}s of lead-in")
    records = loadgen.drive(ready["port"], tr, args.seed,
                            ready["vocab_size"], t0, args.seconds, extra)
    samples = sample_requests(records, tr["check_sample"], args.seed)
    worker.tell(samples=[{"prompt": r["prompt"], "served": r["served"]}
                         for r in samples])
    out = worker.event("result")
    for r in records:
        r.pop("prompt", None)
        r.pop("served", None)
    out["client"] = records
    return out


def sweep(args, cell: dict, worker: Worker) -> int:
    """Builder only, once: offer the cell's open-loop traffic at each of a
    few rates against one server and print what the SERVER reported, to find
    the highest rate it sustains.  No metric comes from here."""
    tr = dict(cell["traffic"], lead_s=0.0)
    ready = worker.event("ready")
    now = CLOCK()
    worker.tell(t0=now, t1=now + 1.0, trace_for=0.0)
    for rate in [float(x) for x in args.sweep.split(",")]:
        t0 = CLOCK() + 0.25
        recs = loadgen.open_loop(ready["port"], dict(tr, rate_per_s=rate),
                                 args.seed, ready["vocab_size"], t0,
                                 args.seconds, 0.0)
        ok = [r for r in recs if r["ok"]]
        drain = max(r["done"] for r in recs) - (t0 + args.seconds)
        half = len(ok) // 2
        row = {"rate": rate, "sent": len(recs), "ok": len(ok),
               "drain_s": drain}
        for k in ("queue_ms", "ttft_ms", "tpot_ms"):
            vals = [r["server"][k] for r in ok if r["server"][k] is not None]
            row[k + "_p50"] = stats.quantile(vals, 0.5)
            row[k + "_p90"] = stats.quantile(vals, 0.9)
            row[k + "_2nd_half_p50"] = stats.quantile(vals[half:], 0.5)
        print("sweep " + json.dumps(row), flush=True)
    worker.tell(samples=[])
    worker.event("result")
    return 0


def read_metric(name: str, ctx: dict):
    """``perfbench/metrics/<name>.py`` holds ``read(ctx)``; a reader that
    finds nothing to read returns None and the metric is left out."""
    return spec.load_module(
        os.path.join(spec.HERE, "metrics", name + ".py")).read(ctx)


def result_line(args, cell: dict, out: dict, units: dict) -> dict:
    ctx = dict(out, cell=cell["name"], chips=cell["chips"],
               config=cell["config"], traffic=cell["traffic"],
               process_start=PROCESS_START)
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name in cell["metrics"][group]:
        value = read_metric(name, ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    numbers = dict(out["check"]["numbers"])
    attempted, failed = _common.attempted_failed(ctx)
    numbers["failed"] = failed
    limits = dict(cell["limits"] or {}, failed=0)
    correct, compared = check.verdict(numbers, limits)
    correct = correct and attempted > 0
    device = dict(out["device"])
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if args.trace and not (out.get("trace") or {}).get("busy_s") \
            and not args.rehearse:
        raise SystemExit("the traced run found no operation on a device: "
                         f"{(out.get('trace') or {}).get('found')}")
    if args.trace and (out.get("trace") or {}).get("busy_s"):
        device["busy_s"] = out["trace"]["busy_s"]
        device["window_s"] = out["trace"]["window_s"]
        line["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                             "idle_gaps": out["trace"]["idle_gaps"]}
    line["check"] = {k: [c["value"], c["limit"]] for k, c in compared.items()}
    os.makedirs(spec.OUT_DIR, exist_ok=True)
    detail = os.path.join(spec.OUT_DIR,
                          f"{cell['name']}.trace{args.trace}.last.json")
    with open(detail, "w") as fh:
        json.dump({"line": line, "setup": out["setup"],
                   "counters": out["counters"], "check": out["check"],
                   "window": out["window"],
                   "trace_found": (out.get("trace") or {}).get("found")},
                  fh, indent=1)
    note(f"setup phases {json.dumps(out['setup'])}")
    note(f"counters {json.dumps(out['counters'])}")
    note(f"check {json.dumps(out['check'])}")
    check.print_compared(compared, correct)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--calibrate", default="")
    ap.add_argument("--break-path", default="")
    ap.add_argument("--sweep", default="",
                    help="builder only: rates to offer, one after another")
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(args.workload, args.rehearse)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    extra = (["--rehearse"] * args.rehearse + ["--control"] * args.control
             + (["--calibrate", args.calibrate] if args.calibrate else [])
             + (["--break-path", args.break_path] if args.break_path
                else []))
    worker = Worker(args, cell["chips"], extra)
    try:
        if args.sweep:
            return sweep(args, cell, worker)
        if cell["traffic"]["kind"] == "train_lm":
            out = worker.event("result")
        else:
            out = serve(args, cell, worker)
    finally:
        rc = worker.close()
    if rc != 0:
        raise SystemExit(f"the worker exited with code {rc}")
    if out.get("kind") == "calibrate":
        print(json.dumps(out))
        return 0
    line = result_line(args, cell, out, units)
    if args.rehearse:
        line["rehearsal"], line["metrics"] = True, {
            k: "read" for k in line["metrics"]}
        line["device"] = {k: v for k, v in line["device"].items()
                          if k in ("platform", "kind", "count")}
        print(json.dumps(line))
        return spec.REHEARSAL_EXIT
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
