#!/usr/bin/env python3
"""Print the reduction of a trace file by program and region
(``perfbench/regions.py``): for every compiled program of the slice its
executions and device time, then region by region the self seconds, the
milliseconds an execution and the share of the program's time, the region
names found on it, and its five largest unnamed operations by HLO head.

    python3 -m perfbench.tools.regions <file.xplane.pb | cell> [--json]

A cell's name stands for the newest trace its traced run left under
``perfbench/out/trace/<cell>``.  Exit code 1 where no program of the file
carries a region (docs/observability.md, "Device regions": a program
without regions, or executables the compile cache loaded with older names).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import regions, spec, xplane  # noqa: E402


def show(red: dict) -> None:
    total = red["total_s"]
    src = red["source"]
    print(f"busy {total:.6f} s on {red['devices']} device(s); operations "
          f"named by tf_op {src['tf_op']}, by the module's HLO "
          f"{src['hlo_proto']}, by their fused instructions {src['fused']}; "
          f"overlap on the line itself {red['overlap_s']:.6f} s")
    for name, prog in sorted(red["programs"].items(),
                             key=lambda kv: -kv[1]["seconds"]):
        runs = prog["executions"]
        print(f"\n{name}: {runs:g} executions, {prog['seconds']:.6f} s "
              f"({100 * prog['seconds'] / total:.1f}% of busy), "
              f"{1e3 * prog['seconds'] / max(runs, 1):.4f} ms an execution")
        print(f"  regions found: {', '.join(prog['names']) or 'none'}")
        print(f"  {'region':24s} {'self s':>10s} {'ms/exec':>10s} "
              f"{'% of it':>10s}")
        for region, sec in sorted(prog["regions"].items(),
                                  key=lambda kv: -kv[1]):
            print(f"  {region:24s} {sec:10.6f} "
                  f"{1e3 * sec / max(runs, 1):10.4f} "
                  f"{100 * sec / max(prog['seconds'], 1e-30):10.2f}")
        for head, ops, sec in prog["unnamed"]:
            print(f"    unnamed {1e3 * sec / max(runs, 1):9.4f} ms/exec  "
                  f"{ops:4d} x {head}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="a .xplane.pb file, or a cell's name")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) else xplane.newest_xplane(
        os.path.join(spec.OUT_DIR, "trace", args.trace))
    if not path:
        raise SystemExit(f"no trace file at or for {args.trace!r}")
    red = regions.reduce(path)
    if args.json:
        print(json.dumps(red))
    elif red["devices"]:
        show(red)
    if not red["found"]:
        print(f"{path}: no operation under a region of the program's "
              f"({len(regions.vocabulary())} names known): a program "
              "without regions, or executables that the compile cache "
              "loaded with older names (docs/observability.md, \"Device "
              "regions\")", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
