"""From a profiler trace (``.xplane.pb``) to numbers, on
``jax.profiler.ProfileData`` (jax 0.9.0) and nothing else.

Device planes and their operation line are found by KIND, not by a name
seen once: a device plane is one whose name starts with ``/device:`` (and is
not a host or a "custom" plane), and its operation line is the line named
like "XLA Ops" where there is one, else the line of that plane with the
most events.  What was found is returned under ``"found"`` so a run can say
what it read.

Busy time is the UNION of the operation intervals of a device (operations
nest and overlap: async collectives run beside compute), averaged over the
device planes; idle gaps are the holes in that union, named by the host
annotation (``jax.profiler.TraceAnnotation`` placed by the harness, names
starting ``perfbench.``) that covers the middle of the gap.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)", re.I)
#: A Mosaic (Pallas) kernel reaches the device trace as a custom call.
CUSTOM_CALL = re.compile(r"custom-call|tpu_custom_call|mosaic", re.I)


def newest_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


OPCODE = re.compile(r"[\s)]([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """The device trace names an operation by its whole HLO line,
    ``%fusion.11 = (f32[...]) fusion(...), kind=kLoop, ...``: keep the
    instruction's own name."""
    return name.split(" = ", 1)[0].strip().lstrip("%")[:80]


def op_class(name: str) -> str:
    """A coarse class for an operation as the device trace names it: by the
    HLO opcode where the name is a whole HLO line, else by the name."""
    lhs, _, rhs = name.partition(" = ")
    m = OPCODE.search(" " + rhs) if rhs else None
    base = m.group(1) if m else lhs.split("(")[0].strip().lstrip("%")
    if COLLECTIVE.match(base):
        return "collective"
    if CUSTOM_CALL.search(base):
        return "custom_call"
    if base.startswith(("fusion", "loop_fusion", "input_fusion")):
        return "fusion"
    if base.startswith(("convolution", "dot", "conv")):
        return "matmul"
    if base.startswith(("copy", "transpose", "bitcast", "reshape",
                        "dynamic-update-slice", "dynamic-slice", "slice",
                        "concatenate", "pad", "gather", "scatter")):
        return "data_movement"
    return "other"


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper() \
        and not name.startswith("/device:CPU")


def _ops_line(plane):
    lines = [(ln, list(ln.events)) for ln in plane.lines]
    lines = [(ln, ev) for ln, ev in lines if ev]
    if not lines:
        return None, []
    named = [x for x in lines if re.search(r"\bops\b", x[0].name, re.I)
             and not re.search(r"framework|async", x[0].name, re.I)]
    return max(named or lines, key=lambda x: len(x[1]))


def reduce(path: str, span_prefix: str = "perfbench.") -> dict:
    """Busy/idle, collectives, the operations that took most time and the
    longest idle gaps of one trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = list(data.planes)
    found = {"planes": [p.name for p in planes], "device_planes": [],
             "ops_lines": [], "lines": {}}
    spans: list[tuple[int, int, str]] = []
    for p in planes:
        found["lines"][p.name] = [ln.name for ln in p.lines][:24]
        if _is_device_plane(p.name):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith(span_prefix):
                    spans.append((int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns), ev.name))
    spans.sort()
    span_starts = [s[0] for s in spans]

    def span_at(t: int) -> str:
        """The harness span that covers ``t`` (they do not nest)."""
        i = bisect.bisect_right(span_starts, t) - 1
        if i >= 0 and spans[i][1] >= t:
            return spans[i][2]
        return "outside_harness_spans"

    busy, window, collective, hidden = [], [], [], []
    by_name: dict[str, float] = {}
    by_class: dict[str, float] = {}
    gaps: dict[str, float] = {}
    n_events = 0
    for p in planes:
        if not _is_device_plane(p.name):
            continue
        line, events = _ops_line(p)
        if line is None:
            continue
        found["device_planes"].append(p.name)
        found["ops_lines"].append(line.name)
        n_events += len(events)
        iv, coll_iv, comp_iv = [], [], []
        for ev in events:
            a, b = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            iv.append((a, b))
            cls = op_class(ev.name)
            (coll_iv if cls == "collective" else comp_iv).append((a, b))
            sec = (b - a) * 1e-9
            short = short_name(ev.name)
            by_name[short] = by_name.get(short, 0.0) + sec
            by_class[cls] = by_class.get(cls, 0.0) + sec
        u = _union(iv)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        window.append((u[-1][1] - u[0][0]) * 1e-9)
        cu = _union(coll_iv)
        c_total = sum(b - a for a, b in cu)
        collective.append(c_total * 1e-9)
        # Collective time during which compute also ran on that device.
        both = sum(b - a for a, b in _union(coll_iv + comp_iv))
        comp = sum(b - a for a, b in _union(comp_iv))
        hidden.append((c_total + comp - both) * 1e-9)
        for (_, e0), (s1, _) in zip(u, u[1:]):
            name = span_at((e0 + s1) // 2)
            gaps[name] = gaps.get(name, 0.0) + (s1 - e0) * 1e-9
    if not busy:
        return {"found": found, "device_events": 0}
    n = len(busy)
    top = lambda d: [[k, v / n] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"found": found, "device_events": n_events, "devices": n,
            "busy_s": sum(busy) / n, "span_s": sum(window) / n,
            "collective_s": sum(collective) / n,
            "collective_hidden_s": sum(hidden) / n,
            "class_s": {k: v / n for k, v in by_class.items()},
            "device_ops": top(by_name), "idle_gaps": top(gaps)}
