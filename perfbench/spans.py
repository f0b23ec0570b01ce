"""Whose turn was it while the device sat idle: the holes between device
operations of a traced slice, shared out among the PROGRAM's own spans
(``profiling.annotate`` regions named ``serve.*``, placed by
``serving/server.py`` and ``serving/engine.py`` on the engine thread).

On ``jax.profiler.ProfileData`` and nothing else, like ``xplane.py``, whose
way of finding the device planes, their operation line and the union of
operation intervals this module imports, so that the holes here are the
holes there.  Where ``xplane.reduce`` names a hole by the one harness span
over its midpoint, this gives each hole's time to the INNERMOST program
span by overlap: a hole that starts in one region and ends in the next is
split between them, and a region's time is its own, without its children.

A trace of a program that places no such span (the parent of the PR that
added them; a training cell) reduces to ``None``: nothing to read.
"""

from __future__ import annotations

import bisect
import functools
import os
import statistics

from perfbench import spec, xplane

PREFIX = "serve."
TURN = "serve.turn"
#: Time of a hole under no span at all.
OUTSIDE = "outside_turn"

Span = tuple[int, int, str]


def innermost(spans: list[Span]) -> list[Span]:
    """Spans of one thread, which nest, cut into disjoint pieces each named
    by the innermost span that covers it.  A child that runs past its
    parent's end (two clock reads apart) is cut at that end."""
    out: list[Span] = []
    stack: list[tuple[int, str]] = []       # (end, name), outermost first
    cursor = 0

    def close(until: int) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(start)
        if stack:
            if start > cursor:
                out.append((cursor, start, stack[-1][1]))
            end = min(end, stack[-1][0])
        cursor = max(cursor, start) if stack else start
        if end > cursor:
            stack.append((end, name))
    close(max((s[1] for s in spans), default=0))
    return out


def apportion(holes: list[tuple[int, int]], pieces: list[Span]) -> dict:
    """Nanoseconds of ``holes`` under each piece's name, by overlap; what
    no piece covers goes under ``OUTSIDE``.  ``pieces`` are disjoint."""
    pieces = sorted(pieces)
    starts = [p[0] for p in pieces]
    out: dict[str, int] = {}
    for a, b in holes:
        covered = 0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(pieces) and pieces[i][0] < b:
            lap = min(b, pieces[i][1]) - max(a, pieces[i][0])
            if lap > 0:
                out[pieces[i][2]] = out.get(pieces[i][2], 0) + lap
                covered += lap
            i += 1
        if b - a > covered:
            out[OUTSIDE] = out.get(OUTSIDE, 0) + (b - a) - covered
    return out


def holes_of(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    u = xplane._union(intervals)
    return [(e0, s1) for (_, e0), (s1, _) in zip(u, u[1:]) if s1 > e0]


def program_line(planes) -> tuple[str, list[Span]] | None:
    """The host line that holds the engine thread's turns, found by what it
    holds, with its ``serve.*`` events."""
    for p in planes:
        if xplane._is_device_plane(p.name):
            continue
        for ln in p.lines:
            spans = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                      ev.name) for ev in ln.events
                     if ev.name.startswith(PREFIX)]
            if any(s[2] == TURN for s in spans):
                return f"{p.name} / {ln.name}", spans
    return None


@functools.lru_cache(maxsize=4)
def reduce(path: str) -> dict | None:
    """Idle seconds of the device under each program span of one trace
    file, averaged over its device planes.  Cached by path: a cell's
    readers share one parse.  Read-only to its callers."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    line = program_line(planes)
    if line is None:
        return None
    line_name, spans = line
    pieces = innermost(spans)
    idle: dict[str, float] = {}
    devices, first, last = 0, None, None
    for p in planes:
        if not xplane._is_device_plane(p.name):
            continue
        _, events = xplane._ops_line(p)
        if not events:
            continue
        devices += 1
        iv = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
              for ev in events]
        lo, hi = min(a for a, _ in iv), max(b for _, b in iv)
        first = lo if first is None else min(first, lo)
        last = hi if last is None else max(last, hi)
        for name, ns in apportion(holes_of(iv), pieces).items():
            idle[name] = idle.get(name, 0.0) + ns * 1e-9
    if not devices:
        return None
    by_name: dict[str, list[int]] = {}
    for a, b, name in spans:
        by_name.setdefault(name, []).append(b - a)
    turns = max(1, len(by_name[TURN]))
    return {
        "idle_s": {k: v / devices for k, v in sorted(idle.items())},
        "ops_span_s": (last - first) * 1e-9,
        "spans": {name: {"count": len(d), "per_turn": len(d) / turns,
                         "median_ms": statistics.median(d) * 1e-6}
                  for name, d in sorted(by_name.items())},
        "found": {"line": line_name, "span_events": len(spans),
                  "devices": devices}}


def of_run(ctx: dict) -> dict | None:
    """The reduction of the traced run behind ``ctx``, with the slice's
    edges (the traced slice as the host clocked it, less first device
    operation to last) beside the holes; ``None`` where the run traced no
    device or the program placed no span."""
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    path = xplane.newest_xplane(os.path.join(spec.OUT_DIR, "trace",
                                             ctx["cell"]))
    red = reduce(path) if path else None
    if red is None:
        return None
    return dict(red, window_s=trace["window_s"],
                edges_s=trace["window_s"] - red["ops_span_s"])


#: The four parts a serving cell reports, each a set of span names.  The
#: turn's own time and its other children are the server loop's; the time
#: of ``serve.step`` outside its three regions (a chunked prefill's
#: dispatch; nothing in a cell that prefills whole buckets) is in no part
#: and is read from ``idle_s`` by name.
PARTS = {
    "stage": ("serve.step.stage",),
    "fetch": ("serve.step.fetch",),
    "retire": ("serve.step.retire",),
    "loop": (TURN, "serve.schedule", "serve.admit", "serve.complete"),
}


def idle_pct(ctx: dict, part: str) -> float | None:
    """Device idle time while the engine thread was in ``part``, as a
    share of the traced slice: the denominator of ``*_device_idle_pct``."""
    red = of_run(ctx)
    if red is None:
        return None
    seconds = sum(red["idle_s"].get(name, 0.0) for name in PARTS[part])
    return 100.0 * seconds / red["window_s"]
