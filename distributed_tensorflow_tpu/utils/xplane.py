"""Dependency-free XPlane profile parser — per-op time from a JAX trace.

``jax.profiler.trace`` writes TensorBoard-loadable ``*.xplane.pb`` protos
(TSL ``XSpace``).  The stock toolchain reads them through TensorBoard's
profile plugin — a GUI; this module decodes the protobuf wire format
directly (no tensorflow/tensorboard import) so the bench harness can put a
per-op time breakdown INTO its JSON artifact: where a train step's device
time goes (matmul vs attention kernels vs elementwise vs collectives) and
how much of the wall clock the device was idle (host/dispatch gap).

The reference has no tracing story at all (its nearest artifact is a
plumbed-but-off ``log_device_placement`` flag, reference
``distributed.py:115``); this is the TPU-idiomatic replacement wired into
measurement rather than a viewer.

Schema (field numbers from tsl/profiler/protobuf/xplane.proto):

- ``XSpace``: planes=1
- ``XPlane``: id=1, name=2, lines=3, event_metadata=4 (map), stat_metadata=5
- ``XLine``: id=1, name=2, timestamp_ns=3, events=4, display_name=11
- ``XEvent``: metadata_id=1, offset_ps=2, duration_ps=3, stats=4,
  num_occurrences=5
- ``XEventMetadata``: id=1, name=2, display_name=4
- ``XStat``: metadata_id=1, double=2, uint64=3, int64=4, str=5, bytes=6,
  ref=7
- ``XStatMetadata``: id=1, name=2
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
from typing import Any, Iterator


# ------------------------------------------------------- wire primitives


def _read_varint(buf: bytes, pos: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("varint too long (corrupt xplane.pb)")


def _fields(buf: bytes) -> Iterator[tuple[int, int, Any]]:
    """Yield (field_number, wire_type, value) over a message's bytes."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _read_varint(buf, pos)
        field, wire = key >> 3, key & 0x7
        if wire == 0:                       # varint
            val, pos = _read_varint(buf, pos)
        elif wire == 1:                     # fixed64
            val = int.from_bytes(buf[pos:pos + 8], "little")
            pos += 8
        elif wire == 2:                     # length-delimited
            n, pos = _read_varint(buf, pos)
            val = buf[pos:pos + n]
            pos += n
        elif wire == 5:                     # fixed32
            val = int.from_bytes(buf[pos:pos + 4], "little")
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


# ------------------------------------------------------------ model


@dataclasses.dataclass
class Event:
    name: str
    offset_ps: int
    duration_ps: int
    stats: dict[str, Any]


@dataclasses.dataclass
class Line:
    name: str
    timestamp_ns: int
    events: list[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: list[Line]


def _parse_stat(buf: bytes, stat_names: dict[int, str]) -> tuple[str, Any]:
    mid, val = 0, None
    for field, _, v in _fields(buf):
        if field == 1:
            mid = v
        elif field == 2:                     # double
            import struct
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif field in (3, 4):                # uint64 / int64
            val = v
        elif field == 7:                     # ref into stat metadata names
            val = stat_names.get(v, v)
        elif field == 5:
            val = v.decode("utf-8", "replace")
        elif field == 6:
            val = v
    return stat_names.get(mid, str(mid)), val


def _parse_event(buf: bytes, event_names: dict[int, str],
                 stat_names: dict[int, str],
                 event_meta_stats: dict[int, dict]) -> Event:
    mid = offset = dur = 0
    stats: dict[str, Any] = {}
    for field, _, v in _fields(buf):
        if field == 1:
            mid = v
        elif field == 2:
            offset = v
        elif field == 3:
            dur = v
        elif field == 4:
            k, sv = _parse_stat(v, stat_names)
            stats[k] = sv
    # Metadata-level stats (e.g. TPU's per-op hlo_category) back-fill what
    # the event itself doesn't carry.
    merged = dict(event_meta_stats.get(mid) or {})
    merged.update(stats)
    return Event(event_names.get(mid, str(mid)), offset, dur, merged)


def _parse_metadata_entry(buf: bytes) -> tuple[int, bytes]:
    """map<int64, X*Metadata> entry -> (key, value_bytes)."""
    key, val = 0, b""
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            val = v
    return key, val


def _metadata_name(buf: bytes) -> str:
    name = display = ""
    for field, _, v in _fields(buf):
        if field == 2:
            name = v.decode("utf-8", "replace")
        elif field == 4 and isinstance(v, bytes):
            display = v.decode("utf-8", "replace")
    return display or name


def _parse_event_metadata(buf: bytes, stat_names: dict[int, str]
                          ) -> tuple[str, dict[str, Any]]:
    """XEventMetadata -> (best name, metadata-level stats).

    On TPU the per-op category ("convolution fusion", "custom call", ...)
    lives in the metadata's OWN stats (field 5), and field 2 (`name`) holds
    the full HLO instruction text while field 4 (`display_name`) has the
    short op name — prefer the short one, keep the stats.
    """
    name = display = ""
    stats: dict[str, Any] = {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = v.decode("utf-8", "replace")
        elif field == 4 and isinstance(v, bytes):
            display = v.decode("utf-8", "replace")
        elif field == 5 and isinstance(v, bytes):
            k, sv = _parse_stat(v, stat_names)
            stats[k] = sv
    return (display or name), stats


def _parse_line(buf: bytes, event_names: dict[int, str],
                stat_names: dict[int, str],
                event_meta_stats: dict[int, dict]) -> Line:
    name = ""
    ts = 0
    events: list[Event] = []
    for field, _, v in _fields(buf):
        if field == 2:
            name = v.decode("utf-8", "replace")
        elif field == 11 and isinstance(v, bytes):
            name = v.decode("utf-8", "replace") or name
        elif field == 3:
            ts = v
        elif field == 4:
            events.append(_parse_event(v, event_names, stat_names,
                                       event_meta_stats))
    return Line(name, ts, events)


def _parse_plane(buf: bytes) -> Plane:
    # Three passes over the raw fields: stat metadata must resolve before
    # event metadata (whose stats reference it), which must resolve before
    # lines (whose events reference both) — the stream may interleave them.
    name = ""
    line_bufs: list[bytes] = []
    em_bufs: list[bytes] = []
    stat_names: dict[int, str] = {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = v.decode("utf-8", "replace")
        elif field == 3:
            line_bufs.append(v)
        elif field == 4:
            em_bufs.append(v)
        elif field == 5:
            k, mv = _parse_metadata_entry(v)
            stat_names[k] = _metadata_name(mv)
    event_names: dict[int, str] = {}
    event_meta_stats: dict[int, dict] = {}
    for b in em_bufs:
        k, mv = _parse_metadata_entry(b)
        nm, st = _parse_event_metadata(mv, stat_names)
        event_names[k] = nm
        event_meta_stats[k] = st
    lines = [_parse_line(b, event_names, stat_names, event_meta_stats)
             for b in line_bufs]
    return Plane(name, lines)


def parse_xspace(data: bytes) -> list[Plane]:
    """Decode a serialized ``XSpace`` into planes/lines/events."""
    return [_parse_plane(v) for field, _, v in _fields(data) if field == 1]


def load_xspace(logdir: str | os.PathLike) -> list[Plane]:
    """Parse the newest ``*.xplane.pb`` under a ``jax.profiler.trace`` dir."""
    pattern = os.path.join(os.fspath(logdir), "**", "*.xplane.pb")
    paths = sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir!r}")
    with open(paths[-1], "rb") as fh:
        data = fh.read()
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return parse_xspace(data)


# --------------------------------------------------------- breakdown


#: bucket -> substrings matched against the op's hlo_category stat (primary)
#: or its name (fallback).  Order matters: first hit wins.
_BUCKETS = (
    ("matmul", ("convolution", "dot", "matmul", "gemm")),
    ("attention_kernel", ("custom-call", "custom call", "mosaic", "flash",
                          "attention")),
    ("collective", ("all-reduce", "all-gather", "all-to-all",
                    "reduce-scatter", "collective", "permute", "send",
                    "recv")),
    ("data_movement", ("copy", "transpose", "reshape", "slice", "concat",
                       "dynamic-update", "gather", "scatter", "select",
                       "infeed", "outfeed")),
)


def classify_op(name: str, category: str = "") -> str:
    hay = f"{category.lower()} {name.lower()}"
    for bucket, needles in _BUCKETS:
        if any(n in hay for n in needles):
            return bucket
    return "elementwise_other"


def device_op_breakdown(planes: list[Plane],
                        device_substr: str = "/device:") -> dict[str, Any]:
    """Aggregate per-op device time from a trace into buckets.

    Walks every ``XLA Ops`` line of every device plane and sums event
    durations by :func:`classify_op` bucket.  Returns::

        {"device_total_ms", "buckets_ms": {bucket: ms},
         "buckets_pct": {bucket: %}, "span_ms", "idle_pct", "top_ops":
         [(name, ms), ...]}

    ``span_ms`` is the union timeline extent of the op lines (first event
    start to last event end); ``idle_pct`` is the fraction of that span the
    device executed nothing — host/dispatch gaps between dispatched ops.
    """
    buckets: dict[str, float] = {}
    per_op: dict[str, float] = {}
    total_ps = 0
    module_ps = 0
    module_calls = 0
    span_start = None
    span_end = None
    for plane in planes:
        if device_substr not in plane.name:
            continue
        for line in plane.lines:
            lname = line.name.lower().strip()
            if lname == "xla modules":
                # One event per executable invocation: the honest per-call
                # device time (immune to host gaps between calls).
                for ev in line.events:
                    module_ps += ev.duration_ps
                    module_calls += 1
                continue
            # Exact match: "Async XLA Ops" durations overlap the main line
            # (DMA in flight behind compute) and would double-count.
            if lname != "xla ops":
                continue
            for ev in line.events:
                cat = str(ev.stats.get("hlo_category", ""))
                bucket = classify_op(ev.name, cat)
                buckets[bucket] = buckets.get(bucket, 0.0) + ev.duration_ps
                key = f"{ev.name} [{cat}]" if cat else ev.name
                per_op[key] = per_op.get(key, 0.0) + ev.duration_ps
                total_ps += ev.duration_ps
                start = line.timestamp_ns * 1000 + ev.offset_ps
                end = start + ev.duration_ps
                span_start = start if span_start is None else min(span_start,
                                                                  start)
                span_end = end if span_end is None else max(span_end, end)
    span_ps = (span_end - span_start) if span_start is not None else 0
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:8]
    return {
        "device_total_ms": round(total_ps / 1e9, 3),
        "module_ms_per_call": (round(module_ps / module_calls / 1e9, 3)
                               if module_calls else None),
        "module_calls": module_calls,
        # Device idle while an executable was resident: gaps XLA left
        # between ops (scheduling/DMA waits) — independent of the host,
        # unlike the timeline-span idle below.
        "intra_module_idle_pct": (round(100 * (1 - total_ps / module_ps), 1)
                                  if module_ps else None),
        "span_ms": round(span_ps / 1e9, 3),
        # Wall-timeline idle between dispatches: the host's gap.
        "idle_pct": (round(100 * (1 - total_ps / span_ps), 1)
                     if span_ps else None),
        "buckets_ms": {k: round(v / 1e9, 3) for k, v in sorted(
            buckets.items(), key=lambda kv: -kv[1])},
        "buckets_pct": {k: round(100 * v / total_ps, 1) for k, v in sorted(
            buckets.items(), key=lambda kv: -kv[1])} if total_ps else {},
        "top_ops": [(name, round(ps / 1e9, 3)) for name, ps in top],
    }


def profile_breakdown(fn, *args, warmup: int = 2, iters: int = 3,
                      logdir: str | None = None) -> dict[str, Any]:
    """Trace ``iters`` calls of ``fn(*args)`` and return the op breakdown.

    ``fn`` must block on completion itself (return after a scalar fetch or
    ``block_until_ready``): dispatch is asynchronous.  The trace dir
    defaults to a temp dir and is left on disk when ``logdir`` is given
    (TensorBoard-loadable for interactive digging).
    """
    import tempfile

    import jax

    for _ in range(warmup):
        fn(*args)
    own = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="dtf_profile_")
    with jax.profiler.trace(logdir):
        for _ in range(iters):
            fn(*args)
    planes = load_xspace(logdir)
    out = device_op_breakdown(planes)
    out["iters"] = iters
    out["trace_dir"] = None if own else logdir
    return out
