"""Device time by the PROGRAM's own regions, from the trace file and nothing
else: every operation of a traced slice's device line given to a program
(the compiled module that ran it) and to a region (``utils/profiling.region``,
a ``jax.named_scope`` whose name is one of ``profiling.REGIONS``).

``xplane.py`` and ``spans.py`` read a trace through
``jax.profiler.ProfileData``, which shows an event's name, its time and its
own statistics.  The name the program gave an operation is not among them.
It is in the file all the same, twice: as a statistic of the event's
METADATA (``tf_op``, the instruction's ``op_name``, beside ``program_id``),
and in each compiled module's whole ``Hlo Proto`` on the plane
``/host:metadata``, fused computations' inner instructions included.  So
this module reads the file's wire format itself: the messages of
``xplane.proto`` (``XSpace``, ``XPlane``, ``XLine``, ``XEvent``,
``XEventMetadata``, ``XStat``, ``XStatMetadata``) and of ``hlo.proto``
(``HloProto``, ``HloModuleProto``, ``HloComputationProto``,
``HloInstructionProto``, ``OpMetadata``), the fields it needs of each.  No
import beyond the standard library, ``perfbench`` and the program's
vocabulary; nothing is asked of the worker and nothing is compiled.

Which planes are devices and which of their lines holds the operations is
``xplane.py``'s decision (``_is_device_plane``, ``_ops_line``), and so are
an operation's short name and class, so that the total here IS
``xplane.reduce``'s ``busy_s``.

1. **Program.**  An operation's metadata names its module by ``program_id``
   (the id of the module's entry on ``/host:metadata``); where a file has
   no such statistic, the ``XLA Modules`` event that covers the
   operation's start does.  A program is named without its fingerprint
   (``jit_prefill``: the prefill programs of all buckets are one program
   here), and its executions are its events on the ``XLA Modules`` line.
2. **Region.**  The innermost component of the operation's own ``op_name``
   (``tf_op``, else the instruction's in the module's HLO) that is in
   ``REGIONS``, after stripping the wrappers JAX puts around a name
   (``jit( )``, ``jvp( )``, ``transpose( )``, ``vmap( )``).  A fusion whose
   own name holds none takes the region most of its fused instructions
   carry (of two regions that tie, the one whose instruction stands
   nearest the fusion's root).  Still none: ``unnamed``, kept by HLO head
   (opcode and result shape).  A collective is ``collective`` whatever
   name the compiler left on it.
3. **Self time.**  Operations on the line nest (a ``while`` and its body):
   an operation's time is its duration less that of the operations inside
   it, so a rolled loop's body counts once, the ``while`` that spans it
   does not count on top, and the self times sum to the union.

The reduction always says what it ``found``: false where no program of the
file carries a region (a CPU rehearsal; the parent of the PR that placed
them; an executable that JAX's persistent cache loaded with the names it
was FIRST compiled with, see docs/observability.md, "Device regions"), and
then every reader below gives nothing.  A program's ``names`` are the
regions found on it: what to look at first after a region was moved.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
import struct
import types

from perfbench import spec, xplane

UNNAMED = "unnamed"
#: A collective is nobody's compute: whatever name the compiler left on it
#: (the operation whose gradient it reduces), it is kept apart.
COLLECTIVE = "collective"
#: How many of its largest unnamed operations a program keeps, by HLO head.
HEADS = 5


@functools.lru_cache(maxsize=1)
def vocabulary() -> frozenset:
    """The device regions' names: the program's own tuple
    (``utils/profiling.REGIONS``).  A program without one (the parent of
    the PR that added it) has no region to find.  Importing the module
    initialises no backend, so the harness's parent process may."""
    try:
        from distributed_tensorflow_tpu.utils import profiling
    except ImportError:
        return frozenset()
    return frozenset(getattr(profiling, "REGIONS", ()))


# ------------------------------------------------------- the wire format


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf):
    """(field number, wire type, value) of each field of a protobuf
    message: an int for a varint or a fixed field's raw bits, the bytes of
    a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wire == 5:
            value, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _stat(buf, names: dict, refs: dict):
    """An ``XStat`` as (its name, its value); a ``ref_value`` reads the
    string it refers to."""
    name, value = None, None
    for field, _, v in _fields(buf):
        if field == 1:
            name = names.get(v, v)
        elif field == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = _text(v)
        elif field == 6:
            value = bytes(v)
        elif field == 7:
            value = refs.get(v, v)
    return name, value


def _event_metadata(buf, names: dict, refs: dict) -> dict:
    out = {"id": 0, "name": "", "stats": {}}
    for field, _, v in _fields(buf):
        if field == 1:
            out["id"] = v
        elif field == 2:
            out["name"] = _text(v)
        elif field == 5:
            k, val = _stat(v, names, refs)
            out["stats"][k] = val
    return out


def _map_value(buf):
    """The value of a protobuf map entry (field 2)."""
    for field, wire, v in _fields(buf):
        if field == 2 and wire == 2:
            return v
    return b""


def _plane_head(buf) -> tuple[str, list, list, list]:
    """An ``XPlane``'s name and its raw lines, event metadata and
    statistic metadata."""
    name, lines, events, stats = "", [], [], []
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            lines.append(v)
        elif field == 4:
            events.append(v)
        elif field == 5:
            stats.append(v)
    return name, lines, events, stats


def _stat_names(raw: list) -> dict:
    out = {}
    for entry in raw:
        ident = name = None
        for field, _, v in _fields(_map_value(entry)):
            if field == 1:
                ident = v
            elif field == 2:
                name = _text(v)
        out[ident] = name
    return out


def _line(buf) -> tuple[str, list]:
    """An ``XLine``'s name and its events as (start ps, end ps, metadata
    id), statistics left unread."""
    name, t0, events = "", 0, []
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            t0 = _signed(v) * 1000
        elif field == 4:
            meta = off = dur = 0
            for f, _, x in _fields(v):
                if f == 1:
                    meta = x
                elif f == 2:
                    off = x
                elif f == 3:
                    dur = x
            events.append((off, off + dur, meta))
    return name, [(t0 + a, t0 + b, m) for a, b, m in events]


def planes(path: str):
    """(name, raw lines, raw event metadata, raw statistic metadata) of
    every plane of a trace file."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    for field, wire, v in _fields(buf):
        if field == 1 and wire == 2:
            yield _plane_head(v)


# ------------------------------------------- names from the modules' HLO


def _ids(wire: int, value) -> list:
    """A repeated int64 field, packed or not."""
    if wire != 2:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_module(buf) -> dict:
    """A serialized ``HloProto`` as {instruction name: (its
    ``metadata.op_name``, its opcode, the ``op_name`` of each instruction
    of the computations it calls, root last)} over all its computations."""
    computations: dict = {}     # id -> [(instruction, op_name)], in order
    instructions: dict = {}     # name -> (op_name, opcode, called ids)
    for field, wire, v in _fields(buf):
        if field != 1 or wire != 2:
            continue
        for f, _, x in _fields(v):                      # HloModuleProto
            if f != 3:
                continue
            ident, body = None, []
            for g, _, inst in _fields(x):               # HloComputationProto
                if g == 5:
                    ident = inst
                if g != 2:
                    continue
                name = code = op_name = ""
                called: list = []
                for h, w, y in _fields(inst):           # HloInstructionProto
                    if h == 1:
                        name = _text(y)
                    elif h == 2:
                        code = _text(y)
                    elif h == 38:
                        called += _ids(w, y)
                    elif h == 7:
                        for k, _, z in _fields(y):      # OpMetadata
                            if k == 2:
                                op_name = _text(z)
                instructions[name] = (op_name, code, called)
                body.append(op_name)
            computations[ident] = body
    return {name: (op_name, code,
                   [n for c in called for n in computations.get(c, ())])
            for name, (op_name, code, called) in instructions.items()}


def _modules_hlo(all_planes: list) -> dict:
    """{program id: (the module as the ``XLA Modules`` line names it,
    ``jit_step(<id>)``, its serialized ``Hlo Proto``)} from the plane
    ``/host:metadata``; parsed when an operation first asks."""
    out = {}
    for name, _, raw_events, raw_stats in all_planes:
        if name != "/host:metadata":
            continue
        names = _stat_names(raw_stats)
        for entry in raw_events:
            meta = _event_metadata(_map_value(entry), names, {})
            proto = meta["stats"].get("Hlo Proto")
            if isinstance(proto, bytes):
                out[meta["id"]] = (meta["name"], proto)
    return out


# ------------------------------------------------------- the reduction

WRAPPED = re.compile(r"[A-Za-z_]+\((.*)\)")
PROGRAM = re.compile(r"^(.*?)\(\d+\)$")
SHAPE = re.compile(r"\{[^{}]*\}")


def region_of(op_name: str, vocab) -> str | None:
    """The innermost path component of ``op_name`` that ``vocab`` holds,
    wrappers (``transpose(jvp(mlp))``) stripped."""
    for part in reversed(op_name.split("/")):
        while (m := WRAPPED.fullmatch(part)):
            part = m.group(1)
        if part in vocab:
            return part
    return None


def fused_region(inner: list, vocab) -> str | None:
    """The region most of a fusion's instructions carry, given their
    ``op_name`` in the computation's order (root last); of regions that
    tie, the one that stands nearest the root."""
    count: dict = {}
    for at, op_name in enumerate(inner):
        region = region_of(op_name, vocab) if op_name else None
        if region is not None:
            n, _ = count.get(region, (0, 0))
            count[region] = (n + 1, at)
    return max(count, key=count.get) if count else None


def opcode(hlo_line: str) -> str:
    """The HLO opcode of an operation the trace names by its whole HLO
    line; else the instruction's name without its number."""
    lhs, _, rhs = hlo_line.partition(" = ")
    m = xplane.OPCODE.search(" " + rhs) if rhs else None
    if m:
        return m.group(1)
    return re.sub(r"[.\d]+$", "", xplane.short_name(hlo_line)) or "?"


def head(hlo_line: str) -> str:
    """An operation's HLO head: its opcode and its result's shape without
    the layout, ``copy bf16[2048,3,16,128]``, so that the like operations
    of every layer fall together."""
    _, _, rhs = hlo_line.partition(" = ")
    code = opcode(hlo_line)
    shape = SHAPE.sub("", rhs.split(f" {code}(", 1)[0]).strip() if rhs else ""
    return f"{code} {shape}"[:120].strip()


def self_times(events: list) -> tuple[list, int]:
    """``events`` [(start, end, key)] of one line, which nest: each
    event's own time, its duration less what the events inside it take,
    as [(start, key, self)] in the events' order by start, and the time
    of events that overlap without nesting (given to the later one).  The
    self times sum to the union of the intervals."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [0] * len(events)
    stack: list[int] = []           # indices of open events, outermost first
    overlap = 0
    for i in order:
        start, end, _ = events[i]
        while stack and events[stack[-1]][1] <= start:
            stack.pop()
        own[i] = end - start
        cursor, first = start, True
        for j in reversed(stack):
            j_end = events[j][1]
            if j_end <= cursor:
                continue
            lap = min(end, j_end) - cursor
            own[j] -= lap
            if first and end > j_end:
                overlap += lap
            first = False
            cursor += lap
            if cursor >= end:
                break
        stack.append(i)
    return [(events[i][0], events[i][2], own[i]) for i in order], overlap


def _ops_and_modules(lines: list) -> tuple:
    """Of a device plane's parsed lines (name, events): the operations'
    line, which is ``xplane._ops_line``'s choice, and the modules' line."""
    as_read = [types.SimpleNamespace(name=ln[0], events=ln[1], parsed=ln)
               for ln in lines]
    ops, _ = xplane._ops_line(types.SimpleNamespace(lines=as_read))
    modules = next((ln for ln in lines if ln[1]
                    and re.search(r"\bmodules\b", ln[0], re.I)), None)
    return (ops.parsed if ops else None), modules


def _empty() -> dict:
    return {"executions": 0, "seconds": 0.0, "regions": {}, "names": set(),
            "unnamed": {}}


class _Namer:
    """From an operation's metadata and module to its region, each pair
    looked up once."""

    def __init__(self, hlo: dict, vocab, source: dict):
        self.hlo, self.vocab, self.source = hlo, vocab, source
        self.parsed: dict = {}
        self.seen: dict = {}

    def instruction(self, program_id, name: str):
        if program_id not in self.parsed:
            entry = self.hlo.get(program_id)
            self.parsed[program_id] = hlo_module(entry[1]) if entry else {}
        return self.parsed[program_id].get(name)

    def region(self, plane: str, meta: dict, program_id) -> str | None:
        key = (plane, meta["id"], program_id)   # a plane numbers its own
        if key not in self.seen:
            self.seen[key] = self._region(meta, program_id)
        return self.seen[key]

    def _region(self, meta: dict, program_id) -> str | None:
        if xplane.op_class(meta["name"]) == "collective":
            return COLLECTIVE
        op_name, via = meta["stats"].get("tf_op"), "tf_op"
        named = isinstance(op_name, str) and op_name
        region = region_of(op_name, self.vocab) if named else None
        inst = None if region else self.instruction(
            program_id, xplane.short_name(meta["name"]))
        if inst and not named and inst[0]:
            region, via = region_of(inst[0], self.vocab), "hlo_proto"
        if inst and region is None and inst[1] == "fusion":
            region, via = fused_region(inst[2], self.vocab), "fused"
        if region is not None:
            self.source[via] += 1
        return region


@functools.lru_cache(maxsize=4)
def reduce(path: str) -> dict:
    """Device self seconds of one trace file by program and region, the
    mean over its device planes.  Cached by path: a cell's readers share
    one parse.  Read-only to its callers.  Never raises on a file it
    cannot read: ``found`` is false then, and ``programs`` empty.

    ``programs``: {program: {"executions", "seconds", "regions": {region:
    seconds}, "names": [the program's regions found], "unnamed": [[HLO
    head, operations, seconds] of its five largest]}}; ``total_s`` (=
    ``xplane.reduce``'s ``busy_s``), ``overlap_s``, ``devices``, and under
    ``source`` the planes and lines read and how many operations each way
    of naming named."""
    source = {"device_planes": [], "ops_lines": [], "tf_op": 0,
              "hlo_proto": 0, "fused": 0}
    nothing = {"found": False, "devices": 0, "total_s": 0.0,
               "overlap_s": 0.0, "programs": {}, "source": source}
    vocab = vocabulary()
    try:
        all_planes = list(planes(path))
    except (OSError, ValueError, IndexError, struct.error):
        return nothing
    hlo = _modules_hlo(all_planes)
    programs: dict = {}
    devices, overlap_ps = 0, 0
    namer = _Namer(hlo, vocab, source)
    by_module = {module: pid for pid, (module, _) in hlo.items()}
    for name, raw_lines, raw_events, raw_stats in all_planes:
        if not xplane._is_device_plane(name):
            continue
        ops, modules = _ops_and_modules([_line(ln) for ln in raw_lines])
        if ops is None:
            continue
        devices += 1
        source["device_planes"].append(name)
        source["ops_lines"].append(ops[0])
        names = _stat_names(raw_stats)
        metas = {}
        for entry in raw_events:
            meta = _event_metadata(_map_value(entry), names, names)
            metas[meta["id"]] = meta
        spans = sorted((a, b, metas[m]["name"])
                       for a, b, m in (modules[1] if modules else [])
                       if m in metas)
        starts = [s[0] for s in spans]
        for _, _, module in spans:
            programs.setdefault(PROGRAM.sub(r"\1", module),
                                _empty())["executions"] += 1
        timed, lap = self_times(ops[1])
        overlap_ps += lap
        heads: dict = {}        # metadata id -> HLO head, worked out once
        for start, meta_id, own in timed:
            meta = metas.get(meta_id)
            if meta is None:
                continue
            program_id = meta["stats"].get("program_id")
            if program_id in hlo:
                module = hlo[program_id][0]
            else:
                i = bisect.bisect_right(starts, start) - 1
                module = spans[i][2] if i >= 0 and spans[i][1] >= start \
                    else "outside_modules"
                program_id = by_module.get(module)
            region = namer.region(name, meta, program_id)
            entry = programs.setdefault(PROGRAM.sub(r"\1", module),
                                        _empty())
            sec = own * 1e-12
            entry["seconds"] += sec
            if region is None:
                region = UNNAMED
                if meta_id not in heads:
                    heads[meta_id] = head(meta["name"])
                held = entry["unnamed"].setdefault(heads[meta_id],
                                                   [set(), 0.0])
                held[0].add(meta_id)
                held[1] += sec
            elif region != COLLECTIVE:
                entry["names"].add(region)
            entry["regions"][region] = entry["regions"].get(region, 0.0) + sec
    if not devices:
        return nothing
    for entry in programs.values():
        entry["executions"] /= devices
        entry["seconds"] /= devices
        entry["regions"] = {k: v / devices
                            for k, v in sorted(entry["regions"].items())}
        entry["names"] = sorted(entry["names"])
        entry["unnamed"] = sorted(
            ([k, len(ops_), sec / devices]
             for k, (ops_, sec) in entry["unnamed"].items()),
            key=lambda row: -row[2])[:HEADS]
    return {"found": any(p["names"] for p in programs.values()),
            "devices": devices, "programs": programs,
            "total_s": sum(p["seconds"] for p in programs.values()),
            "overlap_s": overlap_ps * 1e-12 / devices, "source": source}


# ------------------------------------------------------- for the readers


def of_run(ctx: dict) -> dict | None:
    """The reduction of the traced run behind ``ctx``; ``None`` where the
    run traced no device or its programs carry no region."""
    trace = ctx.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    path = xplane.newest_xplane(os.path.join(spec.OUT_DIR, "trace",
                                             ctx["cell"]))
    red = reduce(path) if path else None
    return red if red and red["found"] else None


#: The programs that run the engine's decode step, and its prefills.
DECODE, PREFILL = ("jit_step", "jit_spec_step"), ("jit_prefill",)
#: What a decode step spends on the cache and on attending over it: the
#: table-wide read, the rows' write, the scores (per head, over latents,
#: or a linear layer's state), with what nests in them.  Every other
#: region of a step is, by far, the read of its weights.
ATTENTION = ("cache.gather", "cache.write", "attn.scores", "mla.absorb",
             "linear_attention.step")


def _of(ctx: dict, programs: tuple | None):
    """Executions, seconds and seconds by region of ``programs`` together
    (``None``: of the program that took most device time, a training
    cell's step) in the traced run behind ``ctx``; ``None`` where they
    carry no region."""
    red = of_run(ctx)
    if red is None:
        return None
    if programs is None:
        programs = (max(red["programs"],
                        key=lambda p: red["programs"][p]["seconds"]),)
    runs = seconds = 0.0
    by: dict = {}
    named = False
    for name in programs:
        entry = red["programs"].get(name)
        if entry is None:
            continue
        named = named or bool(entry["names"])
        runs += entry["executions"]
        seconds += entry["seconds"]
        for region, sec in entry["regions"].items():
            by[region] = by.get(region, 0.0) + sec
    return (runs, seconds, by) if named and runs and seconds else None


def _share(by: dict, seconds: float, regions: tuple | None) -> float:
    """Seconds of ``regions``; ``None``: of every region that is neither
    attention's nor ``unnamed`` (what is left of the whole, so that the
    three parts add up by construction)."""
    if regions is None:
        return seconds - _share(by, seconds, ATTENTION + (UNNAMED,))
    return sum(by.get(r, 0.0) for r in regions)


def ms_per_execution(ctx: dict, programs: tuple, regions: tuple | None):
    """Device self time of ``regions`` an execution of ``programs``, in
    milliseconds."""
    got = _of(ctx, programs)
    return got and 1e3 * _share(got[2], got[1], regions) / got[0]


def pct_of_programs(ctx: dict, programs: tuple | None, regions: tuple):
    """``regions``' share of the device time of ``programs``."""
    got = _of(ctx, programs)
    return got and 100.0 * _share(got[2], got[1], regions) / got[1]
