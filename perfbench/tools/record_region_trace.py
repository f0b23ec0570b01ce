#!/usr/bin/env python3
"""Record the small serving trace the region reducer's test reads
(``tests/perfbench/data/regions/tiny_looped_tpu_1.xplane.pb.gz``): a tiny
``DecodeEngine`` over a model whose two layers run three times over the
same weights (so that its programs hold a ``while`` with the layers' work
nested inside it), three requests of two prompt lengths (two prefill
programs beside the step, with operations named alike) served to the end
under the same profiler options as a traced run.  Beside it goes what
``regions.reduce`` and ``xplane.reduce`` read on the chip when it was
recorded.

As ``record_span_trace.py`` does, the file is slimmed at the level of the
wire format: only the devices' planes (their events without statistics,
and of their metadata's statistics only ``tf_op`` and ``program_id``) and
``/host:metadata`` (of every module's ``Hlo Proto`` only each
instruction's name, opcode, ``op_name`` and called computations), which
is all the reducer reads.  The reductions are taken from the slimmed file and checked against
the whole one before it is thrown away.

    chiprun -- python3 perfbench/tools/record_region_trace.py [out_dir]
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import regions, spec, xplane  # noqa: E402

#: The wire-format helpers of the span trace's recorder (``_fields``,
#: ``_filter``, ``_encode``), loaded from its file.
_wire = spec.load_module(os.path.join(spec.HERE, "tools",
                                      "record_span_trace.py"))
#: The statistics of an operation's metadata that the reducer reads.
STATS = ("tf_op", "program_id")
#: Of a module's ``HloProto`` the length-delimited fields the reducer
#: reads, message by message (numbers: whole fields kept as they are):
#: ``hlo_module`` (1) -> ``name`` (1), ``computations`` (3) -> ``name``
#: (1), ``instructions`` (2) -> ``name`` (1), ``opcode`` (2), ``metadata``
#: (7) -> ``op_name`` (2), ``called_computation_ids`` (38).
HLO = {1: {1: True, 3: {1: True, 2: {1: True, 2: True, 7: {2: True},
                                     38: True}}}}


def only(buf: bytes, keep: dict) -> bytes:
    """A protobuf message with, of its length-delimited fields, only those
    ``keep`` names (a dict: filtered in turn); its numbers byte for byte."""
    out = bytearray()
    for field, wire, start, value, end in _wire._fields(buf):
        rule = keep.get(field) if wire == 2 else True
        if isinstance(rule, dict):
            body = only(buf[value:end], rule)
            out += _wire._encode(field << 3 | 2) + _wire._encode(len(body)) \
                + body
        elif rule:
            out += buf[start:end]
    return bytes(out)


def slim(xspace: bytes) -> bytes:
    """``XSpace.planes`` (1) -> ``XPlane``.  A device's: ``lines`` (3) ->
    ``XLine.events`` (4) -> ``XEvent`` without ``stats`` (4);
    ``event_metadata`` (4) -> map value (2) -> ``XEventMetadata`` without
    ``metadata`` (3) and with, of its ``stats`` (5), only ``STATS``.
    ``/host:metadata``: every module's ``Hlo Proto`` (the ``bytes_value``,
    6, of that statistic) cut down to ``HLO``.  No other plane."""
    def ident(stat: bytes):
        return next((v for f, _, v in regions._fields(stat) if f == 1), None)

    def keep(payload: bytes):
        name, _, _, raw_stats = regions._plane_head(payload)
        names = regions._stat_names(raw_stats)
        if name == "/host:metadata":
            return {4: {2: {5: lambda stat: {6: lambda proto: only(proto, HLO)}
                            if names.get(ident(stat)) == "Hlo Proto"
                            else True}}}
        if not xplane._is_device_plane(name):
            return None
        return {3: {4: {4: None}},
                4: {2: {3: None, 5: lambda stat: True
                        if names.get(ident(stat)) in STATS else None}}}

    return cut(xspace, {1: keep})


def cut(buf: bytes, rules: dict) -> bytes:
    """``record_span_trace._filter`` with one rule more: bytes (from a
    callable) stand in the sub-message's place."""
    out = bytearray()
    for field, wire, start, value, end in _wire._fields(buf):
        rule = rules.get(field, True) if wire == 2 else True
        if callable(rule):
            rule = rule(buf[value:end])
        if rule is None:
            continue
        if isinstance(rule, (dict, bytes)):
            body = rule if isinstance(rule, bytes) \
                else cut(buf[value:end], rule)
            out += _wire._encode(field << 3 | 2) + _wire._encode(len(body)) \
                + body
        else:
            out += buf[start:end]
    return bytes(out)


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "region_trace_fixture")
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                           EngineConfig)
    from distributed_tensorflow_tpu.serving.scheduler import Request

    cfg = dataclasses.replace(
        gpt_lib.mini(), vocab_size=256, hidden_size=128, num_layers=2,
        num_heads=4, intermediate_size=256, max_position=128,
        dtype="bfloat16", pos_encoding="rope", activation="swiglu",
        norm="rmsnorm", norm_placement="sandwich", loop_steps=3,
        exit_gate=True)
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=4, page_size=8, num_pages=64, max_pages_per_seq=16))

    def serve(batch: list[tuple[int, int]]) -> None:
        for i, (p, n) in enumerate(batch):
            engine.admit(Request([(7 * i + k) % 256 for k in range(p)], n,
                                 seed=i))
        while engine.active_slots:
            engine.step()

    serve([(12, 2), (30, 2)])           # both prefill programs and the step
    tmp = os.path.join(out_dir, "tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    serve([(12, 5), (30, 8), (12, 6)])
    jax.profiler.stop_trace()

    devs = jax.devices()
    name = f"tiny_looped_{devs[0].platform}_{len(devs)}.xplane.pb"
    path = os.path.join(out_dir, name)
    recorded = xplane.newest_xplane(tmp)
    with open(recorded, "rb") as fh:
        whole_bytes = fh.read()
    with open(path, "wb") as fh:
        fh.write(slim(whole_bytes))

    def reductions(file: str) -> dict:
        whole = xplane.reduce(file)
        return {"regions": regions.reduce(file),
                "xplane": {k: whole.get(k) for k in (
                    "busy_s", "devices", "device_events")}}

    read = reductions(path)
    if reductions(recorded) != read:
        raise SystemExit("slimming the trace moved a number")
    if not read["regions"]["found"]:
        raise SystemExit("the trace holds no region of the program's: "
                         "nothing to record")
    then = {"recorded_bytes": len(whole_bytes), **read}
    shutil.rmtree(tmp, ignore_errors=True)
    with open(path + ".json", "w") as fh:
        json.dump(then, fh, indent=1)
    # The file is kept gzipped (the modules' HLO are most of it and
    # repeat themselves); the test unpacks it.
    with open(path, "rb") as fh, open(path + ".gz", "wb") as out, \
            gzip.GzipFile(fileobj=out, mode="wb", compresslevel=9,
                          mtime=0) as packed:
        packed.write(fh.read())
    os.remove(path)
    print(json.dumps({"file": name + ".gz",
                      "bytes": os.path.getsize(path + ".gz"), **then}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
