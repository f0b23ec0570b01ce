#!/usr/bin/env python3
"""Record the small serving trace the span reducer's test reads
(``tests/perfbench/data/spans/tiny_serve_tpu_1.xplane.pb``): about a dozen
turns of a tiny ``DecodeEngine`` behind a ``ServingServer`` under the same
profiler options as a traced run — three requests of two prompt lengths
served together, a 20 ms pause with the engine thread idle (a hole outside
every turn), then one request more.  Beside it goes what ``spans.reduce``
and ``xplane.reduce`` read on the chip when it was recorded.

The profiler's file for even so small a run is over a megabyte, most of it
the compiled modules' HLO and per-event statistics that no reducer of this
benchmark reads.  ``slim`` drops those at the level of the wire format
(every plane but the devices' and the host's; the statistics of events and
of their metadata) and leaves every event's name, start and duration as
recorded; the reductions are taken from the slimmed file and checked
against the whole one before it is thrown away.

    chiprun -- python3 perfbench/tools/record_span_trace.py [out_dir]
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _encode(value: int) -> bytes:
    out = bytearray()
    while value > 0x7F:
        out.append((value & 0x7F) | 0x80)
        value >>= 7
    out.append(value)
    return bytes(out)


def _fields(buf: bytes):
    """(field, wire type, start, start of the value, end) of each field of
    a protobuf message."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 2:
            size, i = _varint(buf, i)
            value, i = i, i + size
        elif wire == 0:
            value = i
            _, i = _varint(buf, i)
        else:
            value, i = i, i + {1: 8, 5: 4}[wire]
        yield key >> 3, wire, start, value, i


def _filter(buf: bytes, rules: dict) -> bytes:
    """A protobuf message without the sub-messages ``rules`` maps to None
    and with those it maps to a dict filtered in turn; the rest byte for
    byte.  A callable rule decides on the sub-message's bytes."""
    out = bytearray()
    for field, wire, start, value, end in _fields(buf):
        rule = rules.get(field, True) if wire == 2 else True
        if callable(rule):
            rule = rule(buf[value:end])
        if rule is None:
            continue
        if isinstance(rule, dict):
            body = _filter(buf[value:end], rule)
            out += _encode(field << 3 | 2) + _encode(len(body)) + body
        else:
            out += buf[start:end]
    return bytes(out)


def slim(xspace: bytes) -> bytes:
    """``XSpace.planes`` (1) -> ``XPlane``: ``lines`` (3) -> ``XLine.events``
    (4) -> ``XEvent`` without ``stats`` (4); ``event_metadata`` (4) ->
    map value (2) -> ``XEventMetadata`` without ``metadata`` (3) and
    ``stats`` (5).  Only the devices' planes and the host's are kept."""
    from perfbench import xplane
    plane = {3: {4: {4: None}}, 4: {2: {3: None, 5: None}}}

    def keep(payload: bytes):
        name = next(payload[a:b].decode() for field, wire, _, a, b
                    in _fields(payload) if (field, wire) == (2, 2))
        kept = xplane._is_device_plane(name) or name == "/host:CPU"
        return plane if kept else None

    return _filter(xspace, {1: keep})


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, "chiprun_out", "span_trace_fixture")
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                           EngineConfig)
    from distributed_tensorflow_tpu.serving.scheduler import (FairScheduler,
                                                              Request)
    from distributed_tensorflow_tpu.serving.server import ServingServer
    from perfbench import spans, xplane

    cfg = dataclasses.replace(
        gpt_lib.mini(), vocab_size=256, hidden_size=128, num_layers=2,
        num_heads=4, intermediate_size=256, max_position=128,
        dtype="bfloat16")
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=4, page_size=8, num_pages=64, max_pages_per_seq=16))
    server = ServingServer(engine, FairScheduler(), port=0,
                           request_timeout_s=120.0)
    server.start()

    def serve(batch: list[tuple[int, int]]) -> None:
        """Submit (prompt length, tokens) requests together; wait for all."""
        reqs = [Request([(7 * i + k) % 256 for k in range(p)], n, seed=i)
                for i, (p, n) in enumerate(batch)]
        threads = [threading.Thread(target=server.submit, args=(r,))
                   for r in reqs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert all(len(r.tokens) == r.num_tokens for r in reqs), reqs

    serve([(12, 2), (30, 2)])           # both prefill programs and the step
    tmp = os.path.join(out_dir, "tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=options)
    t0 = time.monotonic()
    serve([(12, 5), (30, 8), (12, 6)])
    time.sleep(0.02)
    serve([(30, 3)])
    window = time.monotonic() - t0
    jax.profiler.stop_trace()
    server.shutdown()

    devs = jax.devices()
    name = f"tiny_serve_{devs[0].platform}_{len(devs)}.xplane.pb"
    path = os.path.join(out_dir, name)
    recorded = xplane.newest_xplane(tmp)
    with open(recorded, "rb") as fh:
        whole_bytes = fh.read()
    with open(path, "wb") as fh:
        fh.write(slim(whole_bytes))

    def reductions(file: str) -> dict:
        whole = xplane.reduce(file)
        return {"spans": spans.reduce(file),
                "xplane": {k: whole.get(k) for k in (
                    "busy_s", "span_s", "devices", "device_events",
                    "class_s")}}

    read = reductions(path)
    if reductions(recorded) != read:
        raise SystemExit("slimming the trace moved a number")
    then = {"window_s": window, "recorded_bytes": len(whole_bytes), **read}
    shutil.rmtree(tmp, ignore_errors=True)
    with open(path + ".json", "w") as fh:
        json.dump(then, fh, indent=1)
    print(json.dumps({"file": name, "bytes": os.path.getsize(path), **then}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
