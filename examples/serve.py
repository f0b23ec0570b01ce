"""Minimal serving shim for exported StableHLO artifacts — closes the
train → export → serve loop (the reference never had one: its graph dies
with the process, reference ``distributed.py:108-131``).

Loads an artifact written by ``tools/export_model.py`` (self-contained:
weights are baked-in constants; symbolic batch dimension) and answers HTTP
requests, micro-batching concurrent callers into one device call::

    python -m distributed_tensorflow_tpu.tools.export_model \
        --model=gpt_mini --logdir <run>/gpt_mini --output /tmp/g.stablehlo
    python examples/serve.py --artifact /tmp/g.stablehlo --port 8600

    curl -d '{"prompt": [10, 11, 12], "num_tokens": 8}' \
        localhost:8600/generate           # gpt_mini: greedy decode
    curl -d '{"prompt": [10, 11, 12], "num_tokens": 8,
              "temperature": 0.8, "top_k": 40, "top_p": 0.9, "seed": 1}' \
        localhost:8600/generate           # sampled (r5): per-request
                                          # config, reproducible per seed
    curl -d '{"inputs": [[...784 floats...]]}' \
        localhost:8600/predict            # classifiers: raw forward
    curl localhost:8600/healthz

Decode prefers the artifact's KV-CACHED pair when the export wrote one
(``<artifact>.prefill`` + ``<artifact>.decode``, see
``tools/export_model.py::export_gpt_decode``): the prompt prefills
per-layer caches in one pass, then each device call generates a CHUNK of
tokens entirely on device against the caches — O(seq_len) per token
(O(window) for sliding-window checkpoints, whose pair carries a RING
cache and a per-row lengths input to prefill), with dispatch cost
amortized over the chunk.  Without the pair (older artifacts) decode
falls back to running the exported fixed-length FORWARD iteratively
(argmax feed-back at each row's own frontier) — O(S²) per token, the
fully-self-contained trade-off.
``eos_id`` stops a row early; rows in one micro-batch step together until
every row is done.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

# `python examples/serve.py` runs with examples/ as sys.path[0]; make the
# repo checkout importable too (a pip-installed package needs no help).
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.append(_REPO)


def load_artifact(path: str):
    """(callable, metadata, cached) from an export + its .json sidecar.

    ``cached`` is None, or — when the sidecar's ``decode`` section points
    at prefill/decode blobs that exist next to the artifact — a dict with
    jitted ``prefill``/``decode`` callables plus the cache geometry.  The
    jit wrapper is what caches one compilation per (batch, prompt-bucket)
    shape across requests."""
    from distributed_tensorflow_tpu.tools.export_model import load_exported

    exported = load_exported(path)
    with open(path + ".json") as fh:
        meta = json.load(fh)
    cached = None
    dmeta = meta.get("decode")
    if dmeta:
        base = os.path.dirname(os.path.abspath(path))
        pre_path = os.path.join(base, dmeta["files"]["prefill"])
        dec_path = os.path.join(base, dmeta["files"]["decode"])
        if os.path.exists(pre_path) and os.path.exists(dec_path):
            import jax
            cached = {
                "prefill": jax.jit(load_exported(pre_path).call),
                "decode": jax.jit(load_exported(dec_path).call),
                "capacity": int(dmeta["capacity"]),
                "chunk": int(dmeta["chunk"]),
                # Windowed (ring-cache) pairs take a per-row lengths input
                # to prefill (older sidecars lack the key -> full cache).
                "window": int(dmeta.get("window", 0)),
            }
            samp_name = dmeta["files"].get("decode_sample")
            samp_path = (os.path.join(base, samp_name) if samp_name
                         else None)
            if samp_path and os.path.exists(samp_path):
                # Sampled decode (r5): temperature/top-k/top-p as per-row
                # traced inputs — absent on pre-r5 artifacts (greedy only).
                cached["decode_sample"] = jax.jit(
                    load_exported(samp_path).call)
    return exported, meta, cached


def decode_batch(call, prompts: list[list[int]], num_tokens: list[int],
                 seq_len: int, eos_id: int | None = None) -> list[list[int]]:
    """Greedy decode a micro-batch through the exported forward.

    All rows step together (one device call per token across the whole
    batch); each row stops contributing once its own budget — or its eos —
    is reached.  Returns prompt + generation per row.
    """
    B = len(prompts)
    lens = np.asarray([len(p) for p in prompts])
    want = np.asarray(num_tokens)
    if np.any(lens + want > seq_len):
        raise ValueError(f"prompt + num_tokens exceeds the artifact's "
                         f"seq_len={seq_len}")
    if np.any(lens < 1) or np.any(want < 1):
        raise ValueError("empty prompt or non-positive num_tokens")
    toks = np.zeros((B, seq_len), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    done = np.zeros(B, bool)
    rows = np.arange(B)
    for step in range(int(want.max())):
        logits = call(toks)                        # [B, S, V] on device
        # Each row's predictor position; rows whose budget is spent keep
        # stepping with the rest of the batch, so clamp their (discarded)
        # reads inside the sequence.  Index on DEVICE first: only the
        # [B, V] frontier rows cross the host-transfer boundary, not the
        # whole [B, S, V] tensor.
        frontier = np.minimum(lens + step - 1, seq_len - 1)
        nxt = np.argmax(np.asarray(logits[rows, frontier]), axis=-1)
        exhausted = step >= want
        if eos_id is not None:
            nxt = np.where(done, eos_id, nxt)
        keep = ~exhausted
        toks[np.arange(B)[keep], (lens + step)[keep]] = nxt[keep].astype(
            np.int32)
        if eos_id is not None:
            done |= nxt == eos_id
        if np.all(exhausted | (done if eos_id is not None else False)):
            break
    out = []
    for i in range(B):
        row = toks[i, :lens[i] + want[i]].tolist()
        if eos_id is not None and eos_id in row[lens[i]:]:
            row = row[:lens[i] + row[lens[i]:].index(eos_id) + 1]
        out.append(row)
    return out


def decode_batch_cached(cached: dict, prompts: list[list[int]],
                        num_tokens: list[int], eos_id: int | None = None,
                        pad_batch: int | None = None,
                        sampling: dict | None = None) -> list[list[int]]:
    """Greedy decode a micro-batch through the KV-cached exported pair.

    One ``prefill`` call fills the caches from the right-padded prompts,
    then each ``decode`` call generates ``chunk`` tokens per row entirely
    on device (per-row ragged frontiers; junk K/V in a row's pad slots is
    masked/overwritten before it can be attended — see
    ``export_gpt_decode``).  ``pad_batch`` pads the batch with dummy rows
    and prompt lengths to 64-multiples so the jit cache sees a bounded
    shape set instead of compiling per request mix.  Rows that finish
    early keep stepping with the batch; their extra tokens are trimmed
    host-side, and cache writes past capacity are dropped by XLA's
    scatter OOB rule (those rows' outputs are already discarded).
    Returns prompt + generation per row.

    ``sampling`` (r5): ``{"temperature": [..], "top_k": [..],
    "top_p": [..], "seed": int}`` with one entry per row — routed through
    the artifact's sampled-decode blob (per-row traced inputs, so mixed
    configs share one micro-batch; rows with temperature 0 decode
    greedily).  Requires an artifact exported with the ``decode_sample``
    blob.
    """
    capacity, chunk = cached["capacity"], cached["chunk"]
    B = len(prompts)
    lens = np.asarray([len(p) for p in prompts])
    want = np.asarray(num_tokens)
    if np.any(lens + want > capacity):
        raise ValueError(f"prompt + num_tokens exceeds the artifact's "
                         f"seq_len={capacity}")
    if np.any(lens < 1) or np.any(want < 1):
        raise ValueError("empty prompt or non-positive num_tokens")
    Bp = max(B, pad_batch or 0)
    Ppad = min(capacity, ((int(lens.max()) + 63) // 64) * 64)
    toks = np.zeros((Bp, Ppad), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    if cached.get("window"):
        # Ring-cache pair: prefill needs each row's true length so pad
        # K/V never enters the ring (batch-pad dummy rows count as
        # length-1 prompts of token 0 — consistent with their frontier
        # below).
        lengths = np.ones((Bp,), np.int32)
        lengths[:B] = lens
        caches = cached["prefill"](toks, lengths)
    else:
        caches = cached["prefill"](toks)
    frontier = np.zeros((Bp,), np.int32)
    positions = np.zeros((Bp,), np.int32)
    for i, p in enumerate(prompts):
        frontier[i] = p[-1]
        positions[i] = len(p) - 1
    eos = np.int32(-1 if eos_id is None else eos_id)
    tok_dev, pos_dev = frontier, positions
    done = np.zeros((Bp,), bool)  # rows that emitted eos in a prior call
    if sampling is not None:
        if "decode_sample" not in cached:
            raise ValueError("artifact has no sampled-decode blob; "
                             "re-export or use greedy decode")
        temp = np.zeros((Bp,), np.float32)
        tk = np.zeros((Bp,), np.int32)
        tp = np.zeros((Bp,), np.float32)
        temp[:B] = sampling["temperature"]
        tk[:B] = sampling["top_k"]
        tp[:B] = sampling["top_p"]
        seed = np.int32(sampling.get("seed", 0))

        def decode_call(tok, pos, eos, done, caches):
            return cached["decode_sample"](tok, pos, eos, done, caches,
                                           seed, temp, tk, tp)
    else:
        decode_call = cached["decode"]
    outs: list = []
    produced = 0
    for _ in range(-(-int(want.max()) // chunk)):
        out, caches = decode_call(tok_dev, pos_dev, eos, done, caches)
        produced += chunk
        tok_dev, pos_dev = out[:, -1], pos_dev + chunk
        if eos_id is None:
            # No early-exit condition to check: keep the chunks on device
            # and fetch ONCE below — a host sync per chunk would serialize
            # the decode on the host/link round trip.
            outs.append(out)
            continue
        out_np = np.asarray(out)
        outs.append(out_np[:B])
        done[:B] |= (out_np[:B] == eos_id).any(axis=1)
        if all(done[i] or produced >= want[i] for i in range(B)):
            break
    gen = np.concatenate([np.asarray(o)[:B] for o in outs], axis=1)
    out_rows = []
    for i in range(B):
        row = list(prompts[i]) + gen[i, :want[i]].tolist()
        tail = row[lens[i]:]
        if eos_id is not None and eos_id in tail:
            row = row[:lens[i] + tail.index(eos_id) + 1]
        out_rows.append(row)
    return out_rows


class _Request:
    def __init__(self, prompt, num_tokens, eos_id, sampling=None):
        self.prompt = prompt
        self.num_tokens = num_tokens
        self.eos_id = eos_id
        #: None (greedy) or {"temperature", "top_k", "top_p", "seed"}
        self.sampling = sampling
        self.event = threading.Event()
        self.result: list[int] | None = None
        self.error: str | None = None
        self.abandoned = False   # caller timed out; don't decode for it

    @property
    def group_key(self):
        """Requests sharing a device call: same eos semantics, and —
        for sampled requests — the same seed (the seed is a scalar
        input; per-row temperature/top-k/top-p mix freely)."""
        return (self.eos_id,
                self.sampling.get("seed", 0) if self.sampling else None)


class Batcher:
    """Gather concurrent /generate requests into one device call.

    Blocks for the first request, then keeps gathering until ``max_batch``
    or ``wait_ms`` elapses — the standard latency/throughput knob.  Mixed
    eos_ids split into sub-batches (the mask semantics differ per id).

    ``decode_fn(prompts, num_tokens, eos_id) -> rows`` is whichever decode
    path the artifact supports (KV-cached pair or forward fallback).
    """

    def __init__(self, decode_fn, max_batch: int = 8,
                 wait_ms: float = 5.0, request_timeout_s: float = 60.0):
        self._decode_fn = decode_fn
        self._max_batch = max_batch
        self._wait_s = wait_ms / 1e3
        self.request_timeout_s = request_timeout_s
        self._q: queue.Queue[_Request] = queue.Queue()
        self.batch_sizes: list[int] = []   # served batch sizes (stats)
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit(self, prompt, num_tokens, eos_id, sampling=None):
        req = _Request(prompt, num_tokens, eos_id, sampling)
        self._q.put(req)
        if not req.event.wait(self.request_timeout_s):
            req.abandoned = True  # server overloaded: don't decode for us
            raise TimeoutError(
                f"decode queue exceeded {self.request_timeout_s:.0f}s")
        if req.error:
            raise ValueError(req.error)
        return req.result

    def _loop(self):
        while True:
            batch = [self._q.get()]
            deadline = time.monotonic() + self._wait_s
            while len(batch) < self._max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    batch.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
            batch = [r for r in batch if not r.abandoned]
            for key in {r.group_key for r in batch}:
                group = [r for r in batch if r.group_key == key]
                self._serve(group, key[0])

    def _serve(self, group, eos):
        self.batch_sizes.append(len(group))
        sampling = None
        if group[0].sampling is not None:
            # One seed per group (the group key); per-row configs.
            sampling = {
                "temperature": [r.sampling["temperature"] for r in group],
                "top_k": [r.sampling["top_k"] for r in group],
                "top_p": [r.sampling["top_p"] for r in group],
                "seed": group[0].sampling["seed"],
            }
        try:
            outs = self._decode_fn([r.prompt for r in group],
                                   [r.num_tokens for r in group], eos,
                                   sampling)
            for r, o in zip(group, outs):
                r.result = o
        except Exception as e:                     # surface to every caller
            for r in group:
                r.error = f"{type(e).__name__}: {e}"
        for r in group:
            r.event.set()


def make_server(artifact: str, port: int = 8600, max_batch: int = 8,
                wait_ms: float = 5.0,
                request_timeout_s: float = 60.0) -> ThreadingHTTPServer:
    """Build (not start) the HTTP server; ``.serve_forever()`` to run.
    Exposed separately so tests can drive it in-process."""
    exported, meta, cached = load_artifact(artifact)
    call = exported.call
    is_lm = meta.get("model") == "gpt_mini"
    seq_len = None
    if is_lm:
        seq_len = int(meta["inputs"][0]["shape"][-1])
        if cached is not None:
            def decode_fn(prompts, wants, eos, sampling=None, _c=cached,
                          _mb=max_batch):
                return decode_batch_cached(_c, prompts, wants, eos_id=eos,
                                           pad_batch=_mb,
                                           sampling=sampling)
        else:
            def decode_fn(prompts, wants, eos, sampling=None, _call=call,
                          _s=seq_len):
                if sampling is not None:
                    raise ValueError(
                        "sampling needs the KV-cached decode set; this "
                        "artifact serves the greedy forward fallback only")
                return decode_batch(_call, prompts, wants, _s, eos_id=eos)
        batcher = Batcher(decode_fn, max_batch=max_batch,
                          wait_ms=wait_ms,
                          request_timeout_s=request_timeout_s)
        meta = dict(meta,
                    serving_decode_path=("kv_cache" if cached is not None
                                         else "forward"))
    else:
        batcher = None

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 keep-alive: every reply carries Content-Length, so the
        # connection survives across requests — a real slice of the r4
        # serving overhead was per-request TCP setup/teardown.
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):               # quiet server
            pass

        def _reply(self, code: int, payload: dict):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                self._reply(200, {"status": "ok", **meta})
            else:
                self._reply(404, {"error": "unknown path"})

        def do_POST(self):
            try:
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
            except ValueError:
                return self._reply(400, {"error": "bad json"})
            try:
                if self.path == "/generate":
                    if batcher is None:
                        return self._reply(
                            400, {"error": f"artifact serves "
                                           f"{meta.get('model')}, not an "
                                           "LM; use /predict"})
                    sampling = None
                    temp = float(body.get("temperature", 0.0))
                    if temp > 0.0:
                        sampling = {
                            "temperature": temp,
                            "top_k": int(body.get("top_k", 0)),
                            "top_p": float(body.get("top_p", 0.0)),
                            "seed": int(body.get("seed", 0)),
                        }
                        if not 0.0 <= sampling["top_p"] <= 1.0:
                            return self._reply(
                                400, {"error": "top_p must be in [0, 1]"})
                    elif any(k in body for k in ("top_k", "top_p", "seed")):
                        # Don't silently decode greedily when the caller
                        # clearly asked for sampling.
                        return self._reply(
                            400, {"error": "top_k/top_p/seed require "
                                           "temperature > 0"})
                    toks = batcher.submit(
                        [int(t) for t in body["prompt"]],
                        int(body.get("num_tokens", 16)),
                        (int(body["eos_id"]) if "eos_id" in body else None),
                        sampling)
                    return self._reply(200, {"tokens": toks})
                if self.path == "/predict":
                    args = [np.asarray(a, dtype=s["dtype"]) for a, s in
                            zip([body["inputs"]] + body.get("extra", []),
                                meta["inputs"])]
                    out = np.asarray(call(*args))
                    return self._reply(200, {"outputs": out.tolist()})
                return self._reply(404, {"error": "unknown path"})
            except (KeyError, TypeError):
                return self._reply(400, {"error": "malformed request"})
            except TimeoutError as e:
                # Overload, not a caller mistake.
                return self._reply(503, {"error": str(e)})
            except ValueError as e:
                return self._reply(400, {"error": str(e)})

    server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    server.batcher = batcher                       # test/observability hook
    server.meta = meta
    return server


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--artifact", required=True)
    parser.add_argument("--port", type=int, default=8600)
    parser.add_argument("--max_batch", type=int, default=8)
    parser.add_argument("--batch_wait_ms", type=float, default=5.0)
    parser.add_argument("--request_timeout_s", type=float, default=60.0,
                        help="503 a /generate caller whose request waits "
                             "longer than this (overload signal)")
    parser.add_argument("--platform", default="",
                        help="jax platform override (e.g. cpu)")
    args = parser.parse_args(argv)
    from distributed_tensorflow_tpu.utils.backend import configure_backend
    configure_backend(args.platform)
    server = make_server(args.artifact, port=args.port,
                         max_batch=args.max_batch,
                         wait_ms=args.batch_wait_ms,
                         request_timeout_s=args.request_timeout_s)
    model = server.meta.get("model")
    path_note = server.meta.get("serving_decode_path")
    print(f"serving {model} from {args.artifact} "
          f"on :{server.server_address[1]} "
          f"(micro-batch up to {args.max_batch}, {args.batch_wait_ms}ms "
          "gather window"
          + (f", decode path: {path_note}" if path_note else "") + ")")
    server.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
