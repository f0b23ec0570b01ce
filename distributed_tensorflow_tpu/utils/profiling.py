"""Tracing / profiling (SURVEY §5) — the JAX-profiler equivalent of the
reference's (absent) tracing story.

The reference's nearest artifacts are a plumbed-but-off
``log_device_placement`` flag and coarse wall-clock timing (reference
``distributed.py:115,133,158-161``).  The TPU-idiomatic replacements:

- :func:`trace` — capture an XLA/TPU profile (TensorBoard-loadable) around a
  code region via ``jax.profiler``;
- :func:`annotate` — name a host-side region so it shows up on the trace
  timeline (no-op overhead when no trace is active) and, with a tracer
  installed, in the telemetry stream; the serving engine's turn is marked
  with it (docs/observability.md, "Serving tracing & SLOs");
- :func:`region` — the same for the DEVICE: a ``jax.named_scope`` whose name
  is one of :data:`REGIONS`, so that every operation the enclosed code
  traces carries the name into the compiled program and from there into a
  profile (docs/observability.md, "Device regions");
- :class:`Timer` — the reference's ``time_begin``/``time_end`` pattern
  (``distributed.py:133,158``) as a context manager;
- :func:`device_memory_stats` — per-device HBM usage snapshot, the "is my
  sharding actually fitting" check.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Any, Iterator

import jax

from . import tracing


@contextlib.contextmanager
def trace(logdir: str | os.PathLike) -> Iterator[None]:
    """Capture a JAX/XLA profile of the enclosed region into ``logdir``.

    View with TensorBoard's profile plugin or Perfetto.  Wraps
    ``jax.profiler.trace``; creates ``logdir`` if needed.
    """
    logdir = os.fspath(logdir)
    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        yield


class _Annotation:
    """The :func:`annotate` region: a ``jax.profiler.TraceAnnotation`` on
    the profiler's clock (recorded only while a profiler session runs, so
    the region shares a timeline with the device operations) plus, when a
    :mod:`.tracing` tracer is installed, a ``kind="span"`` record.  While
    the region is open its span id sits on the tracer's per-thread stack,
    so regions nest: an inner region (and any ``emit_span`` that names no
    parent) records the enclosing region as its ``parent_id``."""

    __slots__ = ("_name", "_stats", "_jax_annotation", "_span")

    def __init__(self, name: str, **stats):
        self._name = name
        self._stats = stats
        self._jax_annotation = jax.profiler.TraceAnnotation(name, **stats)
        self._span = None

    def __enter__(self) -> "_Annotation":
        self._jax_annotation.__enter__()
        tracer = tracing.active()
        if tracer is not None:
            self._span = tracer.span(self._name, source="annotate",
                                     **self._stats)
            self._span.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._jax_annotation.__exit__(*exc)
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(*exc)


def annotate(name: str, **stats):
    """Named host-side region: the program's one way to mark one.  On the
    profiler timeline while a profiler session runs; a ``kind="span"``
    record, nested under the thread's open regions, while a
    :mod:`.tracing` tracer is installed; with neither, one inactive
    ``TraceAnnotation`` and one ``is None`` check.  ``stats`` (counters
    known when the region opens) ride on the profiler event as its stats
    and on the span record as attributes."""
    return _Annotation(name, **stats)


#: The device regions' vocabulary: WHAT is computed, never a method's name
#: or a layer's index, so that a name survives a refactor.  Regions nest
#: and an operation belongs to the innermost.  ``perfbench/regions.py``
#: joins a profile's device operations to these names.
REGIONS = (
    "embed",                    # the embedding take
    "attn.qkv",                 # norm-in, projections, rope, the linear
                                # layer's convolution and decay
    "cache.write",              # K/V, latent or state rows written
    "cache.gather",             # the table-wide read of a paged pool
    "attn.scores",              # scores, mask, softmax, weighted sum
    "attn.gate",                # the output gate: its projection of the
                                # mixer's input, the sigmoid, the product
    "attn.out",                 # out projection, post norm, residual add
    "mlp",                      # norm, gate / in / out, residual add
    "head",                     # final norm, the vocabulary projection
    "sample",                   # key folding, argmax or the sorted path
    "loss",                     # log-softmax over the logits
    "optimizer",                # the update rule and its application
    "linear_attention.scan",    # the gated delta rule over a sequence
    "linear_attention.step",    # ... over one token a row
    "short_conv.mix",           # the gated short convolution over a
                                # sequence: B * X, the taps, C *
    "short_conv.step",          # ... over one token a row and its tail
    "mla.expand",               # per-head keys and values from latents
    "mla.absorb",               # scores over the cached latents themselves
    "moe.route", "moe.experts", "moe.shared",
    "loop.step", "loop.exit_gate",
)


class _Region(contextlib.ContextDecorator):
    """The :func:`region` scope: a ``jax.named_scope`` entered anew each
    time, so that one instance can decorate a function that several
    threads trace (``jax.named_scope``'s own object keeps the stack it
    found on itself)."""

    def __init__(self, name: str):
        self._name = name

    def _recreate_cm(self) -> "_Region":
        return _Region(self._name)

    def __enter__(self) -> None:
        self._scope = jax.named_scope(self._name)
        self._scope.__enter__()

    def __exit__(self, *exc) -> None:
        self._scope.__exit__(*exc)


def region(name: str) -> _Region:
    """Named device region: the program's one way to mark one (the twin
    of :func:`annotate`), around a block (``with``) or a whole function
    (``@``).  Metadata only: the compiled program is the same with or
    without it.  A name outside :data:`REGIONS` raises where the region is
    made: add it to the tuple first, and say in docs/observability.md what
    it holds.

    Inside the model's helpers a region is a ``with`` block around the
    body, not a decorator: the wrapper a decorator puts around a module
    method cost the looped cell's warm set-up 6% of its Python tracing
    time on the chip's host, the scope itself nothing (PERF.md, PR 41)."""
    if name not in REGIONS:
        raise ValueError(f"{name!r} is no device region: "
                         f"profiling.REGIONS has {REGIONS}")
    return _Region(name)


class Timer:
    """Wall-clock region timer — ``Training elapsed time`` parity
    (reference ``distributed.py:133,158-161``)."""

    def __init__(self):
        self.elapsed = 0.0
        self._t0: float | None = None

    def __enter__(self) -> "Timer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        # A timer that was never entered (or was already exited) reports
        # zero instead of crashing — __exit__ runs on error paths where a
        # secondary TypeError would mask the real exception.
        if self._t0 is not None:
            self.elapsed = time.perf_counter() - self._t0
            self._t0 = None


def device_memory_stats() -> list[dict[str, Any]]:
    """Per-device memory snapshot:
    ``[{device, bytes_in_use, bytes_limit, peak_bytes_in_use}]``.

    ``peak_bytes_in_use`` is the allocator's high-watermark where the backend
    reports one (TPU), else 0.  Backends without memory_stats report zeros —
    whether ``memory_stats()`` returns None (CPU) or raises (some plugin
    backends) — so observability code runs unchanged in tests.
    """
    out = []
    for dev in jax.devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:
            stats = {}
        out.append({
            "device": str(dev),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        })
    return out
