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
