"""Paged KV-cache page accounting — the serving tier's memory manager.

The engine's device memory for KV caches is ONE pool of fixed-size pages
per layer (``models/gpt.init_kv_pool``); every resident sequence draws
pages from it, so HBM is sized by *total resident tokens*, not by
``num_slots × max_len`` — the vLLM insight at the granularity this repo
needs.  This module owns the page bookkeeping on the host:

- :class:`PageAllocator` — free-list allocator with per-sequence page
  lists.  Allocation order is deterministic: never-used pages first
  (lowest index), then freed pages in FIFO order (oldest-freed reused
  first), so tests can pin the reuse/eviction order exactly.
- Reservations are worst-case at admission (``ceil((prompt + budget) /
  page_size)``): a sequence can never hit an out-of-pages condition
  mid-decode, so admission control is the ONLY backpressure point and
  in-flight streams never need mid-stream eviction.
- Internal fragmentation (the cost of fixed pages: the tail of the last
  page is reserved but may go unwritten) is reported per pool snapshot —
  the occupancy view the telemetry bus publishes every engine step.

- A sequence's OTHER memory is accounted here too: a model with
  linear-attention or short-convolution layers keeps, per resident
  sequence, one fixed-size row per such layer (a recurrent state with its
  convolution tail, or a tail alone; ``state_bytes_per_slot`` in all),
  indexed by the engine's slot and not by page.  A sequence holds its row
  exactly as long as it holds pages, so the snapshot reports both.

- Pages of a SECOND kind, for a model with sliding-window layers
  (``GptConfig.layer_kinds``): such a layer's pool is small and of its own
  geometry, and a sequence holds of it a fixed RING of at most
  ``ring_pages`` pages (the window and a page more) however long it grows,
  beside the run of pages that grows with it in the full layers' pools.
  The two kinds are allocated, extended and freed together, one call for
  both, and counted apart: two free lists, two high-water marks, two
  tables a sequence (:meth:`PageAllocator.page_table`,
  :meth:`PageAllocator.window_table`); admission needs room in both.

Device tensors never live here: the allocator hands out page indices and
sentinel-padded page tables; :mod:`.engine` owns the arrays.
"""

from __future__ import annotations

import collections
import threading
from typing import Iterable

import numpy as np


class OutOfPages(RuntimeError):
    """The pool cannot cover a reservation — admission must wait/reject."""


class PageAllocator:
    """Host-side page bookkeeping for one paged KV pool.

    ``num_pages`` physical pages of ``page_size`` token slots each.  The
    sentinel index for "no page" in emitted page tables is ``num_pages``
    itself — one past this allocator's range, and in the engine's pools
    (``models/gpt.init_kv_pool``) a page of its own: all zeros, never
    handed out here and never written, so a gather through the sentinel
    is in bounds and reads zeros, and a sequence reads no page it does
    not own.  A write through it is sent past the pool and drops
    (``gpt_lib.written_pages``).  Capacity, admission and occupancy count
    ``num_pages``; a freed page keeps what its last owner wrote, under a
    weight of zero until its next owner overwrites it.

    ``state_bytes_per_slot``: bytes a resident sequence holds in rows of
    its own beside its pages, over all the model's layers that keep one
    (a linear-attention layer's recurrent state and convolution tail, a
    short-convolution layer's tail alone); 0 for a model that has none.
    ``row_bytes_per_token``: bytes one cached token holds over all layers'
    pools (keys and values a head, or one latent row; a row a LOOP STEP a
    layer where the stack is walked several times over the same weights,
    whose pools hold that many runs of ``num_pages`` pages under the one
    page table this allocator hands out); reported, never used to decide
    anything.

    ``window_pages`` / ``ring_pages``: the pages of the sliding-window
    layers' pools (one count for all of them: they share a geometry and a
    table) and the most of them a sequence holds, its ring; 0 for a model
    without such layers, and then nothing below differs from a
    one-kind allocator.  The sentinel of a window table is
    ``window_pages``, that pool's own page of zeros.
    ``window_row_bytes_per_token``: bytes a token inside the window holds
    over those layers; reported only.
    """

    def __init__(self, num_pages: int, page_size: int,
                 state_bytes_per_slot: int = 0,
                 row_bytes_per_token: int = 0, window_pages: int = 0,
                 ring_pages: int = 0, window_row_bytes_per_token: int = 0):
        if num_pages < 1 or page_size < 1:
            raise ValueError(f"need positive pool geometry, got "
                             f"{num_pages} pages x {page_size} slots")
        if window_pages < 0 or bool(window_pages) != bool(ring_pages) \
                or ring_pages > window_pages:
            raise ValueError(
                f"a window pool needs 1 <= ring_pages <= window_pages, got "
                f"{ring_pages} and {window_pages}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.state_bytes_per_slot = int(state_bytes_per_slot)
        self.row_bytes_per_token = int(row_bytes_per_token)
        self.window_pages = int(window_pages)
        self.ring_pages = int(ring_pages)
        self.window_row_bytes_per_token = int(window_row_bytes_per_token)
        # The second kind's free list and owners, in the first's discipline.
        self._window_free: collections.deque[int] = collections.deque(
            range(window_pages))
        self._window_owned: dict[object, list[int]] = {}
        self._window_peak_in_use = 0
        self._peak_sequences = 0
        # Never-used pages dispense lowest-first; freed pages append to the
        # right and are reused oldest-freed-first once the fresh run is
        # exhausted (deterministic, testable reuse order).
        self._free: collections.deque[int] = collections.deque(
            range(num_pages))
        self._owned: dict[object, list[int]] = {}
        self._reserved_tokens: dict[object, int] = {}
        self._peak_in_use = 0      # occupancy high-water mark
        # The engine thread is the only mutator, but statz/healthz handler
        # threads read snapshot() concurrently — iterating
        # _reserved_tokens while free() pops a key is a RuntimeError.
        self._lock = threading.Lock()

    # ------------------------------------------------------------ state

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def peak_in_use(self) -> int:
        """High-water mark of :attr:`pages_in_use` since construction."""
        return self._peak_in_use

    @property
    def window_pages_in_use(self) -> int:
        return self.window_pages - len(self._window_free)

    @property
    def window_peak_in_use(self) -> int:
        """High-water mark of :attr:`window_pages_in_use`."""
        return self._window_peak_in_use

    @property
    def sequences(self) -> int:
        return len(self._owned)

    @property
    def state_slots(self) -> int:
        """Resident sequences holding a state row (a recurrent state, a
        convolution tail) beside their pages."""
        return len(self._owned) if self.state_bytes_per_slot else 0

    @property
    def state_bytes(self) -> int:
        return self.state_slots * self.state_bytes_per_slot

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` token slots."""
        return -(-int(tokens) // self.page_size)

    def window_pages_for(self, tokens: int) -> int:
        """Pages of the window pool a sequence of ``tokens`` token slots
        holds: its run until that is a whole ring, then the ring."""
        return min(self.pages_for(tokens), self.ring_pages)

    def utilization(self) -> float:
        """Fraction of the pool's pages currently reserved."""
        return self.pages_in_use / self.num_pages

    def internal_fragmentation(self) -> float:
        """Reserved-but-unrequested token slots / reserved slots — the
        fixed-page tax (0.0 when every reservation fills its last page,
        or when nothing is reserved)."""
        with self._lock:
            return self._fragmentation_locked()

    def _fragmentation_locked(self) -> float:
        reserved_slots = self.pages_in_use * self.page_size
        if not reserved_slots:
            return 0.0
        requested = sum(self._reserved_tokens.values())
        return (reserved_slots - requested) / reserved_slots

    def owned(self, seq_id) -> list[int]:
        """The sequence's pages in logical order (copy)."""
        return list(self._owned.get(seq_id, ()))

    # ------------------------------------------------------ alloc / free

    def can_alloc(self, tokens: int) -> bool:
        return (self.pages_for(tokens) <= len(self._free)
                and self.window_pages_for(tokens) <= len(self._window_free))

    def alloc(self, seq_id, tokens: int) -> list[int]:
        """Reserve pages covering ``tokens`` token slots for ``seq_id``.

        Raises :class:`OutOfPages` without partial allocation when the
        pool cannot cover it, ``ValueError`` on double-alloc.
        """
        with self._lock:
            if seq_id in self._owned:
                raise ValueError(f"sequence {seq_id!r} already holds "
                                 "pages; use extend()")
            need = self.pages_for(tokens)
            if need > len(self._free):
                raise OutOfPages(
                    f"need {need} page(s) for {tokens} tokens, "
                    f"{len(self._free)} free of {self.num_pages}")
            ring = self.window_pages_for(tokens)
            if ring > len(self._window_free):
                raise OutOfPages(
                    f"need {ring} window page(s) for {tokens} tokens, "
                    f"{len(self._window_free)} free of {self.window_pages}")
            pages = [self._free.popleft() for _ in range(need)]
            self._owned[seq_id] = pages
            self._window_owned[seq_id] = [
                self._window_free.popleft() for _ in range(ring)]
            self._reserved_tokens[seq_id] = int(tokens)
            self._peak_in_use = max(self._peak_in_use, self.pages_in_use)
            self._window_peak_in_use = max(self._window_peak_in_use,
                                           self.window_pages_in_use)
            self._peak_sequences = max(self._peak_sequences,
                                       len(self._owned))
            return list(pages)

    def extend(self, seq_id, tokens: int) -> list[int]:
        """Grow ``seq_id``'s reservation to cover ``tokens`` total token
        slots; returns the newly added pages (possibly empty).  Raises
        :class:`OutOfPages` leaving the existing reservation intact."""
        with self._lock:
            if seq_id not in self._owned:
                raise ValueError(f"sequence {seq_id!r} holds no pages")
            have = self._owned[seq_id]
            need = self.pages_for(tokens) - len(have)
            if need <= 0:
                self._reserved_tokens[seq_id] = max(
                    self._reserved_tokens[seq_id], int(tokens))
                return []
            ring = self._window_owned[seq_id]
            more = self.window_pages_for(tokens) - len(ring)
            if need > len(self._free) or more > len(self._window_free):
                raise OutOfPages(
                    f"extend needs {need} page(s) and {more} window "
                    f"page(s), {len(self._free)} and "
                    f"{len(self._window_free)} free")
            fresh = [self._free.popleft() for _ in range(need)]
            have.extend(fresh)
            ring.extend(self._window_free.popleft() for _ in range(more))
            self._reserved_tokens[seq_id] = int(tokens)
            self._peak_in_use = max(self._peak_in_use, self.pages_in_use)
            self._window_peak_in_use = max(self._window_peak_in_use,
                                           self.window_pages_in_use)
            return fresh

    def free(self, seq_id) -> int:
        """Return ``seq_id``'s pages to the pool (FIFO reuse order);
        returns how many were freed.  Freeing an unknown id is a no-op
        (retire paths may race a server shutdown)."""
        with self._lock:
            pages = self._owned.pop(seq_id, None)
            self._reserved_tokens.pop(seq_id, None)
            self._window_free.extend(self._window_owned.pop(seq_id, ()))
            if not pages:
                return 0
            self._free.extend(pages)
            return len(pages)

    # ------------------------------------------------------- page tables

    def page_table(self, seq_id, max_pages: int) -> np.ndarray:
        """[max_pages] int32 physical-page row for the engine, padded with
        the sentinel (``num_pages``: the pools' page of zeros)."""
        pages = self._owned.get(seq_id, ())
        if len(pages) > max_pages:
            raise ValueError(
                f"sequence {seq_id!r} holds {len(pages)} pages > "
                f"max_pages={max_pages}")
        row = np.full((max_pages,), self.num_pages, np.int32)
        row[:len(pages)] = pages
        return row

    def window_table(self, seq_id) -> np.ndarray:
        """[ring_pages] int32 row of the sequence's RING in the window
        pool, ring page ``j`` at entry ``j``, padded with that pool's
        sentinel (``window_pages``) where the sequence is shorter than a
        ring."""
        row = np.full((self.ring_pages,), self.window_pages, np.int32)
        pages = self._window_owned.get(seq_id, ())
        row[:len(pages)] = pages
        return row

    @staticmethod
    def empty_table(num_pages: int, max_pages: int) -> np.ndarray:
        """All-sentinel row — an idle slot's page table."""
        return np.full((max_pages,), num_pages, np.int32)

    def snapshot(self) -> dict:
        """Occupancy view for telemetry/statz (handler-thread safe)."""
        with self._lock:
            return {
                "num_pages": self.num_pages,
                "page_size": self.page_size,
                "pages_in_use": self.pages_in_use,
                "peak_in_use": self._peak_in_use,
                "free_pages": self.free_pages,
                "sequences": self.sequences,
                "utilization": round(self.utilization(), 4),
                "internal_fragmentation": round(
                    self._fragmentation_locked(), 4),
                "row_bytes_per_token": self.row_bytes_per_token,
                "state_bytes_per_slot": self.state_bytes_per_slot,
                "state_slots": self.state_slots,
                "state_bytes": self.state_bytes,
                "state_bytes_peak": (self._peak_sequences
                                     * self.state_bytes_per_slot),
                # The sliding-window layers' pool, counted apart (zeros
                # for a model without such layers); everything above is
                # the full layers' pool.
                "window": {
                    "num_pages": self.window_pages,
                    "ring_pages": self.ring_pages,
                    "pages_in_use": self.window_pages_in_use,
                    "peak_in_use": self._window_peak_in_use,
                    "free_pages": len(self._window_free),
                    "row_bytes_per_token": self.window_row_bytes_per_token,
                },
            }


def reservation_tokens(prompt_len: int, num_tokens: int) -> int:
    """Worst-case token slots a request can touch: the prompt plus its
    full generation budget (positions ``0 .. prompt+budget-1``)."""
    return int(prompt_len) + int(num_tokens)
