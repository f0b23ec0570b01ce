"""Continuous-batching decode engine — one resident jitted step, a slot
batch, and a paged KV pool (the serving half of docs/serving.md).

The engine owns a FIXED batch of ``num_slots`` decode lanes and per-layer
paged KV pools (``models/gpt.init_kv_pool``).  Admission and retirement
happen PER STEP, not per batch: a new request prefills into freshly
allocated pages and joins the slot batch while other lanes are mid-decode;
a finished lane frees its pages and the slot the same step it emits eos or
exhausts its budget.  Because the decode step's shapes never depend on
which slots are live (idle lanes ride along with sentinel page tables —
their writes drop, their outputs are ignored), the WHOLE serving lifetime
runs two compiled programs: one prefill per prompt-page-count bucket
(LRU-bounded at ``prefill_cache_cap`` resident programs) and ONE decode
step, resident from the first request to the last.  With
``prefill_chunk >= 1`` the per-bucket prefill programs give way to ONE
resident chunk-prefill program: a long prompt no longer stalls every
live decode lane for a full compile-bucket forward — the prefilling
lane occupies its slot as a masked passenger and advances
``prefill_chunk`` prompt positions per engine step while the other
lanes keep decoding (docs/serving.md, "Chunked prefill").  With
``spec_k >= 2`` a third resident program joins them — a spec_k-wide
``decode_chunk_paged`` verify used whenever at least one active lane
opted into speculation (docs/speculative.md): speculative lanes emit
their accepted draft prefix + bonus token per step, plain lanes ride the
same dispatch and emit exactly their node-0 sample.

Weight handling reuses the inference-side levers already in-tree:
``quantize="int8"`` stores the swap-able tree as per-channel int8
(:mod:`..ops.quant`; dequantized inside the jitted step where XLA fuses it
into the matmuls) and ``kv_dtype="float8"`` keeps the pools in
``float8_e4m3fn`` (upcast on read).  Hot model swap
(:meth:`DecodeEngine.swap_params`) stages a prepared tree off-thread and
the engine adopts it BETWEEN steps: in-flight sequences keep their KV
pages and simply continue under the new weights — no drain, no drop.

Single-threaded by contract: exactly one thread (the server's engine
loop) calls :meth:`admit` / :meth:`step`; :meth:`swap_params` may be
called from any thread.

The pools (and, for linear-attention layers, the recurrent state that
rides in the same tree) are DONATED to all four programs: each takes
``pools`` and returns it, the compiled program aliases every leaf's output
onto its input, and a decode step writes its one row a lane a layer in
place instead of copying the whole pool first.  So ``self.pools`` is
rebound from a call's result in the statement that makes the call, and
nothing may keep the tree it passed in: those arrays are deleted.  JAX
falls back to a copy, silently, when something else still holds a buffer
(a ``np.asarray`` view of a leaf does on the CPU), so every dispatch reads
``is_deleted()`` of one leaf it gave away.  A program that raises after
its dispatch consumed the pools leaves them deleted;
:meth:`DecodeEngine.fail_active` builds them anew.

Sampling is ``gpt_lib.sample_logits_dynamic`` inside the step's one
compiled program.  It sorts the vocabulary only where a lane of the batch
has ``temperature > 0``; a batch of greedy lanes takes an argmax and
nothing else (a ``lax.cond`` on the temperatures the step is handed
anyway, so no second program and no option).

The decode step is dispatched ONE STEP AHEAD of the host's reading of it
(:meth:`DecodeEngine.step`): the one thing step N+1 needs from step N that
the host cannot know beforehand is the sampled token, and it is handed
over on the device, so the host's turn (fetch, retire, complete, schedule,
the next stage) runs while the device works.  Positions, budgets, tables,
temperatures and seeds the host knows without the tokens.  The step
program is the serial one's, and so are the tokens.

What a layer of the model keeps in the pools is the model's to know
(``gpt_lib.KINDS``).  The engine asks it for the pools
(``gpt_lib.init_kv_pool``), for a prefill's caches to be landed on them
(``gpt_lib.land_prefill``) and for a step (``GptLM.decode_paged``, its
output taken apart by ``gpt_lib.unpack_step_output``), and reads what it
must size and feed off one record, ``gpt_lib.pool_geometry``.  Which page
a lane gets is the engine's: a page table's entry where a lane holds no
page is the SENTINEL, ``num_pages`` (the pools' one page past the
allocator's range, all zeros, never written), and where the geometry has
a ring (sliding-window layers) the allocator counts pages of two kinds
apart, admission needs room in both, a lane has a table of each kind and
the step uploads both.

What a step counts (the donation, the sampler's arm, the hand-over, the
tables' and the rings' entries, the lanes, the routing and the loop) is
ONE record a step, ``_Flight.counters``, built at the dispatch and
finished at the landing; the ``serve.step.retire`` event, the
``serve_step`` record and the sums of :meth:`DecodeEngine.stats` are views
of it, made by one function (:func:`_views`).
docs/observability.md lists every name, what it counts and where it goes.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any

import numpy as np

from ..models import gpt as gpt_lib
from ..models.drafting import NGramIndex
from ..ops.pallas.paged_attention import pages_walked
from ..ops.quant import (load_inference_tree, prepare_inference_tree,
                         resolve_kv_dtype, validate_quantize)
from ..utils import profiling, tracing
from .kv_pool import PageAllocator, reservation_tokens
from .scheduler import Request


def _unix_at(perf_t: float) -> float:
    """Map a ``perf_counter`` stamp onto the epoch clock (spans carry
    ``t_unix`` so the exporter can align them across hosts).  A
    ``time.monotonic`` stamp maps the same way: off Windows CPython reads
    one clock (``CLOCK_MONOTONIC``) for both."""
    return time.time() - (time.perf_counter() - perf_t)


def _ensure_request_trace(tracer, request: Request) -> None:
    """Give the request its trace identity on first tracer contact: a
    pre-allocated root span id (children parent under it live; the root
    ``serve.request`` span is emitted at retirement) and the
    ``"<run_id>/req<id>"`` trace id every span of this request carries."""
    if not request.span_root:
        request.span_root = tracer.allocate_id()
        request.trace = tracer.request_trace_id(request.id)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Decode-engine geometry and weight-path knobs."""

    num_slots: int = 4            # resident decode lanes (batch dim)
    page_size: int = 16           # token slots per KV page
    num_pages: int = 128          # pool pages per layer
    max_pages_per_seq: int = 8    # page-table width (caps seq length)
    quantize: str = ""            # "" | "int8" weight storage
    # With quantize="int8": the engine CONSUMES the float tree it is given
    # (and every tree ``swap_params`` is), deleting each device leaf once
    # its int8 form is made, so that a model of more than two thirds of
    # the device's memory can be quantized on it; the caller's tree is
    # dead afterwards (ops/quant.quantize_tree).
    consume_params: bool = False
    kv_dtype: str = ""            # "" | "bfloat16" | "float8" pool dtype
    # Speculative decode arm (docs/speculative.md): 0 disables; >= 2
    # compiles a second resident step — a spec_k-wide decode_chunk_paged
    # verify — used whenever at least one active lane opted in
    # (Request.speculative).  Per-slot prompt-lookup drafts come from the
    # shared incremental n-gram index (models/drafting.py).
    spec_k: int = 0
    spec_ngram: int = 3
    # Chunked prefill (docs/serving.md, "Chunked prefill"): 0 = legacy
    # whole-bucket prefill at admission (the prompt stalls every live
    # decode lane for one full compile-bucket forward); >= 1 = a
    # prefilling lane occupies its slot and advances `prefill_chunk`
    # prompt tokens per engine step through ONE resident chunk program
    # while the other lanes keep decoding.
    prefill_chunk: int = 0
    # Bound on the per-bucket prefill compile cache (whole-bucket path):
    # adversarial prompt-length mixes otherwise pin one jitted program
    # per page count for the process lifetime.  LRU eviction beyond it.
    prefill_cache_cap: int = 8

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.max_pages_per_seq

    def __post_init__(self):
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        validate_quantize(self.quantize)
        if self.consume_params and self.quantize != "int8":
            raise ValueError(
                "consume_params frees the float leaves that "
                "quantize='int8' has replaced; without it the engine "
                "serves the caller's own tree")
        resolve_kv_dtype(self.kv_dtype)  # validates
        if self.spec_k == 1 or self.spec_k < 0:
            raise ValueError(f"spec_k must be 0 (off) or >= 2, "
                             f"got {self.spec_k}")
        if self.spec_k and self.spec_ngram < 1:
            raise ValueError(f"spec_ngram must be >= 1, "
                             f"got {self.spec_ngram}")
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, "
                             f"got {self.prefill_chunk}")
        if self.prefill_cache_cap < 1:
            raise ValueError(f"prefill_cache_cap must be >= 1, "
                             f"got {self.prefill_cache_cap}")


class _Slot:
    """One live sequence's lane state (host side)."""

    __slots__ = ("request", "prompt_len", "budget", "generated",
                 "in_flight", "spec", "history", "hist_len", "index",
                 "table", "prefill_pos", "prefill_target", "prefill_chunks",
                 "prefill_pages", "t_prefill_start")

    def __init__(self, request: Request, spec_ngram: int = 0):
        self.request = request
        self.prompt_len = len(request.prompt)
        self.budget = request.num_tokens
        self.generated = 0
        # Steps dispatched with this lane live whose output has not
        # landed: 0 or 1, and 2 for a moment inside a call that runs ahead.
        self.in_flight = 0
        # Chunked-prefill bookkeeping: positions [prefill_pos,
        # prefill_target) of the prompt still owe their K/V to the pool.
        # target stays 0 on the whole-bucket path (never prefilling).
        self.table = None            # full page table, np [MP]
        self.prefill_pos = 0
        self.prefill_target = 0
        self.prefill_chunks = 0
        self.prefill_pages = 0
        self.t_prefill_start = 0.0
        # Speculative lanes keep their token history + an incremental
        # n-gram index on the host; drafting is O(ngram + k) per step.
        self.spec = bool(spec_ngram)
        if self.spec:
            self.history = np.zeros(self.prompt_len + self.budget,
                                    np.int32)
            self.history[:self.prompt_len] = request.prompt
            self.hist_len = self.prompt_len
            self.index = NGramIndex(spec_ngram)
            self.index.update(self.history, self.hist_len - 1)
        else:
            self.history = None
            self.hist_len = 0
            self.index = None

    @property
    def prefilling(self) -> bool:
        """Lane seated but its prompt K/V not yet fully resident — it
        rides the decode batch as a masked passenger (sentinel table)
        and advances by chunks instead of emitting tokens."""
        return self.prefill_pos < self.prefill_target

    @property
    def due(self) -> bool:
        """Whether the next dispatch carries this lane: its prompt is
        resident and the steps landed and in flight leave budget over.  A
        count the host keeps without any token (a speculative lane, which
        may land several a step, is only ever asked with none in flight)."""
        return (not self.prefilling
                and self.generated + self.in_flight < self.budget)

    def draft(self, k: int) -> np.ndarray:
        """[k] drafted continuation tokens for the lane's current tail."""
        return self.index.draft(self.history, self.hist_len, k)

    def commit(self, tokens: list[int]) -> None:
        """Fold tokens emitted this step into history + index (the last
        token stays un-indexed so the next tail can't self-match)."""
        n = len(tokens)
        self.history[self.hist_len:self.hist_len + n] = tokens
        self.hist_len += n
        self.index.update(self.history, self.hist_len - 1)


@dataclasses.dataclass(frozen=True)
class _Flight:
    """One decode step from its dispatch to the landing of its output:
    the device arrays to fetch, and what the host knew at the dispatch and
    the step's record says at the landing."""

    out: list                  # [tokens (+ riders)] | [greedy, sampled0]
    lanes: list                # per slot, the _Slot that rode, else None
    ahead: bool                # dispatched before the step before landed
    chunk: Any                 # the speculative arm's [B, K] feed, else None
    t0: float                  # the stage's start, time.monotonic
    queue_depth: int
    # What the step counts, by the names its sinks show (:func:`_views`):
    # the dispatch's here, the landing's added to it there.
    counters: dict
    # Since the dispatch before: admissions, their prompt tokens, and the
    # milliseconds their prefills (and this turn's chunk) took.
    admitted: int
    prompt_tokens: int
    prefill_ms: float
    prefill_rows: int


#: Of a step's counters, the READINGS (a level, a clock): the event and
#: the record say them, no running sum adds them up.
_READINGS = ("state_slots", "state_bytes", "window_pages_in_use",
             "window_pages_peak", "upload_us", "dispatch_us", "spec_rows")
#: The sums :meth:`DecodeEngine.stats` shows flat, and under ``moe`` and
#: ``loop``: every name for every model, 0 where it never counts.
_SUMS = ("pool_steps_in_place", "pool_steps_copied", "steps_ahead",
         "steps_serial", "lane_steps_discarded", "sample_steps_greedy",
         "sample_steps_sampled", "table_pages", "table_pages_held",
         "window_table_pages", "window_table_pages_held", "attn_pages_read",
         "window_attn_pages_read", "attn_kernel_layers", "lanes_live",
         "window_lanes_wrapped")
_MOE = ("experts_touched", "expert_slots", "expert_tokens_max",
        "routed_tokens")
_LOOP = ("loop_steps_run", "loop_tokens", "exit_step_expected_milli")


def _views(counters: dict, stateful: bool) -> tuple[dict, dict, dict]:
    """A landed step's counters as its three sinks take them: the
    ``serve.step.retire`` event's stats (whole numbers; the state rows only
    of a model that has them), the ``serve_step`` record's fields (the
    stage's two parts ride there in milliseconds) and what the running
    sums add (a step is counted once, by which way its donation and its
    sampler went).  The only place a name changes."""
    in_place, sampled = counters["pools_in_place"], counters["sampled_lanes"]
    hidden = ("spec_rows",) if stateful else (
        "spec_rows", "state_slots", "state_bytes")
    event = {k: v for k, v in counters.items() if k not in hidden}
    record = {k: v for k, v in counters.items()
              if k not in ("upload_us", "dispatch_us")}
    record["pools_in_place"] = bool(in_place)
    sums = {k: v for k, v in counters.items() if k not in _READINGS}
    del sums["pools_in_place"], sums["sampled_lanes"]
    sums["pool_steps_in_place" if in_place else "pool_steps_copied"] = 1
    sums["sample_steps_sampled" if sampled else "sample_steps_greedy"] = 1
    return event, record, sums


def _rider_counters(riders: dict) -> dict:
    """A landed step's counters from what rode behind its tokens
    (``gpt_lib.unpack_step_output``); a model without the rider is without
    the names.  Of the routing histogram ([sparse layers x experts], live
    lanes only), over the sparse layers: routed experts that got a token,
    how many there are, the most tokens one expert got in one layer, and
    all the (token, expert) pairs (live lanes x experts a token x layers).
    Of a weight-shared loop's two numbers a lane, over the live lanes: the
    loop steps they ran, how many lanes (tokens) that was, and the sum of
    their expected exit steps in thousandths (a whole number, as a
    profiler event's stats are read)."""
    counted = {}
    if "routing_counts" in riders:
        counts = riders["routing_counts"]
        counted.update(experts_touched=int(np.count_nonzero(counts)),
                       expert_slots=int(counts.size),
                       expert_tokens_max=int(counts.max()),
                       routed_tokens=int(counts.sum()))
    if "loop_steps_run" in riders:
        ran = riders["loop_steps_run"]
        counted.update(
            loop_steps_run=int(ran.sum()),
            loop_tokens=int(np.count_nonzero(ran)),
            exit_step_expected_milli=int(round(
                1e3 * float(riders["exit_step_expected"].sum()))))
    return counted


class DecodeEngine:
    """Slot-batched continuous decoding over a paged KV pool."""

    def __init__(self, model: gpt_lib.GptLM, params: Any,
                 config: EngineConfig | None = None, telemetry=None):
        import jax
        import jax.numpy as jnp

        self._jax, self._jnp = jax, jnp
        self.model = model
        self.config = cfg = config or EngineConfig()
        self.telemetry = telemetry
        mcfg = model.cfg
        if mcfg.attention_window:
            raise ValueError(
                "the paged engine serves a window as a KIND of layer "
                "(GptConfig.layer_kinds 'sliding_attention' with "
                "sliding_window: a ring of pages a lane); "
                "GptConfig.attention_window, one window for all layers, "
                "is the unpaged paths'")
        # Positions must stay addressable by the position table (rope-less
        # checkpoints) — the engine's logical capacity is the tighter of
        # the page-table span and the model's max_position.
        self.capacity = min(cfg.max_seq_len, mcfg.max_position)
        if cfg.spec_k or cfg.prefill_chunk:
            # Neither carries a recurrent state, a ring, a latent row or a
            # routed-expert MLP, nor walks a weight-shared loop; a no-op
            # for any other model.
            on = "spec_k" if cfg.spec_k else "prefill_chunk"
            mcfg.refuse_state_layers(f"DecodeEngine with EngineConfig.{on}")
        self._cache_dtype = resolve_kv_dtype(cfg.kv_dtype)
        # What the pools hold a token, a slot and a ring, the layers by
        # what they keep, and what rides behind the step's tokens: the
        # model's to know, this record's to say.
        self.geometry = geo = gpt_lib.pool_geometry(
            mcfg, cfg.page_size, self._cache_dtype)
        # What a whole-bucket prefill's span says of the pools it filled.
        self._prefill_attrs = {name: getattr(geo, name) for name in (
            "state_layers", "conv_layers", "sparse_layers",
            "latent_row_bytes", "loop_steps", "cache_rows", "row_bytes",
            "window_layers", "ring_pages", "route_ahead_layers")}
        self._tree = self._prepare_params(params)
        self._pending: tuple[Any, int] | None = None  # (tree, label step)
        self.model_step = 0            # checkpoint step the weights carry
        self.swaps = 0
        self.pools = self._fresh_pools()
        # Layers of the step program that attend their pool through the
        # paged-attention kernel: every K/V layer on a TPU under a Pallas
        # configuration, none on a CPU.
        self._kernel_layers = gpt_lib.paged_kernel_layers(mcfg, self.pools)
        self.allocator = PageAllocator(
            cfg.num_pages, cfg.page_size,
            state_bytes_per_slot=geo.state_bytes,
            row_bytes_per_token=geo.row_bytes,
            window_pages=cfg.num_slots * geo.ring_pages,
            ring_pages=geo.ring_pages,
            window_row_bytes_per_token=geo.window_row_bytes)

        B, MP = cfg.num_slots, cfg.max_pages_per_seq
        self._slots: list[_Slot | None] = [None] * B
        self._tokens = np.zeros((B,), np.int32)
        self._positions = np.zeros((B,), np.int32)
        self._tables = np.full((B, MP), cfg.num_pages, np.int32)
        # The lanes' rings in the window layers' pools, that pool's own
        # sentinel where a lane holds no page; no row without such layers.
        self._window_tables = np.full(
            (B, geo.ring_pages), self.allocator.window_pages, np.int32)
        self._temp = np.zeros((B,), np.float32)
        self._top_k = np.zeros((B,), np.int32)
        self._top_p = np.zeros((B,), np.float32)
        self._seeds = np.zeros((B,), np.int32)

        self.step_index = 0
        # The step dispatched and not yet landed, if any (step()).
        self._flight: _Flight | None = None
        self._t_landed = 0.0           # time.monotonic of the last landing
        self._admitted_since_step = 0
        # Since the last step's dispatch: prompt tokens seated, and the
        # milliseconds their whole-bucket prefills took.
        self._prompt_tokens_since_step = 0
        self._prefill_ms_since_step = 0.0
        self._spec_rows_last_step = 0
        # Whether every dispatch since the last step's (the chunk
        # prefill's, the admissions' prefills; then the step's own)
        # consumed the pools it was donated.
        self._pools_in_place = True
        # The landed steps' counters, summed by name (:func:`_views`).
        self._sums: collections.Counter = collections.Counter()
        self._step_fn = self._build_step()
        # The hand-over on the device, a program of its own: a step's
        # tokens are those of the output of the step before (riders or
        # none behind them), but for a lane seated since, which takes the
        # host's seed token (``seeded`` holds it there and -1 elsewhere).
        self._hand_over = jax.jit(lambda out, seeded: jnp.where(
            seeded >= 0, seeded,
            gpt_lib.unpack_step_output(mcfg, out, B)[0]))
        self._spec_step_fn = (self._build_spec_step()
                              if cfg.spec_k else None)
        # Per-bucket prefill programs, LRU-bounded (prefill_cache_cap);
        # the chunk-prefill program is memoized per chunk width (one in
        # practice — the width is an engine constant).
        self._prefill_fns: collections.OrderedDict[int, Any] = \
            collections.OrderedDict()
        self._prefill_evictions = 0
        self._chunk_fns: dict[int, Any] = {}
        # Cumulative milliseconds spent producing prompt K/V (bulk
        # prefill calls + chunk dispatches) — the bench's
        # `prefill_stall_ms` decomposition reads this.
        self.prefill_ms_total = 0.0

    # ------------------------------------------------------------ params

    def _prepare_params(self, params):
        """Host tree -> device-resident serving tree (int8 when asked) —
        the shared prepare/load recipe of ops/quant.py."""
        return self._jax.tree.map(
            self._jnp.asarray,
            prepare_inference_tree(params, self.config.quantize,
                                   self.config.consume_params))

    def _dequant(self, tree):
        return load_inference_tree(tree, self.config.quantize,
                                   self._jnp.dtype(self.model.cfg.dtype))

    def swap_params(self, params, step: int = 0) -> None:
        """Stage new weights for adoption between engine steps.

        Safe from any thread: preparation (quantize + device transfer)
        runs HERE, on the caller; the engine thread's next step just swaps
        a reference.  In-flight sequences keep decoding — their KV pages
        were computed under the old weights, the continuation runs under
        the new (the standard continuous-batching swap semantics;
        docs/serving.md#hot-swap)."""
        prepared = self._prepare_params(params)
        self._pending = (prepared, int(step))

    def apply_pending_swap(self) -> bool:
        """Adopt staged weights (engine thread, between steps)."""
        pending = self._pending
        if pending is None:
            return False
        t0 = time.perf_counter()
        self._pending = None
        tree, step = pending
        self._tree = tree
        prev = self.model_step
        self.model_step = step
        self.swaps += 1
        if self.telemetry is not None:
            self.telemetry.counter("serve_swaps").inc()
            self.telemetry.emit(
                "model_swap", step=self.step_index,
                from_model_step=prev, to_model_step=step,
                in_flight=self.active_slots)
        tracer = tracing.active()
        if tracer is not None:
            # The adoption pause, stamped once at the engine level AND
            # onto every in-flight request's trace: a request whose decode
            # straddled a hot swap shows the pause inside its own span
            # tree, so "this stream hiccuped because a swap landed" needs
            # no cross-referencing.
            dur_ms = (time.perf_counter() - t0) * 1e3
            t_unix = _unix_at(t0)
            swap_id = tracer.emit_span(
                "serve.swap", t_unix, dur_ms, step=self.step_index,
                parent_id=0, from_model_step=prev, to_model_step=step,
                in_flight=self.active_slots)
            for state in self._slots:
                if state is None:
                    continue
                req = state.request
                _ensure_request_trace(tracer, req)
                tracer.emit_span(
                    "serve.swap_pause", t_unix, dur_ms,
                    step=self.step_index,
                    parent_id=req.span_root or swap_id, trace=req.trace,
                    request_id=req.id, tenant=req.tenant,
                    from_model_step=prev, to_model_step=step)
        return True

    # ----------------------------------------------------- jitted bodies

    def _build_step(self):
        jax = self._jax
        model, geo = self.model, self.geometry

        def step(tree, tokens, positions, tables, pools, temp, tk, tp,
                 seeds):
            params = self._dequant(tree)
            # With a ring ``tables`` is the pair (full, rings).
            rings = {}
            if geo.ring_pages:
                tables, rings["window_tables"] = tables
            # An idle lane's table is all sentinel: its page writes drop
            # by themselves, a state row, a router and a loop's counters
            # have to be told.
            live = (tables[:, 0] < self.config.num_pages) \
                if geo.needs_live else None
            sown = {"mutable": list(geo.riders)} if geo.riders else {}
            out = model.apply(
                {"params": params}, tokens, pools, tables, positions, live,
                method=gpt_lib.GptLM.decode_paged, **rings, **sown)
            (logits, pools), aux = out if sown else (out, {})
            # Per-row keys folded on the ABSOLUTE index being generated:
            # a sampled stream is reproducible for its (seed, position)s
            # no matter which other requests shared the batch.
            with profiling.region("sample"):
                keys = jax.vmap(
                    lambda s, p: jax.random.fold_in(jax.random.key(s), p))(
                        seeds, positions + 1)
                nxt = gpt_lib.sample_logits_dynamic(logits, keys, temp, tk,
                                                    tp)
            nxt = gpt_lib.pack_step_output(model.cfg, nxt, aux, live)
            return nxt, pools

        return jax.jit(step, donate_argnames=("pools",))

    def _build_spec_step(self):
        """The speculative arm's resident step: ONE decode_chunk_paged
        verify over the whole slot batch.  Chunk column 0 is each lane's
        current token (so ``logits[:, 0]`` is exactly what the plain step
        computes — non-speculative rows sample from it with identical
        per-row keys and keep token parity); columns 1.. are drafts,
        verified against the greedy argmaxes on device.  Rejected page
        writes stay masked by the per-row frontier until real tokens
        overwrite them."""
        jax, jnp = self._jax, self._jnp
        model = self.model

        def spec_step(tree, chunk, positions, tables, pools, temp, tk, tp,
                      seeds):
            params = self._dequant(tree)
            logits, pools = model.apply(
                {"params": params}, chunk, pools, tables, positions,
                method=gpt_lib.GptLM.decode_chunk_paged)
            with profiling.region("sample"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                keys = jax.vmap(
                    lambda s, p: jax.random.fold_in(jax.random.key(s), p))(
                        seeds, positions + 1)
                sampled0 = gpt_lib.sample_logits_dynamic(
                    logits[:, 0], keys, temp, tk, tp)
            return greedy, sampled0, pools

        return jax.jit(spec_step, donate_argnames=("pools",))

    def _prefill_fn(self, n_pages: int):
        """Jitted prompt prefill writing straight into the pool; one
        compilation per prompt-page-count, LRU-bounded at
        ``prefill_cache_cap`` resident programs (an adversarial mix of
        prompt lengths would otherwise grow one jitted program per page
        count for the process lifetime — the `serve_compile_cache`
        gauge watches the resident count)."""
        fn = self._prefill_fns.get(n_pages)
        if fn is not None:
            self._prefill_fns.move_to_end(n_pages)
            return fn
        jax = self._jax
        model, mcfg = self.model, self.model.cfg
        page = self.config.page_size
        p_len = n_pages * page

        def prefill(tree, tokens, pools, phys, slot=None, absorb=None,
                    ring=None):
            """``slot`` and ``absorb``, for a model with state layers
            (a recurrent state, a short convolution's tail) only: the
            lane's slot and how many tokens its state absorbs.
            The state starts from zeros INSIDE this program and lands on
            the slot's row whole, so nothing of the row's last tenant
            survives.  ``ring``, for a model with window layers only: the
            lane's ring pages that this prompt reaches, in ring order."""
            params = self._dequant(tree)
            caches = gpt_lib.init_kv_cache(
                mcfg, 1, p_len, dtype=self._cache_dtype,
                ring_rows=self.geometry.ring_pages * page)
            lengths = () if absorb is None else (absorb[None],)
            _, caches = model.apply({"params": params}, tokens, caches,
                                    *lengths, method=gpt_lib.GptLM.prefill)
            return gpt_lib.land_prefill(mcfg, caches, pools, page, phys,
                                        slot, ring)

        fn = jax.jit(prefill, donate_argnames=("pools",))
        self._prefill_fns[n_pages] = fn
        while len(self._prefill_fns) > self.config.prefill_cache_cap:
            self._prefill_fns.popitem(last=False)
            self._prefill_evictions += 1
        return fn

    def _chunk_prefill_fn(self, chunk: int):
        """Jitted chunk-prefill program (``GptLM.prefill_chunk_paged``):
        C prompt tokens per prefilling row against the paged pool, no LM
        head.  ONE resident compilation per chunk width for the engine
        lifetime — memoized exactly like :meth:`_prefill_fn` so the
        BENCH_r04 per-call retrace class cannot ride back in through
        this builder (the dtflint jit-hygiene fixture pins this shape)."""
        fn = self._chunk_fns.get(chunk)
        if fn is not None:
            return fn
        jax = self._jax
        model = self.model

        def chunk_prefill(tree, tokens, positions, tables, pools):
            params = self._dequant(tree)
            return model.apply(
                {"params": params}, tokens, pools, tables, positions,
                method=gpt_lib.GptLM.prefill_chunk_paged)

        fn = jax.jit(chunk_prefill, donate_argnames=("pools",))
        self._chunk_fns[chunk] = fn
        return fn

    def _fresh_pools(self):
        cfg = self.config
        return gpt_lib.init_kv_pool(
            self.model.cfg, cfg.num_pages, cfg.page_size,
            dtype=self._cache_dtype, num_slots=cfg.num_slots)

    def _gave_away(self, leaf) -> None:
        """After a dispatch that was donated the pools; ``leaf`` is one
        array of the tree it passed in.  Deleted: the program took the
        buffers and wrote in place.  Alive: something else holds one and
        JAX copied them instead, without a word, so the step's record
        says it."""
        self._pools_in_place &= leaf.is_deleted()

    # -------------------------------------------------------- admission

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def free_slots(self) -> int:
        return self.config.num_slots - self.active_slots

    def validate(self, request: Request) -> None:
        """Reject malformed requests up front (HTTP 400 territory)."""
        vocab = self.model.cfg.vocab_size
        if not request.prompt:
            raise ValueError("empty prompt")
        if any(not 0 <= t < vocab for t in request.prompt):
            raise ValueError(f"prompt token out of range [0, {vocab})")
        if request.num_tokens < 1:
            raise ValueError("num_tokens must be >= 1")
        if request.eos_id is not None and not (
                0 <= request.eos_id < vocab):
            raise ValueError(f"eos_id must be in [0, {vocab})")
        if not 0.0 <= request.top_p <= 1.0:
            raise ValueError("top_p must be in [0, 1]")
        # top_k / seed land in int32 slot arrays — an unbounded value
        # would raise OverflowError inside admit(), which the engine
        # loop's catch-all turns into failing EVERY in-flight stream.
        if not 0 <= request.top_k < 2 ** 31:
            raise ValueError("top_k must be in [0, 2**31)")
        if not 0 <= request.seed < 2 ** 31:
            raise ValueError("seed must be in [0, 2**31)")
        if request.speculative and request.temperature > 0.0:
            raise ValueError(
                "speculative decoding is greedy-only (acceptance compares "
                "against argmax); drop temperature or the speculative flag")
        total = len(request.prompt) + request.num_tokens
        if total > self.capacity:
            raise ValueError(
                f"prompt + num_tokens = {total} exceeds the engine "
                f"capacity {self.capacity} (pages x page_size, capped by "
                f"the model's max_position)")
        # A worst-case reservation larger than the whole pool would pass
        # the capacity check on small pools yet never become admissible —
        # the request would pin its tenant's queue head until timeout.
        need = self.allocator.pages_for(
            reservation_tokens(len(request.prompt), request.num_tokens))
        if need > self.config.num_pages:
            raise ValueError(
                f"request reserves {need} KV page(s) worst-case but the "
                f"pool only has {self.config.num_pages}")

    def can_admit(self, request: Request) -> bool:
        """Slot and KV pages available right now (the scheduler's
        admissibility predicate; assumes :meth:`validate` passed)."""
        if self.free_slots < 1:
            return False
        return self.allocator.can_alloc(
            reservation_tokens(len(request.prompt), request.num_tokens))

    def admit(self, request: Request) -> int:
        """Prefill the prompt into fresh pages and seat the request.

        The first GENERATED token comes from the next :meth:`step` — the
        lane is seeded with the last prompt token at position P-1, so the
        resident decode step produces token P like any other step (one
        program for every token)."""
        with profiling.annotate("serve.admit"):
            return self._admit(request)

    def _admit(self, request: Request) -> int:
        cfg = self.config
        if self._flight is not None:
            # A step in flight (an arrival nobody saw coming) is waited
            # for, not landed: the prefill's time below is then the
            # prefill's own, and the step's tokens are still the next
            # call's to hand back.
            self._jax.block_until_ready(self._flight.out)
        slot = next(i for i, s in enumerate(self._slots) if s is None)
        P = len(request.prompt)
        tracer = tracing.active()
        if tracer is not None:
            _ensure_request_trace(tracer, request)
        t_res = time.perf_counter()
        pages = self.allocator.alloc(
            request.id, reservation_tokens(P, request.num_tokens))
        t_pre = time.perf_counter()
        if tracer is not None:
            tracer.emit_span(
                "serve.reserve", _unix_at(t_res), (t_pre - t_res) * 1e3,
                step=self.step_index, parent_id=request.span_root,
                trace=request.trace, request_id=request.id,
                tenant=request.tenant, pages=len(pages))
        n_prefill = self.allocator.pages_for(P)
        # The lane's ring in the window layers' pool (empty without such).
        ring_table = self.allocator.window_table(request.id)
        chunked = cfg.prefill_chunk > 0
        if not chunked:
            # Whole-bucket prefill (legacy): one forward over the whole
            # padded prompt bucket, blocking this engine step for its
            # full duration — a never-seen page count pays its fresh
            # bucket compile here too.
            try:
                p_len = n_prefill * cfg.page_size
                toks = np.zeros((1, p_len), np.int32)
                toks[0, :P] = request.prompt
                phys = np.asarray(pages[:n_prefill], np.int32)
                # The first decode step processes token P-1 AGAIN at
                # position P-1.  Writing its K/V twice is idempotent;
                # absorbing it twice into a recurrent state is not, so the
                # lane seats with the state after tokens 0..P-2.
                geo = self.geometry
                seat = (np.int32(slot), np.int32(P - 1)) \
                    if geo.state_layers else ()
                ring = {"ring": self._jnp.asarray(
                    ring_table[:n_prefill])} if geo.ring_pages else {}
                given = self.pools[0][0]
                self.pools = self._prefill_fn(n_prefill)(
                    self._tree, self._jnp.asarray(toks), self.pools,
                    self._jnp.asarray(phys), *seat, **ring)
                self._gave_away(given)
            except Exception:
                self.allocator.free(request.id)
                raise
            # Block before timing, like _advance_prefill: on an async
            # backend the call above returns at dispatch and the
            # prefill's device time would otherwise be absorbed into the
            # next decode step — the stall decomposition (and the
            # serve.prefill span) must record device time on both paths.
            self._jax.block_until_ready(self.pools)
            prefill_ms = (time.perf_counter() - t_pre) * 1e3
            self.prefill_ms_total += prefill_ms
            self._prefill_ms_since_step += prefill_ms
            if tracer is not None:
                # chunks=1: the whole bucket landed in one dispatch —
                # the chunked path's spans count theirs instead.
                tracer.emit_span(
                    "serve.prefill", _unix_at(t_pre), prefill_ms,
                    step=self.step_index, parent_id=request.span_root,
                    trace=request.trace, request_id=request.id,
                    tenant=request.tenant, bucket=n_prefill,
                    pages=n_prefill, prompt_tokens=P, chunks=1,
                    **self._prefill_attrs)
        spec = bool(cfg.spec_k) and request.speculative
        state = _Slot(request, cfg.spec_ngram if spec else 0)
        state.table = self.allocator.page_table(request.id,
                                                cfg.max_pages_per_seq)
        state.prefill_pages = n_prefill
        self._slots[slot] = state
        if chunked and P > 1:
            # The lane seats in PREFILLING state: its row keeps the
            # sentinel page table (decode-batch writes drop, outputs
            # ignored — exactly an idle lane) while step() advances the
            # prompt `prefill_chunk` positions per engine step.  Only
            # positions [0, P-1) owe K/V — the decode step writes P-1
            # itself, same as the whole-bucket seed.
            state.prefill_target = P - 1
            state.t_prefill_start = t_pre
        else:
            # Whole-bucket path, or a chunked P == 1 prompt: nothing
            # owes K/V (the decode step writes position 0 itself), so
            # the lane goes live immediately — no program runs, no
            # serve.prefill span (nothing prefilled).
            self._tables[slot] = state.table
            self._window_tables[slot] = ring_table
        self._tokens[slot] = request.prompt[-1]
        self._positions[slot] = P - 1
        self._temp[slot] = request.temperature
        self._top_k[slot] = request.top_k
        self._top_p[slot] = request.top_p
        self._seeds[slot] = request.seed
        self._admitted_since_step += 1
        self._prompt_tokens_since_step += P
        request.t_admit = time.perf_counter()
        return slot

    def _idle_row(self, slot: int) -> None:
        """The slot's row as an idle lane rides: the sentinel table, so
        that its writes drop and its state stays, and zeros."""
        self._tables[slot] = self.config.num_pages
        self._window_tables[slot] = self.allocator.window_pages
        self._tokens[slot] = 0
        self._positions[slot] = 0
        self._temp[slot] = 0.0
        self._top_k[slot] = 0
        self._top_p[slot] = 0.0
        self._seeds[slot] = 0

    def _retire(self, slot: int, status: str) -> Request:
        state = self._slots[slot]
        assert state is not None
        req = state.request
        self._slots[slot] = None
        self._idle_row(slot)
        self.allocator.free(req.id)
        req.t_done = time.perf_counter()
        if self.telemetry is not None:
            tel = self.telemetry
            tel.counter("serve_requests").inc()
            tel.counter("serve_tokens_out").inc(len(req.tokens))
            # Global + per-tenant latency distributions: the bracketed
            # name renders as a {tenant=...} label on /metricz and feeds
            # watch_serve's per-tenant percentile columns.
            for name, value in (("serve_ttft_ms", req.ttft_ms),
                                ("serve_tpot_ms", req.tpot_ms),
                                ("serve_e2e_ms", req.e2e_ms)):
                if value is not None:
                    tel.histogram(name).record(value)
                    tel.histogram(f"{name}[{req.tenant}]").record(value)
            extra = {}
            if state.spec and req.spec_rounds:
                extra = {"speculative": True,
                         "spec_rounds": req.spec_rounds,
                         "spec_accepted_per_round": round(
                             len(req.tokens) / req.spec_rounds, 2)}
            tel.emit("serve_request", step=self.step_index,
                     tenant=req.tenant, status=status,
                     prompt_tokens=state.prompt_len,
                     tokens_out=len(req.tokens),
                     queue_ms=req.queue_ms, ttft_ms=req.ttft_ms,
                     tpot_ms=req.tpot_ms, e2e_ms=req.e2e_ms,
                     model_step=self.model_step, **extra)
        tracer = tracing.active()
        if tracer is not None:
            _ensure_request_trace(tracer, req)
            t_done_unix = _unix_at(req.t_done)
            tracer.emit_span(
                "serve.retire", t_done_unix, 0.0, step=self.step_index,
                parent_id=req.span_root, trace=req.trace,
                request_id=req.id, tenant=req.tenant, status=status,
                tokens_out=len(req.tokens))
            # The root span, submit..done: its children (queue wait,
            # reserve, prefill, decode lanes, swap pauses, retire) were
            # emitted live under the pre-allocated id.  When the request
            # arrived with wire trace context (X-DTF-Parent), the root
            # nests under the calling tier's span instead of floating —
            # that is what stitches the engine tree into the cross-tier
            # route.global -> route.cell -> route.fleet chain.
            tracer.emit_span(
                "serve.request", req.t_submit_unix,
                (req.t_done - req.t_submit) * 1e3, step=self.step_index,
                parent_id=req.wire_parent, span_id=req.span_root,
                trace=req.trace,
                request_id=req.id, tenant=req.tenant, status=status,
                tokens_out=len(req.tokens), queue_ms=req.queue_ms,
                ttft_ms=req.ttft_ms, tpot_ms=req.tpot_ms,
                model_step=self.model_step)
        return req

    # ------------------------------------------------------------- step

    def _spec_slots_active(self) -> bool:
        # Prefilling spec lanes don't draft yet — they are masked
        # passengers until their prompt K/V is resident.
        return any(s is not None and s.spec and not s.prefilling
                   for s in self._slots)

    def _advance_prefill(self) -> tuple[float, int]:
        """One chunk-prefill dispatch: every prefilling lane advances up
        to ``prefill_chunk`` prompt positions through the resident chunk
        program; lanes whose frontier reaches P-1 go live (real page
        table installed) and decode from the NEXT dispatch.  Non-
        prefilling rows ride along with sentinel tables — the program's
        shapes never depend on which lanes prefill, so it compiles once.

        Pad columns of a final partial chunk carry token 0 at positions
        >= the target: their junk K/V lands at positions the decode
        lane overwrites before its validity frontier reaches them (the
        same masking argument as rejected speculative writes).

        Returns (elapsed ms, prefilling rows advanced).
        """
        cfg = self.config
        jnp = self._jnp
        C = cfg.prefill_chunk
        B, MP = cfg.num_slots, cfg.max_pages_per_seq
        t0 = time.perf_counter()
        tokens = np.zeros((B, C), np.int32)
        positions = np.zeros((B,), np.int32)
        tables = np.full((B, MP), cfg.num_pages, np.int32)
        rows: list[tuple[int, _Slot, int]] = []
        for slot, state in enumerate(self._slots):
            if (state is None or not state.prefilling
                    or state.request.abandoned):
                continue
            f = state.prefill_pos
            r = min(C, state.prefill_target - f)
            tokens[slot, :r] = state.request.prompt[f:f + r]
            positions[slot] = f
            tables[slot] = state.table
            rows.append((slot, state, r))
        if not rows:
            return 0.0, 0
        given = self.pools[0][0]
        self.pools = self._chunk_prefill_fn(C)(
            self._tree, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables), self.pools)
        self._gave_away(given)
        # Block here so the recorded chunk cost is device time, not
        # dispatch time — the decode step would otherwise absorb it and
        # the prefill_stall_ms decomposition would read zero.
        self._jax.block_until_ready(self.pools)
        dur_ms = (time.perf_counter() - t0) * 1e3
        self.prefill_ms_total += dur_ms
        tracer = tracing.active()
        now = time.perf_counter()
        for slot, state, r in rows:
            state.prefill_pos += r
            state.prefill_chunks += 1
            if state.prefilling:
                continue
            # Frontier reached P-1: install the real table — the lane
            # decodes like any other from the next dispatch on.
            self._tables[slot] = state.table
            req = state.request
            if tracer is not None:
                _ensure_request_trace(tracer, req)
                tracer.emit_span(
                    "serve.prefill", _unix_at(state.t_prefill_start),
                    (now - state.t_prefill_start) * 1e3,
                    step=self.step_index, parent_id=req.span_root,
                    trace=req.trace, request_id=req.id,
                    tenant=req.tenant, bucket=state.prefill_pages,
                    pages=state.prefill_pages,
                    prompt_tokens=state.prompt_len,
                    chunks=state.prefill_chunks, chunk_tokens=C)
        return dur_ms, len(rows)

    def step(self, queue_depth: int = 0) -> list[Request]:
        """One decode step's tokens for every live lane; returns the
        requests retired this call (completed/abandoned).  No-op (after
        adopting a staged swap) when every lane is idle.

        When at least one active lane opted into speculation the step
        runs the CHUNK program instead: speculative lanes feed their
        current token plus ``spec_k - 1`` drafts and may emit several
        tokens (the accepted prefix + the free correction), plain lanes
        ride the same dispatch and emit exactly their node-0 sample —
        token-for-token what the plain step would have produced.

        A call LANDS one step (fetches its output and retires it: every
        lane that rode it gets its token) and, in steady decode,
        DISPATCHES the step after it first: that step takes the landing
        step's output as its tokens on the device (a lane seated since
        takes the host's seed token), so it needs nothing the host has not
        got, and the device finds it queued when it ends the one before.
        A call that finds no step in flight (the first after an idle
        engine, or after a turn that did not run ahead) dispatches the
        step it lands as well.  What a caller may rely on: every call
        hands back exactly one step's tokens, the ones a strictly serial
        loop would give, to every lane that rode that step, so
        ``req.tokens`` of a plain lane grows by one a call: from the first
        call after its :meth:`admit`, or from the second where the
        admission found a step in flight (that call lands the step
        dispatched before the lane sat and dispatches the lane's first).
        Between calls one step may be in flight (:meth:`settle` lands it).
        Not run ahead: a speculative turn (its drafts come from tokens the
        host has committed), an engine with ``prefill_chunk``, a turn
        after which no lane has budget left, and a turn in which
        ``queue_depth > 0`` while a slot is free or frees at a budget in
        the landing step (the waiting request is seated next turn, and a
        step run ahead would only stand in its prefill's way).  A lane
        that reaches its budget rides the step after as an idle row, which
        the host knows without any token.  A lane that samples its
        ``eos_id`` is seen when its step lands, one dispatch late: the
        step already queued carries it once more, into pages it held at
        that dispatch, and that lane-step's token is dropped
        (``lane_steps_discarded``).

        Three regions each marked with :func:`profiling.annotate` under
        ``serve.step``, one of each a call: ``.stage`` (host arrays,
        uploads and the dispatch of what the call dispatches: one step,
        two in a call that dispatches the step it lands and the one
        after, none where only a last step is left to land), ``.fetch``
        (the blocking copy back of the LANDING step's outputs: in steady
        decode the step before the one just dispatched) and ``.retire``
        (the per-slot loop and the telemetry).  A step's boundaries are
        stamped once and feed the ``serve_step`` record and the
        ``serve.decode_round`` span alike.  A step's stage is cut once
        more, at its dispatch, by a stamp and not by child regions (which
        would take their time out of ``serve.step.stage`` for whoever
        reads it): ``upload_ms`` (host arrays, the seven uploads and the
        hand-over) and ``dispatch_ms`` (the call until it returns) add up
        to ``stage_ms``, and ride as ``upload_us`` / ``dispatch_us`` on
        the profiler's ``serve.step.retire`` event of the step they
        staged, beside ``steps_ahead`` / ``steps_serial`` (which of the
        two that step was) and ``lane_steps_discarded``."""
        self.apply_pending_swap()
        if self.active_slots == 0:
            return []
        with profiling.annotate("serve.step"):
            return self._step(queue_depth)

    def settle(self) -> list[Request]:
        """Land the step in flight, if there is one, and return what it
        retired: afterwards the host has every token the device has made.
        For whoever stops calling :meth:`step` (the server's loop on its
        way out)."""
        flight, self._flight = self._flight, None
        if flight is None:
            return []
        with profiling.annotate("serve.step"):
            return self._land(flight)

    def _runs_ahead(self, queue_depth: int) -> bool:
        """After the landing step's dispatch, by what the host can count
        without its tokens: some lane has budget left for one more step,
        and no admission is in sight (a request waits and a slot is free,
        or frees when the landing step retires a lane at its budget)."""
        due = freeing = False
        for state in self._slots:
            if state is None:
                continue
            if state.due:
                due = True
            else:
                freeing = True
        if not due:
            return False
        return not (queue_depth > 0 and (freeing or self.free_slots > 0))

    def _step(self, queue_depth: int) -> list[Request]:
        chunk_ms, prefill_rows = 0.0, 0
        if self.config.prefill_chunk:
            # Prompt chunks first, decode second: a lane whose frontier
            # reaches P-1 in this dispatch gets its real table installed
            # and its seed token rides the decode dispatch BELOW — its
            # first generated token costs no extra step.
            chunk_ms, prefill_rows = self._advance_prefill()
        spec_mode = (self._spec_step_fn is not None
                     and self._spec_slots_active())
        landing = self._flight
        # One stage region a call, around what the call stages: the step
        # it lands where none was in flight, the step after it where the
        # engine runs ahead; both in the first call after an idle engine,
        # neither in a call that has only a last step to land.
        with profiling.annotate("serve.step.stage"):
            if landing is None:
                landing = self._flight = self._dispatch(
                    queue_depth, None, spec_mode, chunk_ms, prefill_rows)
            ahead = None
            if not (spec_mode or self.config.prefill_chunk) \
                    and self._runs_ahead(queue_depth):
                ahead = self._dispatch(queue_depth, landing)
        # From here the landing step is this frame's; what fail_active
        # would find unfetched is the step dispatched ahead.
        self._flight = ahead
        retired = self._land(landing)
        if ahead is not None and self.active_slots == 0:
            # Every lane the step ahead carries left at an eos or was
            # abandoned: nobody would call for it again.
            self._flight = None
            retired += self._land(ahead)
        return retired

    def _dispatch(self, queue_depth: int, after: _Flight | None,
                  spec_mode: bool = False, chunk_ms: float = 0.0,
                  prefill_rows: int = 0) -> _Flight:
        """Stage and dispatch one step over the lanes that are due, inside
        the call's ``serve.step.stage`` region.  With ``after`` (a plain
        step in flight) the tokens are that step's output taken on the
        device, but for a lane that did not ride it, seated since: that one
        takes the host's; without, every lane takes the host's."""
        jnp = self._jnp
        B = self.config.num_slots
        t0 = time.monotonic()
        given = self.pools[0][0]
        lanes = [s if s is not None and s.due else None for s in self._slots]
        page, sentinel = self.config.page_size, self.config.num_pages
        counters = {
            # The predicate the sampler evaluates on the device, read off
            # the host's copy of the same array.
            "sampled_lanes": int(np.count_nonzero(self._temp > 0.0)),
            # What the gather of this dispatch reads: all of the table, of
            # which this many entries are pages and not the sentinel.  And
            # what a read of held pages only visits, which the step makes
            # on the kernel's path (a chunk of drafts is attended the
            # plain way).
            "table_pages": self._tables.size,
            "table_pages_held": int(np.count_nonzero(
                self._tables < sentinel)),
            "attn_pages_read": int(pages_walked(
                self._tables, self._positions, sentinel, page).sum()),
            "attn_kernel_layers": 0 if spec_mode else self._kernel_layers,
            # The seated lanes of this dispatch: the rows the step's own
            # ``live`` mask will find (a table that names a page).
            "lanes_live": int(np.count_nonzero(
                self._tables[:, 0] < sentinel)),
            "steps_ahead": int(after is not None),
            "steps_serial": int(after is None),
            # What this step's lanes hold in state rows beside their pages.
            "state_slots": self.allocator.state_slots,
            "state_bytes": self.allocator.state_bytes}
        if self.geometry.ring_pages:
            # The rings' tables apart, and the window pool's occupancy.
            counters.update(
                window_table_pages=self._window_tables.size,
                window_table_pages_held=int(np.count_nonzero(
                    self._window_tables < self.allocator.window_pages)),
                window_attn_pages_read=int(pages_walked(
                    self._window_tables, self._positions,
                    self.allocator.window_pages, page).sum()),
                window_pages_in_use=self.allocator.window_pages_in_use,
                window_pages_peak=self.allocator.window_peak_in_use,
                # The seated lanes whose position has passed the ring's
                # rows: their ring has gone round and is held whole.
                window_lanes_wrapped=int(np.count_nonzero(
                    (self._tables[:, 0] < sentinel) & (
                        self._positions
                        >= self.geometry.ring_pages * page))))
        chunk, spec_rows = None, 0
        if spec_mode:
            K = self.config.spec_k
            chunk = np.zeros((B, K), np.int32)
            chunk[:, 0] = self._tokens
            for slot, state in enumerate(lanes):
                if state is not None and state.spec:
                    chunk[slot, 1:] = state.draft(K - 1)
                    spec_rows += 1
            feed = chunk
        elif after is None:
            feed = self._tokens.copy()
        else:
            feed = np.full((B,), -1, np.int32)
            for slot, state in enumerate(lanes):
                if state is not None and after.lanes[slot] is not state:
                    feed[slot] = self._tokens[slot]
        self._spec_rows_last_step = spec_rows
        # Uploaded from copies made here, on the host: the arrays move on
        # below while this step may still be reading what it was handed
        # (the CPU backend aliases an aligned NumPy buffer, and jnp.array's
        # own copy is a device program that reads the alias later).
        tokens, positions, tables, temp, top_k, top_p, seeds = (
            jnp.asarray(a) for a in (feed, *map(np.copy, (
                self._positions, self._tables, self._temp, self._top_k,
                self._top_p, self._seeds))))
        if after is not None:
            tokens = self._hand_over(after.out[0], tokens)
        if self.geometry.ring_pages:
            tables = (tables, jnp.asarray(self._window_tables.copy()))
        # The stage, cut at the dispatch: host arrays, their seven uploads
        # and the hand-over before this stamp, the call over the whole
        # parameter tree until it returns after it.
        t_uploaded = time.monotonic()
        fn = self._spec_step_fn if spec_mode else self._step_fn
        *out, self.pools = fn(
            self._tree, tokens, positions, tables, self.pools,
            temp, top_k, top_p, seeds)
        self._gave_away(given)
        t_staged = time.monotonic()
        # Whole microseconds, so that the two parts add up to the stage on
        # the profiler's event (whose stats are whole numbers) and on the
        # record alike.
        upload_us = round((t_uploaded - t0) * 1e6)
        counters.update(
            pools_in_place=int(self._pools_in_place), spec_rows=spec_rows,
            upload_us=upload_us,
            dispatch_us=round((t_staged - t0) * 1e6) - upload_us)
        # The host's arrays now say what the NEXT dispatch feeds: a lane
        # moves one position on, and one whose budget this step fills
        # rides the next as an idle row, whatever its token turns out to be.
        for slot, state in enumerate(lanes):
            if state is None:
                continue
            state.in_flight += 1
            if state.due:
                self._positions[slot] += 1
            else:
                self._idle_row(slot)
        flight = _Flight(
            out=out, lanes=lanes, ahead=after is not None, chunk=chunk,
            t0=t0, queue_depth=queue_depth, counters=counters,
            admitted=self._admitted_since_step,
            prompt_tokens=self._prompt_tokens_since_step,
            prefill_ms=self._prefill_ms_since_step + chunk_ms,
            prefill_rows=prefill_rows)
        self._admitted_since_step = 0
        self._prompt_tokens_since_step = 0
        self._prefill_ms_since_step = 0.0
        self._pools_in_place = True
        return flight

    def _land(self, flight: _Flight) -> list[Request]:
        """Fetch a dispatched step's output and retire it: tokens to the
        lanes that rode it, finished lanes out, the step's record."""
        B, K = self.config.num_slots, self.config.spec_k
        spec_mode = flight.chunk is not None
        t_fetch = time.monotonic()
        with profiling.annotate("serve.step.fetch"):
            if spec_mode:
                greedy, nxt = (np.asarray(a) for a in flight.out)
            else:
                nxt = np.asarray(flight.out[0])
        nxt, riders = gpt_lib.unpack_step_output(self.model.cfg, nxt, B)
        now = time.monotonic()
        # A step dispatched ahead could not start before the one before it
        # ended, which is when that one landed: its time runs from there,
        # so that the steps' times tile the host's clock and do not overlap.
        t_begin = max(flight.t0, self._t_landed) if flight.ahead \
            else flight.t0
        self._t_landed = now
        step_ms = (now - t_begin) * 1e3
        self.step_index += 1
        counters = dict(
            flight.counters,
            # Lane-steps this dispatch spent on a lane that had left (an
            # eos seen a step late, a request abandoned) by its landing.
            lane_steps_discarded=sum(
                rode is not None and self._slots[slot] is not rode
                for slot, rode in enumerate(flight.lanes)),
            **_rider_counters(riders))
        event, fields, sums = _views(counters,
                                     bool(self.geometry.state_layers))
        self._sums.update(sums)
        with profiling.annotate("serve.step.retire", **event):
            tracer = tracing.active()
            round_id = 0
            t_round_unix = 0.0
            if tracer is not None:
                # One batched-round span per engine step, emitted at the
                # end of the region under an id reserved here (it carries
                # the region's own duration); the live lanes fan out below
                # as its children carrying their request's trace id, so the
                # same wall-clock interval appears once on the engine
                # timeline and once inside every participating request.
                t_round_unix = _unix_at(t_begin)
                round_id = tracer.allocate_id()
            spec_accepted = 0
            retired: list[Request] = []
            for slot, state in enumerate(self._slots):
                if state is None:
                    continue
                req = state.request
                rode = flight.lanes[slot] is state
                if rode:
                    state.in_flight -= 1
                if req.abandoned:
                    retired.append(self._retire(slot, "abandoned"))
                    continue
                if not rode:
                    # No token from this step: a prefilling lane rode it
                    # as a masked passenger (its decode-row writes dropped
                    # through the sentinel table), a lane seated behind it
                    # not at all.
                    continue
                if spec_mode and state.spec:
                    # Longest drafted prefix matching the greedy argmaxes,
                    # plus the free correction token — clamped to the
                    # lane's remaining budget.
                    row, g = flight.chunk[slot], greedy[slot]
                    accept = 1
                    while (accept < K and row[accept] == g[accept - 1]
                           and not (req.eos_id is not None
                                    and row[accept - 1] == req.eos_id)):
                        accept += 1
                    accept = min(accept, state.budget - state.generated)
                    emitted = [int(t) for t in row[1:accept]]
                    emitted.append(int(g[accept - 1]))
                    req.spec_rounds += 1
                else:
                    emitted = [int(nxt[slot])]
                if req.t_first_token is None:
                    req.t_first_token = now
                done_status = None
                count = 0
                for token in emitted:
                    req.tokens.append(token)
                    state.generated += 1
                    count += 1
                    if req.eos_id is not None and token == req.eos_id:
                        done_status = "ok"
                        break
                    if state.generated >= state.budget:
                        done_status = "ok"
                        break
                if state.spec:
                    state.commit(emitted[:count])
                    # Count what actually LANDED — an accepted eos
                    # truncates the emission mid-chunk, and the acceptance
                    # metric must not report the tokens the break
                    # discarded.
                    spec_accepted += count
                if tracer is not None:
                    _ensure_request_trace(tracer, req)
                    lane_attrs = {}
                    if spec_mode and state.spec:
                        lane_attrs = {"accepted": count,
                                      "drafted": K - 1}
                    tracer.emit_span(
                        "serve.decode_lane", t_round_unix, step_ms,
                        step=self.step_index, parent_id=round_id,
                        trace=req.trace, request_id=req.id,
                        tenant=req.tenant, tokens=count, **lane_attrs)
                if done_status is not None:
                    retired.append(self._retire(slot, done_status))
                else:
                    # The host's token, for a dispatch that follows this
                    # landing; the position moved one on at the dispatch.
                    self._tokens[slot] = emitted[count - 1]
                    self._positions[slot] += count - 1
            tel = self.telemetry
            if tel is not None:
                tel.histogram("serve_step_ms").record(step_ms)
                tel.gauge("serve_active_slots").set(self.active_slots)
                tel.gauge("serve_kv_pages_in_use").set(
                    self.allocator.pages_in_use)
                # Resident compiled prefill programs (LRU-bounded) + the
                # chunk program(s): /statz and /metricz both surface this.
                tel.gauge("serve_compile_cache").set(
                    len(self._prefill_fns) + len(self._chunk_fns))
                if spec_accepted:
                    tel.counter("serve_spec_tokens").inc(spec_accepted)
            if tracer is not None or tel is not None:
                # The region's last boundary: everything of the retire
                # region but the two emits themselves.
                upload_us, dispatch_us = (counters["upload_us"],
                                          counters["dispatch_us"])
                split_ms = {
                    "upload_ms": upload_us / 1e3,
                    "dispatch_ms": dispatch_us / 1e3,
                    "stage_ms": (upload_us + dispatch_us) / 1e3,
                    "fetch_ms": round((now - t_fetch) * 1e3, 3),
                    "retire_ms": round((time.monotonic() - now) * 1e3, 3)}
            if tracer is not None:
                tracer.emit_span(
                    "serve.decode_round", t_round_unix, step_ms,
                    step=self.step_index, parent_id=0, span_id=round_id,
                    active_slots=self.active_slots + len(retired),
                    spec_rows=counters["spec_rows"],
                    model_step=self.model_step, **split_ms)
            if tel is not None:
                tel.emit("serve_step", step=self.step_index,
                         active_slots=self.active_slots + len(retired),
                         admitted=flight.admitted,
                         retired=len(retired), queue_depth=flight.queue_depth,
                         kv_pages_in_use=self.allocator.pages_in_use,
                         kv_pages_total=self.config.num_pages, **fields,
                         t_start=round(flight.t0, 6),
                         step_ms=round(step_ms, 3), **split_ms,
                         spec_accepted=spec_accepted,
                         prompt_tokens=flight.prompt_tokens,
                         prefill_rows=flight.prefill_rows,
                         prefill_ms=round(flight.prefill_ms, 3),
                         model_step=self.model_step)
        return retired

    def fail_active(self, error: str) -> list[Request]:
        """Retire every live lane with an error (engine-fatal paths).  A
        program that raised after its dispatch had consumed the donated
        pools left them deleted: with every lane gone zeroed pools are
        the right state, so they are built anew and the engine serves
        the next request."""
        out = []
        for slot, state in enumerate(self._slots):
            if state is None:
                continue
            state.request.error = error
            out.append(self._retire(slot, "error"))
        # A step in flight is waited for and dropped: its lanes are gone.
        # Where it, or the step before it, is what failed, the pools it
        # returned are no better than the deleted ones.
        flight, self._flight = self._flight, None
        try:
            self._jax.block_until_ready(
                (flight.out if flight is not None else (), self.pools))
        except Exception:  # noqa: BLE001 — deleted, or the failure itself
            self.pools = self._fresh_pools()
            self._pools_in_place = True
        return out

    def stats(self) -> dict:
        """Occupancy/identity snapshot for /statz and the watch view."""
        return {
            "engine_step": self.step_index,
            "active_slots": self.active_slots,
            "num_slots": self.config.num_slots,
            "capacity_tokens": self.capacity,
            "model_step": self.model_step,
            "swaps": self.swaps,
            "quantize": self.config.quantize,
            "kv_dtype": self.config.kv_dtype,
            "spec_k": self.config.spec_k,
            "spec_rows": self._spec_rows_last_step,
            "prefill_chunk": self.config.prefill_chunk,
            "prefilling_slots": sum(
                1 for s in self._slots if s is not None and s.prefilling),
            # Resident compiled programs (the serve_compile_cache gauge's
            # /statz twin): per-bucket prefill programs are LRU-bounded
            # at prefill_cache_cap; chunk programs are one per width.
            "compile_cache": {
                "prefill_programs": len(self._prefill_fns),
                "chunk_programs": len(self._chunk_fns),
                "cap": self.config.prefill_cache_cap,
                "evictions": self._prefill_evictions,
            },
            # State rows beside the pages (linear-attention layers'
            # recurrent state, short-convolution layers' tails): the peak
            # and the bytes a slot are in the pool's snapshot.
            "state_slots": self.allocator.state_slots,
            "state_bytes": self.allocator.state_bytes,
            # The landed steps' counters summed (docs/observability.md
            # says what each counts); 0 where the model never counts one.
            **{name: self._sums[name] for name in _SUMS},
            "moe": {name: self._sums[name] for name in _MOE},
            "loop": {name: self._sums[name] for name in _LOOP},
            "kv_pool": self.allocator.snapshot(),
        }
