"""A decoder of sliding-window layers beside full ones
(``GptConfig.layer_kinds`` with ``"sliding_attention"``): the same
parameters under a banded mask, and in the paged pools a RING of pages a
lane where a full layer holds a run that grows; with grouped-query heads of a
size of their own, a norm a head, a sigmoid gate on the attention's output,
four norms a block and routed experts 8 of 128.  Against the benchmark's
plain reference (``perfbench/refs/trinity-mini.py``, loaded by path: one
reference, not two) at the rehearsal size of
``perfbench/configs/trinity-mini.json`` (five layers: dense sliding, then
sliding, sliding, sliding, full; 64 wide, heads of 32, window 16) in float32.
Pages of 8 rows: a ring is 3 pages, 24 rows.

Tolerances, with their reasons:

- ``LOGIT_TOL`` 2e-4 on logits of size about 1-4: program and reference are
  float32 throughout and differ in the order of their sums (rows sorted by
  expert against a masked loop over all 128, a ring's rows in ring order
  against the full score matrix under a mask, the widened query of
  ``GptBlock._attend_rows`` against grouped heads); sound readings here are
  3e-6 to 2e-5.  A token whose eighth and ninth router scores lie closer
  than that would choose another expert on one side and read 0.05 or more:
  none of the sequences here has one, and a new seed that finds one has
  found no fault.  bfloat16 anywhere reads 1e-2 (so does the ``int8`` +
  ``float8`` control, by far: a rehearsal cannot show that, the chip does).
- ``GAP_TOL`` 1e-4 on a served token's logit gap below the reference's best:
  a greedy token IS the reference's best unless two logits lie closer than
  the above.
- Where a test says "bit for bit" it compares the float32 patterns.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.kv_pool import (OutOfPages,
                                                        PageAllocator)
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import Telemetry
from perfbench import spec, weights, worker

CONFIG = os.path.join(spec.HERE, "configs", "trinity-mini.json")
SEED = 2 ** 31 + 44
LOGIT_TOL, GAP_TOL = 2e-4, 1e-4
PAGE, WINDOW, RING = 8, 16, 3
SLIDING, FULL = gpt_lib.SLIDING_ATTENTION, gpt_lib.FULL_ATTENTION


@pytest.fixture(scope="module")
def cfg():
    """The rehearsal size in float32."""
    cfg = spec.load_json(CONFIG)
    cfg = spec.deep_update(cfg, cfg["rehearsal"])
    cfg["model"]["dtype"] = cfg["param_dtype"] = "float32"
    cfg["model"]["attention_backend"] = "xla"
    return cfg


@pytest.fixture(scope="module")
def ref(cfg):
    return spec.named_module(cfg, "reference")


@pytest.fixture(scope="module")
def want_logits(cfg, ref):
    """The reference's logits for a sequence of up to 96 tokens, through
    ONE compiled shape: padded to 96 (no earlier position sees the padding,
    and a padded token's experts add nothing to another token)."""
    with jax.default_matmul_precision("highest"):
        layers = ref.Layers(cfg, SEED)

    def logits(seq):
        toks = np.zeros((96,), np.int32)
        toks[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            return np.asarray(layers.head(layers.halves, layers.hidden(
                jnp.asarray(toks))))[:len(seq)]
    return logits


@pytest.fixture(scope="module")
def model_and_params(cfg):
    gcfg = worker.gpt_config({"config": cfg, "config_file": CONFIG})
    model = gpt_lib.GptLM(gcfg)
    params = weights.program_tree(SEED, weights.Maker(cfg))
    worker.check_tree(jax, model, params, cfg)
    return model, params


class Rows:
    def __init__(self):
        self.rows = []

    def log(self, step, **fields):
        self.rows.append(fields)


def engine_of(model, params, slots=4, records=None, num_pages=64, **kw):
    return DecodeEngine(model, params, EngineConfig(
        num_slots=slots, page_size=PAGE, num_pages=num_pages,
        max_pages_per_seq=16, **kw),
        telemetry=None if records is None else Telemetry(records))


def tokens_of(n, index=0):
    return np.random.default_rng([SEED, index]).integers(0, 512, n).tolist()


def serve(engine, *requests):
    waiting = list(requests)
    while waiting or engine.active_slots:
        while waiting and engine.can_admit(waiting[0]):
            engine.validate(waiting[0])
            engine.admit(waiting.pop(0))
        engine.step()
    return [r.tokens for r in requests]


def gaps(ref, cfg, *requests):
    return np.concatenate(ref.served_gaps(
        cfg, SEED, [{"prompt": r.prompt, "served": r.tokens}
                    for r in requests], 128))


def decode_fn(model, params, num_pages=64):
    """The engine's decode step without its sampler: every lane's logits."""
    return jax.jit(lambda tok, pools, tables, pos, rings: model.apply(
        {"params": params}, tok, pools, tables, pos,
        tables[:, 0] < num_pages, rings,
        method=gpt_lib.GptLM.decode_paged))


def forced(engine, decode, seqs, prompts):
    """Lanes seated by ``engine.admit`` (the engine's own prefill and
    landing, its own tables), then decoded token after token with each
    lane's NEXT token taken from ``seqs`` and not from the logits: returns,
    a lane, the logits at positions ``P - 1 .. len(seq) - 2``.  A lane that
    has run out of tokens rides on as an idle row."""
    B = engine.config.num_slots
    sentinel = engine.config.num_pages
    out = [[] for _ in seqs]
    at = [p - 1 for p in prompts]
    slots = []
    for seq, P in zip(seqs, prompts):
        req = Request(seq[:P], len(seq) - P)
        engine.validate(req)
        slots.append(engine.admit(req))
    pools = engine.pools
    while any(a < len(s) - 1 for a, s in zip(at, seqs)):
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tables = np.full_like(engine._tables, sentinel)
        rings = np.full_like(engine._window_tables,
                             engine.allocator.window_pages)
        riding = [i for i, (a, s) in enumerate(zip(at, seqs))
                  if a < len(s) - 1]
        for i in riding:
            tok[slots[i]], pos[slots[i]] = seqs[i][at[i]], at[i]
            tables[slots[i]] = engine._tables[slots[i]]
            rings[slots[i]] = engine._window_tables[slots[i]]
        logits, pools = decode(jnp.asarray(tok), pools, jnp.asarray(tables),
                               jnp.asarray(pos), jnp.asarray(rings))
        for i in riding:
            out[i].append(np.asarray(logits[slots[i]]))
            at[i] += 1
    engine.pools = pools
    return [np.stack(o) for o in out], slots


# ------------------------------------------------------------- the model


def test_call_is_the_references_logits(cfg, ref, want_logits,
                                       model_and_params):
    model, params = model_and_params
    assert model.cfg.kinds == (SLIDING,) * 4 + (FULL,)
    assert model.cfg.sparse_layers == (False, True, True, True, True)
    assert (model.cfg.head_dim, model.cfg.hidden_size
            // model.cfg.num_heads) == (32, 16)
    toks = tokens_of(90)          # five and a half windows
    got = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))[0]
    want = ref.logits(cfg, SEED, toks)
    assert float(np.abs(want - want_logits(toks)).max()) < 1e-5
    assert float(np.abs(want).max()) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


def test_each_new_mechanism_moves_the_logits(want_logits, model_and_params):
    """What the reference is compared WITH has every mechanism switched on:
    the same weights without one of them read far from it."""
    import dataclasses
    model, params = model_and_params
    toks = tokens_of(40, 1)
    want = want_logits(toks)
    for off in ({"scale_embedding": False}, {"rope_kinds": ()},
                {"sliding_window": 12}):
        other = gpt_lib.GptLM(dataclasses.replace(model.cfg, **off))
        got = other.apply({"params": params},
                          jnp.asarray([toks], jnp.int32))[0]
        assert float(jnp.max(jnp.abs(got - want))) > 50 * LOGIT_TOL, off


@pytest.mark.parametrize("prompts,lengths", [
    # ends before the window (11 of 16), AT it (the last position attended
    # from is 15), and five windows past it; a fourth slot idle
    ((5, 9, 70), (11, 17, 86)),
    # a prompt of one token, one that fills its page bucket, one that ends
    # a token into a page past a whole ring
    ((1, 16, 25), (20, 30, 40)),
], ids=["before-at-past", "edges"])
def test_prefill_then_paged_decode_is_the_references_logits(
        want_logits, model_and_params, prompts, lengths):
    model, params = model_and_params
    engine = engine_of(model, params)
    seqs = [tokens_of(n, 10 + n) for n in lengths]
    got, _ = forced(engine, decode_fn(model, params), seqs, prompts)
    for seq, P, mine in zip(seqs, prompts, got):
        want = want_logits(seq)[P - 1:len(seq) - 1]
        assert mine.shape == want.shape
        assert float(np.abs(mine - want).max()) < LOGIT_TOL, (P, len(seq))
    # a window layer's pool holds a ring a slot and no more, whatever the
    # context; the full layer's holds the run
    for kind, (k_pool, v_pool) in zip(model.cfg.kinds, engine.pools):
        pages = 4 * RING + 1 if kind == SLIDING else 64 + 1
        assert k_pool.shape == v_pool.shape == (pages, PAGE, 2 * 32)
    assert engine.allocator.window_peak_in_use <= 4 * RING


def test_a_slot_reused_by_a_shorter_sequence_reads_nothing_of_the_longer(
        want_logits, model_and_params):
    """Three slots, so that the window pool has exactly three rings: the
    newcomer gets the pages the longest sequence filled five times over,
    and holds fewer of them."""
    model, params = model_and_params
    engine = engine_of(model, params, slots=3)
    first = [Request(tokens_of(n, n), k) for n, k in ((70, 9), (21, 30),
                                                      (33, 30))]
    for r in first:
        engine.admit(r)
    while first[0].t_done is None:
        engine.step()
    engine.settle()
    assert engine.free_slots == 1
    old_ring = np.flatnonzero(np.asarray(
        engine.pools[0][0]).reshape(3 * RING + 1, -1).any(axis=1))
    assert len(old_ring) == 3 * RING          # every ring page was written
    seq = tokens_of(14, 7)           # two pages: less than a ring
    decode = decode_fn(model, params)
    # the two lanes still decoding ride along as idle rows here: their
    # pages are not touched
    (mine,), (slot,) = forced(engine, decode, [seq], [6])
    assert slot == 0
    ring = engine._window_tables[slot]
    assert (ring < engine.allocator.window_pages).sum() == 2
    assert set(ring[:2]) <= set(old_ring.tolist())
    want = want_logits(seq)[5:13]
    assert float(np.abs(mine - want).max()) < LOGIT_TOL


def test_served_tokens_are_the_references_with_more_requests_than_slots(
        cfg, ref, model_and_params):
    model, params = model_and_params
    records = Rows()
    engine = engine_of(model, params, slots=2, records=records)
    requests = [Request(tokens_of(n, 100 + n), k)
                for n, k in ((50, 12), (7, 5), (16, 20), (3, 30), (90, 6))]
    serve(engine, *requests)
    assert [len(r.tokens) for r in requests] == [12, 5, 20, 30, 6]
    assert float(gaps(ref, cfg, *requests).max()) < GAP_TOL
    pool = engine.stats()["kv_pool"]
    assert pool["pages_in_use"] == pool["window"]["pages_in_use"] == 0
    assert pool["window"]["peak_in_use"] == 2 * RING
    assert pool["window"]["num_pages"] == 2 * RING
    assert pool["peak_in_use"] == 12 + 5      # 90 + 6 beside 3 + 30


# -------------------------------------------- what a lane's output reads


def foreign_and_stale(engine, slot, pos):
    """Two [pages, page] masks over a window pool, for lane ``slot`` at
    position ``pos``: the pages it does not own (the sentinel's apart,
    which is nobody's and all zeros), and in its OWN ring the rows whose
    newest position lies a window or more behind or was never written."""
    rows = engine.allocator.window_pages + 1
    foreign = np.ones((rows, PAGE), bool)
    foreign[-1] = False
    stale = np.zeros((rows, PAGE), bool)
    ring = engine._window_tables[slot]
    for j, page in enumerate(ring):
        if page == engine.allocator.window_pages:
            continue
        s = j * PAGE + np.arange(PAGE)
        behind = (pos - s) % (RING * PAGE)
        foreign[page] = False
        stale[page] = (behind >= WINDOW) | (behind > pos)
    return foreign, stale


@pytest.mark.parametrize("value", [np.nan, np.inf], ids=["nan", "inf"])
def test_a_lane_reads_no_row_outside_its_window_and_no_page_it_does_not_own(
        model_and_params, value):
    """``tests/test_sentinel_page.py``'s rule in the window form, bit for
    bit: with every page poisoned that the lane does not own (other lanes'
    and free ones, in BOTH kinds of pool: NaN or inf) its logits are the
    same, and the poison was there to be read, because the other lanes read
    it.  And in its OWN ring the rows the window has left (up to a page of
    them, which a newer token has not overwritten yet) and the rows no
    token has reached count under a weight of exactly zero, as a full
    layer's rows past the position do: whatever finite values they hold,
    the logits are the same."""
    model, params = model_and_params
    cfg = model.cfg
    engine = engine_of(model, params, slots=3, num_pages=32)
    prompts = (70, 20, 5)           # past a ring, inside one, before a page
    for i, P in enumerate(prompts):
        engine.admit(Request(tokens_of(P, 40 + i), 30))
    for _ in range(3):
        engine.step()
    engine.settle()
    pos = engine._positions.copy()
    assert pos.tolist() == [73, 23, 8]
    decode = decode_fn(model, params, 32)
    args = (jnp.asarray(engine._tokens), jnp.asarray(engine._tables),
            jnp.asarray(pos), jnp.asarray(engine._window_tables))

    def run(pools):
        return np.asarray(decode(args[0], pools, *args[1:])[0])

    want = run(engine.pools)
    assert np.isfinite(want).all() and np.abs(want).max() > 0.1
    for lane in range(3):
        own = engine._tables[lane]
        foreign = np.ones((32 + 1,), bool)
        foreign[own[own < 32]] = False
        foreign[-1] = False
        others, stale = foreign_and_stale(engine, lane, pos[lane])
        # (every lane has such rows: the eight the window has left, or
        # those no token has reached)
        assert stale.sum() == (8, 8, 15)[lane]
        bad = {FULL: jnp.asarray(foreign)[:, None, None],
               SLIDING: jnp.asarray(others)[:, :, None]}
        got = run([tuple(jnp.where(
            bad[kind], value, jnp.where(jnp.asarray(stale)[:, :, None], 1e30,
                                        x) if kind == SLIDING else x)
            for x in entry) for kind, entry in zip(cfg.kinds, engine.pools)])
        np.testing.assert_array_equal(got[lane].view(np.uint32),
                                      want[lane].view(np.uint32))
        assert not np.isfinite(got[[i for i in range(3) if i != lane]]).all()


def test_ring_addressing_by_hand(model_and_params):
    """One sliding block alone: position p is written to ring page
    ``(p // page) % ring_pages`` at offset ``p % page``, and the step
    attends exactly the window's positions."""
    model, params = model_and_params
    cfg = model.cfg
    block = gpt_lib.GptBlock(cfg, SLIDING, False)
    p0 = params["layer0"]
    ring = jnp.asarray([[4, 2, 7]])            # ring pages 0, 1, 2
    k_pool = jnp.zeros((10, PAGE, 64))
    x = jax.random.normal(jax.random.key(1), (1, 1, 64))
    for pos in (0, 7, 8, 23, 24, 47, 100):
        _, k_new, _ = block.apply(
            {"params": p0}, x, k_pool, k_pool, ring, jnp.asarray([pos]),
            method=gpt_lib.GptBlock.decode_step_paged)
        wrote = np.argwhere(np.asarray(k_new).any(axis=-1))
        page = [4, 2, 7][(pos // PAGE) % RING]
        assert wrote.tolist() == [[page, pos % PAGE]], pos


# ---------------------------------------------------------- the allocator


def test_the_allocators_two_counts_are_a_numpy_count():
    """Random admissions, extensions and retirements against two plain
    arrays of owners: the free and held counts of each kind, the peaks, the
    tables, and admission that needs room in BOTH."""
    rng = np.random.default_rng(44)
    alloc = PageAllocator(40, PAGE, window_pages=4 * RING, ring_pages=RING,
                          window_row_bytes_per_token=2048)
    full = np.full((40,), -1)
    ring = np.full((4 * RING,), -1)
    live, peak, wpeak = {}, 0, 0
    for step in range(400):
        if live and rng.random() < 0.4:
            seq = rng.choice(sorted(live))
            if rng.random() < 0.5:
                assert alloc.free(seq) == (full == seq).sum()
                full[full == seq] = -1
                ring[ring == seq] = -1
                del live[seq]
                continue
            tokens = live[seq] + int(rng.integers(0, 20))
            need = -(-tokens // PAGE) - (full == seq).sum()
            more = min(-(-tokens // PAGE), RING) - (ring == seq).sum()
            fits = need <= (full < 0).sum() and more <= (ring < 0).sum()
            try:
                fresh = alloc.extend(seq, tokens)
            except OutOfPages:
                assert not fits
                continue
            assert fits and len(fresh) == max(need, 0)
            live[seq] = max(live[seq], tokens)
        else:
            seq, tokens = 1000 + step, int(rng.integers(1, 90))
            need, held = -(-tokens // PAGE), min(-(-tokens // PAGE), RING)
            fits = need <= (full < 0).sum() and held <= (ring < 0).sum()
            assert alloc.can_alloc(tokens) == fits
            if not fits:
                with pytest.raises(OutOfPages):
                    alloc.alloc(seq, tokens)
                continue
            fresh = alloc.alloc(seq, tokens)
            live[seq] = tokens
        full[np.asarray(alloc.owned(seq), int)] = seq
        table = alloc.window_table(seq)
        ring[table[table < 4 * RING]] = seq
        # a sequence's tables: its own pages in order, then the sentinels
        assert alloc.page_table(seq, 16).tolist() == alloc.owned(seq) + [
            40] * (16 - len(alloc.owned(seq)))
        assert (table < 4 * RING).sum() == min(
            -(-live[seq] // PAGE), RING) == alloc.window_pages_for(live[seq])
        assert (table[(table < 4 * RING).sum():] == 4 * RING).all()
        peak = max(peak, (full >= 0).sum())
        wpeak = max(wpeak, (ring >= 0).sum())
        snap = alloc.snapshot()
        assert (snap["pages_in_use"], snap["free_pages"],
                snap["peak_in_use"]) == ((full >= 0).sum(),
                                         (full < 0).sum(), peak)
        assert snap["window"] == {
            "num_pages": 4 * RING, "ring_pages": RING,
            "pages_in_use": (ring >= 0).sum(), "free_pages":
            (ring < 0).sum(), "peak_in_use": wpeak,
            "row_bytes_per_token": 2048}
        # no page of either kind has two owners
        for seq_id in live:
            assert (full == seq_id).sum() == len(alloc.owned(seq_id))
    assert peak == 40 or wpeak == 4 * RING     # admission did push back


def test_an_allocator_without_window_layers_is_the_one_kind_allocator():
    alloc = PageAllocator(8, 4)
    assert alloc.can_alloc(32) and not alloc.can_alloc(33)
    alloc.alloc("a", 9)
    assert alloc.window_table("a").shape == (0,)
    assert alloc.snapshot()["window"] == {
        "num_pages": 0, "ring_pages": 0, "pages_in_use": 0, "peak_in_use": 0,
        "free_pages": 0, "row_bytes_per_token": 0}
    with pytest.raises(ValueError, match="ring_pages"):
        PageAllocator(8, 4, window_pages=3, ring_pages=4)


# ------------------------------------------------------------ the engine


def test_the_window_counters_are_a_numpy_count(model_and_params,
                                               monkeypatch):
    """``window_table_pages`` / ``_held`` / ``window_pages_in_use`` /
    ``_peak`` and ``window_attn_pages_read`` on the ``serve_step`` record,
    on the profiler's retire event and in ``engine.stats()`` against a
    count of the ring tables and positions each dispatch was handed; the
    full tables' counters beside them; the
    routing counters over 4 x 128 slots; the prefill span's new fields."""
    seen = []
    real = profiling.annotate
    monkeypatch.setattr(profiling, "annotate", lambda name, **stats: (
        seen.append((name, stats)), real(name, **stats))[1])
    model, params = model_and_params
    records = Rows()
    engine = engine_of(model, params, slots=3, records=records)
    counted, hists = [], []

    def counting(fn):
        def dispatch(tree, tokens, positions, tables, *rest):
            table, rings = (np.asarray(t) for t in tables)
            # a lane holds its ring from its admission to its retirement,
            # a step longer than its table's row names it
            seated = [s for s in engine._slots if s is not None]
            # what a read of held pages only visits: those at or before
            # the page of a lane's position, which in a ring that has gone
            # round is every page (PR 45)
            at = np.asarray(positions)[:, None] // PAGE
            counted.append({
                "table_pages": table.size,
                "table_pages_held": int((table < 64).sum()),
                "attn_pages_read": int(((table < 64) & (
                    np.arange(table.shape[1])[None, :] <= at)).sum()),
                "attn_kernel_layers": 0,
                "window_table_pages": rings.size,
                "window_table_pages_held": int((rings < 3 * RING).sum()),
                "window_attn_pages_read": int(((rings < 3 * RING) & (
                    np.arange(RING)[None, :] <= at)).sum()),
                "window_pages_in_use": sum(
                    min(-(-(s.prompt_len + s.budget) // PAGE), RING)
                    for s in seated)})
            out = fn(tree, tokens, positions, tables, *rest)
            hists.append(np.asarray(out[0])[3:].reshape(4, 128))
            return out
        return dispatch
    engine._step_fn = counting(engine._step_fn)
    requests = [Request(tokens_of(n, 200 + n), k) for n, k in
                ((30, 5), (4, 9), (17, 3), (60, 4), (9, 6))]
    serve(engine, *requests)
    steps = [r for r in records.rows if r.get("kind") == "serve_step"]
    assert len(steps) == len(counted) > 8
    assert [{k: r[k] for k in counted[0]} for r in steps] == counted
    retire = [s for n, s in seen if n == "serve.step.retire"]
    assert [{k: s[k] for k in counted[0]} for s in retire] == counted
    peaks = np.maximum.accumulate([c["window_pages_in_use"]
                                   for c in counted])
    assert [r["window_pages_peak"] for r in steps] == peaks.tolist()
    stats = engine.stats()
    for key in ("table_pages", "table_pages_held", "window_table_pages",
                "window_table_pages_held", "attn_pages_read",
                "window_attn_pages_read", "attn_kernel_layers"):
        assert stats[key] == sum(c[key] for c in counted)
    # a lane inside the window walks less than its ring, one past it all
    assert 0 < stats["window_attn_pages_read"] \
        < stats["window_table_pages_held"]
    assert 0 < stats["window_table_pages_held"] < stats["window_table_pages"]
    assert all(c["window_table_pages"] == 3 * RING for c in counted)
    assert stats["kv_pool"]["window"]["peak_in_use"] == peaks[-1] > RING
    assert stats["pool_steps_copied"] == 0
    # the routing counters cover the four sparse layers' 128 experts
    for r, h in zip(steps, hists):
        assert r["expert_slots"] == 4 * 128
        assert r["experts_touched"] == np.count_nonzero(h)
        assert r["expert_tokens_max"] == h.max()
        # every lane that rode, 8 experts in each of 4 layers
        assert r["routed_tokens"] == h.sum() and h.sum() % 32 == 0


def test_the_prefill_span_names_the_window_layers(model_and_params):
    from distributed_tensorflow_tpu.utils import tracing
    model, params = model_and_params
    records = Rows()
    tracing.install(tracing.Tracer(Telemetry(records), run_id="sliding"))
    try:
        serve(engine_of(model, params), Request(tokens_of(40, 300), 3))
    finally:
        tracing.clear()
    span = next(r for r in records.rows if r.get("name") == "serve.prefill")
    assert (span["window_layers"], span["ring_pages"], span["row_bytes"],
            span["sparse_layers"]) == (4, RING, 512, 4)


def test_routing_at_8_of_128_is_balanced(cfg, model_and_params):
    """The router's kernel is drawn like any kernel (the configuration's
    ``assumed``), so expert e's logit over unit-rms streams is normal with
    deviation |w_e|, and |w_e| spreads by 1 / sqrt(2 x width) around 1: 9%
    at the rehearsal's 64, 1.6% at the published 2,048.  Eight of 128 is
    the upper 6% tail, where a deviation 9% larger is chosen a third more
    often.  So: at the PUBLISHED width (the draw alone, over isotropic
    streams: 4,096 tokens) every expert gets between 0.5 and 2 times its
    fair share, as ISSUE 44 asks; at the rehearsal width through the whole
    model every expert of every sparse layer is used, between 0.1 and 4
    times (readings 0.16-3.6): the narrow model's imbalance, not the
    rule's (the chip's ``tri_expert_load_peak`` reads the full size)."""
    from distributed_tensorflow_tpu.ops import routed_experts
    fair = 4096 * 8 / 128
    kernel = weights.leaf(jax.random.key(44), "router/kernel", (2048, 128),
                          cfg["init"], jnp.float32)
    m = jax.random.normal(jax.random.key(1), (4096, 2048))
    m = m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True))
    chosen, w = routed_experts.route(m @ kernel, jnp.zeros((128,)), 8, 2.826)
    share = np.bincount(np.asarray(chosen).ravel(), minlength=128) / fair
    assert 0.5 < share.min() and share.max() < 2.0
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.826, rtol=1e-5)
    model, params = model_and_params
    toks = np.asarray(tokens_of(4096, 9)).reshape(32, 128)
    _, sown = model.apply({"params": params}, jnp.asarray(toks, jnp.int32),
                          mutable=["routing"])
    for i in range(1, 5):
        counts = np.asarray(sown["routing"][f"layer{i}"]["counts"][0])
        assert counts.sum() == 4096 * 8
        assert 0.1 < counts.min() / fair and counts.max() / fair < 4.0


# ------------------------------------------------------- what is refused


def test_what_the_window_kind_composes_with_and_what_refuses_it():
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                intermediate_size=48, max_position=64, pos_encoding="rope",
                norm="rmsnorm", activation="swiglu")
    kinds = (SLIDING, FULL)
    # beside full layers, grouped heads and experts: yes
    ok = gpt_lib.GptConfig(**base, layer_kinds=kinds, sliding_window=8,
                           kv_heads=2, num_experts=4, experts_per_token=2,
                           expert_intermediate_size=8,
                           norm_placement="sandwich", rope_kinds=(SLIDING,))
    assert ok.window_layers == 1 and ok.ring_pages(4) == 3
    assert ok.ring_pages(3) == 4               # a window that ends mid-page
    for bad, text in (
            (dict(layer_kinds=kinds), "sliding_window"),
            (dict(sliding_window=8), "sliding_window"),
            (dict(layer_kinds=kinds, sliding_window=8, attention_window=8),
             "attention_window"),
            (dict(layer_kinds=(SLIDING, "linear_attention"),
                  sliding_window=8, linear_num_heads=2,
                  linear_key_head_dim=4, linear_value_head_dim=4),
             "linear_attention"),
            (dict(layer_kinds=kinds, sliding_window=8, loop_steps=2),
             "loop_steps"),
            (dict(layer_kinds=kinds, sliding_window=8, latent_kv_rank=8,
                  latent_q_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
                  v_head_dim=8, pos_encoding="none"), "latent_kv_rank"),
            (dict(rope_kinds=(SLIDING,)), "rope_kinds"),
            (dict(head_size=-1), "head_size")):
        with pytest.raises(ValueError, match=text):
            gpt_lib.GptConfig(**{**base, **bad})
    # the paths that hold one kind of entry refuse the ring by name
    model = gpt_lib.GptLM(ok)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    with pytest.raises(ValueError, match="sliding_attention"):
        ok.refuse_state_layers("somewhere")
    with pytest.raises(ValueError, match="window_tables"):
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, jnp.zeros((2,), jnp.int32),
            gpt_lib.init_kv_pool(ok, 8, 4, num_slots=2),
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), bool), method=gpt_lib.GptLM.decode_paged), params)
    with pytest.raises(ValueError, match="sliding_attention"):
        DecodeEngine(model, params, EngineConfig(prefill_chunk=4))
    # the global window keeps its meaning, and its refusal names the kind
    with pytest.raises(ValueError, match="KIND of layer"):
        gpt_lib.init_kv_pool(gpt_lib.GptConfig(**base, attention_window=8),
                             8, 4)
    assert gpt_lib.init_kv_cache(
        gpt_lib.GptConfig(**base, attention_window=8), 1, 40)[0][0].shape \
        == (1, 8, 4, 8)
    caches = gpt_lib.init_kv_cache(ok, 1, 40, ring_rows=12)
    assert [c[0].shape[1] for c in caches] == [12, 40]
    assert [c[0].shape[1] for c in gpt_lib.init_kv_cache(ok, 1, 8,
                                                         ring_rows=12)] \
        == [8, 8]
