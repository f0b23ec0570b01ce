"""The not-allocated sentinel of a page table is a PAGE: the one after the
allocator's ``num_pages`` in every paged pool, all zeros, handed out by
nobody and never written (PR 39; ``gpt_lib.init_kv_pool``).  The decode
step's gather reads it like any page, so no pass blanks the gathered rows,
and what holds is exact: **a lane's output depends on no page it does not
own at that step**, non-finite values there included, in every paged form
(K/V rows in a dense and in a hybrid decoder, the K/V chunk, a loop step's
run of pages, a latent row's two parts; since PR 45 also where the step
attends its pools through the paged-attention kernel, which COPIES no page
a lane does not own, and since PR 47 through the latent layers' kernel).
On the parent of PR 39 the K/V cases hold too (``mode="fill"`` wrote the zeros) and the latent ones FAIL:
its gather clipped a sentinel entry onto the pool's last real page, whose
rows reach every lane's weighted sum as ``0 x row``.

Tiny widths, float32, on the CPU; equal means bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.ops.pallas import paged_attention as paged_ops
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import Telemetry

BASE = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            intermediate_size=48, max_position=64, dtype="float32")
CONFIGS = {
    "dense": dict(pos_encoding="rope", kv_heads=2, activation="swiglu",
                  norm="rmsnorm"),
    "hybrid": dict(
        num_layers=4, pos_encoding="none", norm="rmsnorm",
        activation="swiglu", norm_placement="post", qk_norm=True,
        layer_kinds=("linear_attention",) * 3 + ("full_attention",),
        linear_num_heads=2, linear_key_head_dim=8, linear_value_head_dim=16),
    "looped": dict(pos_encoding="rope", activation="swiglu", norm="rmsnorm",
                   norm_placement="sandwich", loop_steps=3, exit_gate=True),
    # The paged-attention kernel's shapes: a head fills 128 lanes, and (in
    # float32) a page whole tiles of 8 rows.  Steered onto the kernel's
    # path below, under the TPU interpreter.
    "kernel": dict(pos_encoding="rope", num_heads=2, kv_heads=1,
                   head_size=128, activation="swiglu", norm="rmsnorm",
                   attention_backend="pallas"),
    # The same path at heads of 64, two kv heads a lane tile (a flat row of
    # 128), under a short-convolution layer whose entry is a tail a slot,
    # and routed experts: the kernel sees one kv "head" of 128.
    "kernel64": dict(pos_encoding="rope", hidden_size=64, num_heads=4,
                     kv_heads=2, head_size=64, qk_head_norm=True,
                     activation="swiglu", norm="rmsnorm",
                     attention_backend="pallas",
                     layer_kinds=("short_conv", "full_attention"),
                     short_conv_kernel_dim=3, num_experts=4,
                     experts_per_token=2, expert_intermediate_size=16,
                     first_dense_layers=1),
    # A latent layer's two pools on ITS kernel's path (PR 47): latents in
    # whole lane tiles, a rotated key of 64 so that two tokens fill a row
    # of the keys' pool, pages of 16.
    "latent-kernel": dict(
        num_layers=3, pos_encoding="none", activation="swiglu",
        norm="rmsnorm", rope_base=1e6, latent_kv_rank=128, latent_q_rank=48,
        qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=96,
        num_heads=5, hidden_size=40, num_experts=8, experts_per_token=2,
        expert_intermediate_size=32, num_shared_experts=1,
        routed_scaling_factor=1.8, first_dense_layers=1,
        attention_backend="pallas"),
    "latent": dict(
        num_layers=3, pos_encoding="none", activation="swiglu",
        norm="rmsnorm", rope_base=1e6, latent_kv_rank=32, latent_q_rank=48,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
        num_experts=8, experts_per_token=2, expert_intermediate_size=32,
        num_shared_experts=1, routed_scaling_factor=1.8,
        first_dense_layers=1),
}
PAGES, PAGE, MP = 12, 4, 4
S = PAGES                                  # the sentinel
#: Three lanes of different lengths and an idle one.  Lane 1 fills its
#: table; page 11, the allocator's last (what a clipped sentinel read), and
#: pages 1, 4, 6, 8 are free.
TABLES = np.asarray([[2, 5, S, S], [0, 3, 7, 9], [10, S, S, S], [S] * MP],
                    np.int32)
POSITIONS = np.asarray([6, 13, 2, 0], np.int32)
TOKENS = np.asarray([3, 9, 17, 0], np.int32)


def model_of(name):
    model = gpt_lib.GptLM(gpt_lib.GptConfig(**{**BASE, **CONFIGS[name]}))
    return model, model.init(jax.random.key(39),
                             jnp.zeros((1, 8), jnp.int32))["params"]


def paged(kind):
    return kind not in gpt_lib.STATE_KINDS


def junk_pools(cfg, seed=0, page=PAGE):
    """Pools as a server that has run for a while holds them: every page
    but the sentinel's holds what some owner wrote (a freed page is never
    blanked), every slot a recurrent state."""
    keys = iter(jax.random.split(jax.random.key(seed), 64))
    pools = gpt_lib.init_kv_pool(cfg, PAGES, page, num_slots=len(TABLES))
    return [tuple(
        jax.random.normal(next(keys), x.shape, x.dtype).at[-1].set(0)
        if paged(kind) else jax.random.normal(next(keys), x.shape,
                                                   x.dtype)
        for x in entry) for kind, entry in zip(cfg.kinds, pools)]


def not_owned_by(cfg, lane):
    """[rows] bool over a pool's pages: those ``lane`` does not own, in
    every loop step's run; never the sentinel's page, which is nobody's."""
    own = set(TABLES[lane][TABLES[lane] < S].tolist())
    other = np.asarray([p not in own for p in range(PAGES)])
    return np.concatenate([np.tile(other, cfg.loop_steps), [False]])


def poisoned(cfg, pools, lane, value):
    rows = jnp.asarray(not_owned_by(cfg, lane))
    return [tuple(jnp.where(rows[:, None, None], value, x)
                  if paged(kind) else x for x in entry)
            for kind, entry in zip(cfg.kinds, pools)]


def sentinel_pages_are_zero(cfg, pools):
    return all(not np.asarray(x[-1]).any()
               for kind, entry in zip(cfg.kinds, pools) if paged(kind)
               for x in entry)


def run(model, params, program, pools):
    # (the same rows of each lane's pages whatever the page's size)
    tables = jnp.asarray(TABLES)
    positions = jnp.asarray(POSITIONS * (pools[-1][0].shape[1] // PAGE))
    if program == "chunk":
        # Lane 0's four tokens run past its two pages (positions 8, 9 have
        # no page), lane 1's past its table (16: no entry at all).
        chunk = jnp.stack([jnp.asarray(TOKENS)] * 4, axis=1) + jnp.arange(4)
        return model.apply({"params": params}, chunk, pools, tables,
                           positions,
                           method=gpt_lib.GptLM.decode_chunk_paged)
    live = tables[:, 0] < S
    return model.apply({"params": params}, jnp.asarray(TOKENS), pools,
                       tables, positions, live,
                       method=gpt_lib.GptLM.decode_paged)


FORMS = [("dense", "step"), ("dense", "chunk"), ("hybrid", "step"),
         ("looped", "step"), ("latent", "step"), ("kernel", "step"),
         ("kernel64", "step"), ("latent-kernel", "step")]
#: The page each kernel case runs at: whole sublane tiles of float32, and
#: for the rotated keys' half-page of rows as well.
KERNEL_PAGE = {"kernel": 2 * PAGE, "kernel64": 2 * PAGE,
               "latent-kernel": 4 * PAGE}


def on_the_kernels_path(monkeypatch):
    """What a TPU decides by its backend, steered here: the step attends
    every pool the kernel can walk through it (interpreted off the chip)."""
    monkeypatch.setattr(
        gpt_lib, "paged_kernel_attends",
        lambda cfg, pool, key_pool=None: (
            paged_ops.supports(pool, cfg.head_dim) if key_pool is None
            else paged_ops.supports_latent(pool, key_pool)))


@pytest.mark.parametrize("value", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("name,program", FORMS,
                         ids=["-".join(f) for f in FORMS])
def test_a_lanes_logits_depend_on_no_page_it_does_not_own(
        name, program, value, monkeypatch):
    model, params = model_of(name)
    cfg = model.cfg
    page = PAGE
    if name in KERNEL_PAGE:
        on_the_kernels_path(monkeypatch)
        monkeypatch.setattr(paged_ops, "_CHUNK_MAX", 128)
        page = KERNEL_PAGE[name]
        calls = []
        for entry in ("paged_attention", "latent_paged_attention"):
            monkeypatch.setattr(
                paged_ops, entry,
                lambda *a, real=getattr(paged_ops, entry), **kw: (
                    calls.append(kw), real(*a, **kw))[1])
    pools = junk_pools(cfg, page=page)
    assert sentinel_pages_are_zero(cfg, pools)
    assert all(x.shape[0] == cfg.loop_steps * PAGES + 1
               for kind, entry in zip(cfg.kinds, pools) if paged(kind)
               for x in entry)
    step = jax.jit(lambda p: run(model, params, program, p))
    want, after = step(pools)
    if name in KERNEL_PAGE:
        # traced once, a call a K/V (or latent) layer
        assert len(calls) == sum(map(paged, cfg.kinds))
    want = np.asarray(want)
    assert np.isfinite(want[:3]).all() and np.abs(want[:3]).max() > 0.1
    assert sentinel_pages_are_zero(cfg, after)
    for lane in range(3):
        got, after = step(poisoned(cfg, pools, lane, value))
        got = np.asarray(got)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got[lane].view(np.uint32),
                                      want[lane].view(np.uint32))
        # (the poison was there to be read: a lane that owns a poisoned
        # page reads it)
        assert not np.isfinite(got[[i for i in range(3) if i != lane]]).all()
        assert sentinel_pages_are_zero(cfg, after)


def test_a_write_through_the_sentinel_goes_past_the_pool():
    rows = 3 * PAGES + 1
    pages = jnp.asarray([[0, PAGES - 1, PAGES]])
    runs = gpt_lib.loop_step_pages(pages, 2, rows, 3)
    assert runs.tolist() == [[2 * PAGES, 3 * PAGES - 1, rows - 1]]
    assert gpt_lib.written_pages(runs, rows).tolist() == [
        [2 * PAGES, 3 * PAGES - 1, rows]]
    pool = jnp.ones((PAGES + 1, PAGE, 8)).at[-1].set(0)
    table = jnp.asarray([[1, PAGES]])
    assert gpt_lib.gather_pages(pool, table).shape == (1, 2 * PAGE, 8)
    assert np.asarray(gpt_lib.gather_pages(pool, table))[0, PAGE:].max() == 0
    wrote = pool.at[gpt_lib.written_pages(table[0], PAGES + 1)].set(
        7.0, mode="drop")
    assert np.asarray(wrote[1]).min() == 7 and not np.asarray(wrote[-1]).any()


# ------------------------------------------------------------ the engine


class Rows:
    def __init__(self):
        self.rows = []

    def log(self, step, **fields):
        self.rows.append(fields)


def engine_of(name, records=None, **kw):
    model, params = model_of(name)
    return DecodeEngine(model, params, EngineConfig(**{
        "num_slots": 3, "page_size": PAGE, "num_pages": PAGES,
        "max_pages_per_seq": MP, **kw}),
        telemetry=None if records is None else Telemetry(records))


@pytest.mark.parametrize("name,kw", [
    ("dense", {}), ("dense", {"spec_k": 4}), ("dense", {"prefill_chunk": 3}),
    ("hybrid", {}), ("looped", {}), ("latent", {}),
    ("kernel", {"page_size": 2 * PAGE}),
    ("latent-kernel", {"page_size": 4 * PAGE})],
    ids=["dense", "dense-spec", "dense-chunked", "hybrid", "looped",
         "latent", "kernel", "latent-kernel"])
def test_the_engine_never_writes_the_sentinels_page_and_counts_its_table(
        name, kw, monkeypatch):
    """A short run with admissions and retirements, a slot reused, a
    prompt that ends inside a page and one that fills its bucket, (with
    ``spec_k``) drafts past a lane's reservation: after every prefill and
    every step the sentinel's page of every pool is all zeros, the
    allocator never hands it out, and ``table_pages`` /
    ``table_pages_held`` on the ``serve_step`` record, on the profiler's
    retire event and in ``engine.stats()`` are a NumPy count of the table
    each dispatch was handed; ``attn_pages_read`` beside them a count of
    the pages a lane holds up to its position's (what the paged-attention
    kernel copies), and ``attn_kernel_layers`` the layers of the dispatched
    program that read so: none on the CPU's plain path, every K/V layer
    (every latent layer, since PR 47) in a case steered onto a kernel's."""
    if name in KERNEL_PAGE:
        on_the_kernels_path(monkeypatch)
        monkeypatch.setattr(paged_ops, "_CHUNK_MAX", 128)
    seen = []
    real = profiling.annotate
    monkeypatch.setattr(profiling, "annotate", lambda name, **stats: (
        seen.append((name, stats)), real(name, **stats))[1])
    records = Rows()
    engine = engine_of(name, records, **kw)
    cfg = engine.model.cfg
    assert engine.allocator.num_pages == PAGES
    assert engine.stats()["kv_pool"]["num_pages"] == PAGES
    counted = []

    page = engine.config.page_size

    def counting(fn, kernel_layers):
        def dispatch(tree, tokens, positions, tables, *rest):
            table, at = np.asarray(tables), np.asarray(positions)
            # a lane's held pages at or before its position's page
            upto = np.arange(MP)[None, :] <= at[:, None] // page
            counted.append({
                "table_pages": table.size,
                "table_pages_held": int((table < PAGES).sum()),
                "attn_pages_read": int(((table < PAGES) & upto).sum()),
                "attn_kernel_layers": kernel_layers})
            return fn(tree, tokens, positions, tables, *rest)
        return dispatch
    engine._step_fn = counting(
        engine._step_fn, cfg.num_layers if name in KERNEL_PAGE else 0)
    if engine._spec_step_fn is not None:
        engine._spec_step_fn = counting(engine._spec_step_fn, 0)

    rng = np.random.default_rng(39)
    waiting = [Request(rng.integers(0, 64, P).tolist(), n,
                       speculative="spec_k" in kw)
               for P, n in ((5, 4), (8, 3), (3, 6), (6, 2), (1, 9))]
    while waiting or engine.active_slots:
        while waiting and engine.can_admit(waiting[0]):
            engine.admit(waiting.pop(0))
            assert sentinel_pages_are_zero(cfg, engine.pools)
        assert all(PAGES not in engine.allocator.owned(s.request.id)
                   for s in engine._slots if s is not None)
        engine.step()
        assert sentinel_pages_are_zero(cfg, engine.pools)
    steps = [r for r in records.rows if r.get("kind") == "serve_step"]
    assert len(steps) == len(counted) > 8
    assert [{k: r[k] for k in counted[0]} for r in steps] == counted
    retire = [s for n, s in seen if n == "serve.step.retire"]
    assert [{k: s[k] for k in counted[0]} for s in retire] == counted
    stats = engine.stats()
    assert stats["table_pages"] == 3 * MP * len(counted)
    assert stats["table_pages_held"] == sum(
        c["table_pages_held"] for c in counted)
    # some entries were held and some read the sentinel's page
    assert 0 < stats["table_pages_held"] < stats["table_pages"]
    assert all(c["table_pages"] == 3 * MP for c in counted)
    # the walk is held pages, and fewer where a reservation runs ahead of
    # a lane's position or a lane has left (an idle row holds nothing)
    assert stats["attn_pages_read"] == sum(
        c["attn_pages_read"] for c in counted)
    assert 0 < stats["attn_pages_read"] <= stats["table_pages_held"]
    assert stats["attn_kernel_layers"] == sum(
        c["attn_kernel_layers"] for c in counted) == (
            cfg.num_layers * len(counted) if name in KERNEL_PAGE else 0)
    assert stats["window_attn_pages_read"] == 0
    assert stats["pool_steps_copied"] == 0
