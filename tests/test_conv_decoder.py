"""A decoder of gated short-convolution layers beside grouped-query
attention layers (``GptConfig.layer_kinds`` with ``"short_conv"``) around
routed experts: a convolution layer keeps the last two rows of ``B * X`` a
sequence, one row a decode slot in the paged pools, where an attention layer
keeps pages.  Against the benchmark's plain reference
(``perfbench/refs/lfm2-24b-a2b.py``, loaded by path: one reference, not two)
at the rehearsal size of ``perfbench/configs/lfm2-24b-a2b.json`` (five
layers: dense conv; attention, conv, conv, conv: the leading dense layer and
one period; 64 wide, heads of 16, 4 of 16 experts) in float32.  Pages of 8
rows.

Tolerances, with their reasons:

- ``LOGIT_TOL`` 2e-4 on logits of size about 1-4: program and reference are
  float32 throughout and differ in the order of their sums (rows sorted by
  expert against a masked loop over all 16, the widened query of
  ``GptBlock._attend_rows`` against grouped heads, a tail's two rows against
  shifted copies of the whole sequence) and in the routing weights'
  ``+ 1e-20`` against the published ``+ 1e-6`` (5e-7 of a weight); sound
  readings here are 2e-6 to 3e-5.  A token whose fourth and fifth router
  scores lie closer than that would choose another expert on one side and
  read 0.05 or more: none of the sequences here has one, and a new seed that
  finds one has found no fault.  bfloat16 anywhere reads 1e-2 (so does the
  ``int8`` + ``float8`` control, by far: a rehearsal cannot show that, the
  chip does).
- ``GAP_TOL`` 1e-4 on a served token's logit gap below the reference's best:
  a greedy token IS the reference's best unless two logits lie closer than
  the above.
- A stale tail must read at least ``BROKEN`` 0.02, 100 times ``LOGIT_TOL``:
  sound readings of that fault here are 0.1 to 1.
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
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import Telemetry
from perfbench import spec, weights, worker

CONFIG = os.path.join(spec.HERE, "configs", "lfm2-24b-a2b.json")
SEED = 2 ** 31 + 46
LOGIT_TOL, GAP_TOL, BROKEN = 2e-4, 1e-4, 0.02
PAGE, PAGES, PAD = 8, 64, 64
CONV, FULL = gpt_lib.SHORT_CONV, gpt_lib.FULL_ATTENTION
KINDS = (CONV, FULL, CONV, CONV, CONV)


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
    """The reference's logits for a sequence of up to ``PAD`` tokens,
    through ONE compiled shape (no earlier position sees the padding)."""
    with jax.default_matmul_precision("highest"):
        layers = ref.Layers(cfg, SEED)

    def logits(seq):
        toks = np.zeros((PAD,), np.int32)
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


def engine_of(model, params, slots=4, records=None, **kw):
    return DecodeEngine(model, params, EngineConfig(
        num_slots=slots, page_size=PAGE, num_pages=PAGES,
        max_pages_per_seq=8, **kw),
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
                    for r in requests], PAD))


def tails(pools, kinds=KINDS):
    """The convolution layers' tails [conv layers, slots, 2, hidden],
    copied (a view of a leaf would turn the next donation into a copy)."""
    return np.stack([np.array(entry[0], copy=True)
                     for kind, entry in zip(kinds, pools) if kind == CONV])


@pytest.fixture(scope="module")
def decode(model_and_params):
    """The engine's decode step without its sampler: every lane's logits,
    the pools, and the sparse layers' routing histograms [4, experts]."""
    model, params = model_and_params

    def step(tok, pools, tables, pos):
        (logits, pools), aux = model.apply(
            {"params": params}, tok, pools, tables, pos,
            tables[:, 0] < PAGES, method=gpt_lib.GptLM.decode_paged,
            mutable=["routing"])
        counts = jnp.stack([aux["routing"][f"layer{i}"]["counts"][0]
                            for i in range(1, len(KINDS))])
        return logits, pools, counts
    return jax.jit(step)


def forced(engine, decode, seqs, prompts):
    """Lanes seated by ``engine.admit`` (the engine's own prefill and
    landing, its own tables), then decoded token after token with each
    lane's NEXT token taken from ``seqs`` and not from the logits: returns,
    a lane, the logits at positions ``P - 1 .. len(seq) - 2``, the slots,
    and a step's (live lanes, routing histogram).  A lane that has run out
    of tokens, and every lane the engine seated before, rides on as an
    idle row."""
    B = engine.config.num_slots
    out = [[] for _ in seqs]
    at = [p - 1 for p in prompts]
    slots, routed = [], []
    for seq, P in zip(seqs, prompts):
        req = Request(seq[:P], len(seq) - P)
        engine.validate(req)
        slots.append(engine.admit(req))
    pools = engine.pools
    while any(a < len(s) - 1 for a, s in zip(at, seqs)):
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tables = np.full_like(engine._tables, PAGES)
        riding = [i for i, (a, s) in enumerate(zip(at, seqs))
                  if a < len(s) - 1]
        for i in riding:
            tok[slots[i]], pos[slots[i]] = seqs[i][at[i]], at[i]
            tables[slots[i]] = engine._tables[slots[i]]
        logits, pools, counts = decode(jnp.asarray(tok), pools,
                                       jnp.asarray(tables), jnp.asarray(pos))
        routed.append((len(riding), np.asarray(counts)))
        for i in riding:
            out[i].append(np.asarray(logits[slots[i]]))
            at[i] += 1
    engine.pools = pools
    return [np.stack(o) for o in out], slots, routed


# ------------------------------------------------------------- the model


def test_call_is_the_references_logits(want_logits, model_and_params):
    model, params = model_and_params
    assert model.cfg.kinds == KINDS and model.cfg.conv_layers == 4
    assert model.cfg.sparse_layers == (False,) + (True,) * 4
    toks = tokens_of(PAD - 3)
    got = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))[0]
    want = want_logits(toks)
    assert float(np.abs(want).max()) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL
    # the taps matter: without the two older rows the logits move
    cut = dict(params, layer3=dict(params["layer3"], conv_taps=params[
        "layer3"]["conv_taps"].at[:2].set(0)))
    off = model.apply({"params": cut}, jnp.asarray([toks], jnp.int32))[0]
    assert float(jnp.max(jnp.abs(off - want))) > BROKEN


def test_prefill_then_paged_decode_is_the_references_logits(
        want_logits, model_and_params, decode):
    """Prompts SHORTER THAN THE TAPS (one token: an empty tail; two: one
    real row behind a zero), one padded to a bucket (13 of 16) and one that
    fills its bucket, of unequal length across lanes, a fifth slot DEAD all
    the way: its tails stay bit for bit and its rows are routed nowhere."""
    model, params = model_and_params
    engine = engine_of(model, params, slots=5)
    prompts, lengths = (1, 2, 13, 16), (12, 14, 30, 25)
    seqs = [tokens_of(n, 10 + n) for n in lengths]
    # the dead slot's tails hold something a shift would lose
    engine.pools = [
        (entry[0].at[4].set(jax.random.normal(jax.random.key(i), (2, 64))),)
        if kind == CONV else entry
        for i, (kind, entry) in enumerate(zip(KINDS, engine.pools))]
    before = tails(engine.pools)
    got, slots, routed = forced(engine, decode, seqs, prompts)
    assert slots == [0, 1, 2, 3]
    for seq, P, mine in zip(seqs, prompts, got):
        want = want_logits(seq)[P - 1:len(seq) - 1]
        assert mine.shape == want.shape
        assert float(np.abs(mine - want).max()) < LOGIT_TOL, (P, len(seq))
    after = tails(engine.pools)
    assert after.shape == (4, 5, 2, 64) and after.dtype == np.float32
    assert (after[:, 4] == before[:, 4]).all()          # bit for bit
    assert not (after[:, :4] == before[:, :4]).all(axis=(2, 3)).any()
    # one mask for the experts and for the state: every step routes the
    # live lanes' 4 pairs a sparse layer and nothing of a dead row
    for live, counts in routed:
        assert counts.shape == (4, 16)
        assert (counts.sum(axis=1) == 4 * live).all()
    assert routed[0][0] == 4 and routed[-1][0] == 1
    # a convolution layer's entry is its tail and nothing else; an
    # attention layer's the run of pages
    for kind, entry in zip(KINDS, engine.pools):
        if kind == CONV:
            assert [x.shape for x in entry] == [(5, 2, 64)]
        else:
            assert [x.shape for x in entry] == [(PAGES + 1, PAGE, 2 * 16)] * 2


def test_a_slot_reused_by_a_shorter_sequence_starts_from_zeros(
        want_logits, model_and_params, decode, ref, cfg):
    """Through ``engine.step`` (the step-ahead dispatch): a long request
    leaves its tail where it was when it retires; the two-token prompt
    that takes its slot reads none of it (the prefill lands the whole
    row, a zero row before the one real one), while the neighbour still
    decoding rides on as an idle row and keeps its tails bit for bit."""
    model, params = model_and_params
    engine = engine_of(model, params, slots=2)
    first = [Request(tokens_of(40, 1), 6), Request(tokens_of(9, 2), 40)]
    for r in first:
        engine.admit(r)
    while first[0].t_done is None:
        engine.step()
    engine.settle()
    assert engine.free_slots == 1 and engine.stats()["steps_ahead"] > 0
    assert float(gaps(ref, cfg, first[0]).max()) < GAP_TOL
    left = tails(engine.pools)
    assert np.abs(left[:, 0]).min(axis=(1, 2)).max() > 0   # not reset
    seq = tokens_of(12, 3)
    (mine,), (slot,), _ = forced(engine, decode, [seq], [2])
    assert slot == 0
    want = want_logits(seq)[1:11]
    assert float(np.abs(mine - want).max()) < LOGIT_TOL
    assert (tails(engine.pools)[:, 1] == left[:, 1]).all()
    # the test of the test: with the old tenant's tail put back under the
    # newcomer the served tokens are no longer the reference's
    stale = engine_of(model, params, slots=2)
    req = Request(seq[:2], 10)
    stale.admit(req)
    old = iter(left)
    stale.pools = [(entry[0].at[0].set(next(old)[0]),) if kind == CONV
                   else entry for kind, entry in zip(KINDS, stale.pools)]
    while stale.active_slots:
        stale.step()
    assert float(gaps(ref, cfg, req).max()) > BROKEN


# ------------------------------------------------------------ the engine


def test_lanes_live_and_state_bytes_are_a_numpy_count(
        ref, cfg, model_and_params, monkeypatch):
    """Five requests over three slots through ``engine.step``: every slot
    is reused, by longer and by shorter sequences, under the step-ahead
    dispatch, and the served tokens are the reference's.
    ``lanes_live`` and ``state_bytes`` / ``state_slots`` on the
    ``serve_step`` record, on the profiler's retire event and in
    ``engine.stats()`` against a count of the table each dispatch was
    handed and of the sequences the allocator holds; the prefill span's
    ``conv_layers``."""
    from distributed_tensorflow_tpu.utils import tracing
    seen = []
    real = profiling.annotate
    monkeypatch.setattr(profiling, "annotate", lambda name, **stats: (
        seen.append((name, stats)), real(name, **stats))[1])
    model, params = model_and_params
    records = Rows()
    engine = engine_of(model, params, slots=3, records=records)
    per_slot = 4 * 2 * 64 * 4           # four tails of two float32 rows
    assert gpt_lib.state_bytes_per_slot(model.cfg) == per_slot
    counted = []

    def counting(fn):
        def dispatch(tree, tokens, positions, tables, *rest):
            seated = sum(s is not None for s in engine._slots)
            counted.append({
                "lanes_live": int((np.asarray(tables)[:, 0] < PAGES).sum()),
                "state_slots": seated, "state_bytes": seated * per_slot})
            return fn(tree, tokens, positions, tables, *rest)
        return dispatch
    engine._step_fn = counting(engine._step_fn)
    requests = [Request(tokens_of(n, 70 + n), k) for n, k in
                ((30, 5), (4, 9), (17, 3), (2, 6), (9, 4))]
    tracing.install(tracing.Tracer(Telemetry(records), run_id="conv"))
    try:
        serve(engine, *requests)
    finally:
        tracing.clear()
    assert [len(r.tokens) for r in requests] == [5, 9, 3, 6, 4]
    assert float(gaps(ref, cfg, *requests).max()) < GAP_TOL
    assert engine.stats()["steps_ahead"] > engine.stats()["steps_serial"]
    assert engine.stats()["pool_steps_copied"] == 0
    steps = [r for r in records.rows if r.get("kind") == "serve_step"]
    assert len(steps) == len(counted) > 8
    assert [{k: r[k] for k in counted[0]} for r in steps] == counted
    retire = [s for n, s in seen if n == "serve.step.retire"]
    assert [{k: s[k] for k in counted[0]} for s in retire] == counted
    live = [c["lanes_live"] for c in counted]
    assert max(live) == 3 and min(live) >= 1 and len(set(live)) > 1
    # a lane whose budget a step in flight fills rides the next as a dead
    # row while it still holds its slot: fewer live than seated there
    assert all(c["lanes_live"] <= c["state_slots"] for c in counted)
    stats = engine.stats()
    assert stats["lanes_live"] == sum(live)
    assert stats["kv_pool"]["state_bytes_per_slot"] == per_slot
    assert stats["attn_kernel_layers"] == 0             # a CPU
    span = next(r for r in records.rows if r.get("name") == "serve.prefill")
    assert (span["state_layers"], span["conv_layers"],
            span["sparse_layers"], span["row_bytes"]) == (4, 4, 4, 256)


def test_what_the_conv_kind_composes_with_and_what_refuses_it():
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                intermediate_size=48, max_position=64)
    kinds = (CONV, FULL)
    ok = gpt_lib.GptConfig(
        **base, layer_kinds=kinds, short_conv_kernel_dim=3, kv_heads=2,
        qk_head_norm=True, pos_encoding="rope", activation="swiglu",
        norm="rmsnorm", num_experts=4, experts_per_token=2,
        expert_intermediate_size=8, first_dense_layers=1)
    assert ok.has_state_layers and ok.conv_layers == 1
    assert gpt_lib.state_bytes_per_slot(ok) == 2 * 32 * 2     # bfloat16
    assert gpt_lib.kv_row_bytes_per_token(ok) == 2 * 2 * 8 * 2
    pool = gpt_lib.init_kv_pool(ok, 8, 4, num_slots=3)
    assert [x.shape for x in pool[0]] == [(3, 2, 32)]
    assert pool[0][0].dtype == jnp.bfloat16
    cache = gpt_lib.init_kv_cache(ok, 1, 16, dtype="float8_e4m3fn")
    assert cache[0][0].dtype == jnp.bfloat16        # a tail is no page
    assert cache[1][0].dtype == jnp.float8_e4m3fn
    for bad, text in (
            (dict(layer_kinds=kinds), "short_conv_kernel_dim"),
            (dict(short_conv_kernel_dim=3), "short_conv_kernel_dim"),
            (dict(layer_kinds=kinds, short_conv_kernel_dim=1),
             "short_conv_kernel_dim"),
            (dict(layer_kinds=(CONV, "sliding_attention"),
                  short_conv_kernel_dim=3, sliding_window=8),
             "short_conv"),
            (dict(layer_kinds=kinds, short_conv_kernel_dim=3, loop_steps=2),
             "short_conv"),
            (dict(layer_kinds=kinds, short_conv_kernel_dim=3,
                  latent_kv_rank=8, latent_q_rank=8, qk_nope_head_dim=4,
                  qk_rope_head_dim=4, v_head_dim=8, pos_encoding="none"),
             "short_conv"),
            (dict(layer_kinds=kinds, short_conv_kernel_dim=3,
                  attention_window=8), "attention_window"),
            (dict(layer_kinds=kinds, short_conv_kernel_dim=3,
                  attn_int8=True), "attn_int8")):
        with pytest.raises(ValueError, match=text):
            gpt_lib.GptConfig(**{**base, **bad})
    # every other cache path refuses the kind by name, and says where it
    # does lie; the decode step and the prefill want their masks
    with pytest.raises(ValueError, match="short_conv layer's convolution "
                                         "tail"):
        ok.refuse_state_layers("somewhere")
    model = gpt_lib.GptLM(ok)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert set(params["layer0"]) == {"ln_attn", "ln_mlp", "in_proj",
                                     "conv_taps", "out", "mlp_in",
                                     "mlp_gate", "mlp_out"}
    assert params["layer0"]["in_proj"]["kernel"].shape == (32, 96)
    assert params["layer0"]["conv_taps"].shape == (3, 32)
    with pytest.raises(ValueError, match="live="):
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, jnp.zeros((3,), jnp.int32), pool,
            jnp.zeros((3, 2), jnp.int32), jnp.zeros((3,), jnp.int32),
            method=gpt_lib.GptLM.decode_paged), params)
    with pytest.raises(ValueError, match="lengths="):
        jax.eval_shape(lambda p: model.apply(
            {"params": p}, jnp.zeros((1, 8), jnp.int32),
            gpt_lib.init_kv_cache(ok, 1, 8),
            method=gpt_lib.GptLM.prefill), params)
    for on in (dict(spec_k=2), dict(prefill_chunk=4)):
        with pytest.raises(ValueError, match="short_conv"):
            DecodeEngine(model, params, EngineConfig(**on))
    with pytest.raises(ValueError, match="layer_kinds"):
        gpt_lib.infer_arch_from_layer0({"conv_taps": 0, "in_proj": 0})


def test_routing_at_4_of_64_is_balanced(cfg):
    """The router's kernel is drawn like any kernel (the configuration's
    ``assumed``): at the PUBLISHED width, over isotropic unit-rms streams
    (4,096 tokens), every expert gets between 0.5 and 2 times its fair
    share, and the weights of a token sum to 1 (``routed_scaling_factor``
    1, renormalised)."""
    from distributed_tensorflow_tpu.ops import routed_experts
    fair = 4096 * 4 / 64
    kernel = weights.leaf(jax.random.key(46), "router/kernel", (2048, 64),
                          cfg["init"], jnp.float32)
    m = jax.random.normal(jax.random.key(1), (4096, 2048))
    m = m / jnp.sqrt(jnp.mean(m * m, -1, keepdims=True))
    chosen, w = routed_experts.route(m @ kernel, jnp.zeros((64,)), 4, 1.0)
    share = np.bincount(np.asarray(chosen).ravel(), minlength=64) / fair
    assert 0.5 < share.min() and share.max() < 2.0
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
