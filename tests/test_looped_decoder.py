"""A decoder whose stack of layers is applied ``loop_steps`` times over the
SAME weights (``GptConfig.loop_steps``), each application attending its own
keys and values, with a norm on each sublayer's input AND output
(``norm_placement="sandwich"``), the final norm after every application and
an exit gate that reports where a token would leave (``exit_gate``): tiny
widths, 3 loop steps over 4 layers, float32, on the CPU, against a
straight-line forward written out in this file.

Tolerances, with their reasons:

- ``LOGIT_TOL`` 5e-5 on logits of size about 1-3 and ``MASS_TOL`` 5e-6 on
  exit masses in [0, 1]: program and straight-line forward are float32
  throughout and differ in the order of their sums (a scan over the steps
  against a Python loop, the fused qkv projection against three slices,
  attention over gathered flat rows against whole sequences); sound
  readings here are 2e-6 to 8e-6 and 3e-7.  bfloat16 anywhere reads 1e-2.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils.telemetry import Telemetry

R, L, HEADS, HIDDEN, PAGE, PAGES = 3, 4, 4, 32, 4, 12
LOGIT_TOL, MASS_TOL = 5e-5, 5e-6
CFG = gpt_lib.GptConfig(
    vocab_size=64, hidden_size=HIDDEN, num_layers=L, num_heads=HEADS,
    intermediate_size=48, max_position=64, dtype="float32",
    pos_encoding="rope", rope_base=1e6, activation="swiglu", norm="rmsnorm",
    norm_placement="sandwich", loop_steps=R, exit_gate=True)
TOKENS = np.random.default_rng(38).integers(0, 64, (2, 11))


@pytest.fixture(scope="module")
def model_and_params():
    """Seeded weights with every norm scale and bias drawn too (all ones
    and zeros would hide a norm applied in the wrong place)."""
    model = gpt_lib.GptLM(CFG)
    params = model.init(jax.random.key(38), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(39), len(leaves))
    return model, jax.tree.unflatten(tree, [
        x + 0.3 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


# ------------------------------------------------ the straight-line forward


def rms(x, scale):
    x = np.asarray(x, np.float64)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * scale


def rope(x):
    """``x`` [T, heads, D] at positions 0..T-1, pairs (i, i + D/2)."""
    T, half = x.shape[0], x.shape[-1] // 2
    ang = np.arange(T)[:, None] * CFG.rope_base ** (-np.arange(half) / half)
    sin, cos = np.sin(ang)[:, None], np.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def straight_line(params, tokens):
    """One sequence ``tokens`` [T] in float64 NumPy, a Python loop over
    the steps and the layers.  Returns its logits [T, V], the exit masses
    [R, T] and the rotated keys and the values of every (step, layer),
    ``rows[t][l]`` = (k, v) [T, heads * D]."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    T = len(tokens)
    h = p["word_emb"]["embedding"][tokens]
    lam, rows = [], []
    for _ in range(R):
        rows.append([])
        for i in range(L):
            w = p[f"layer{i}"]
            x = rms(h, w["ln_attn"]["scale"])
            qkv = np.einsum("th,hcnd->tcnd", x, w["qkv"]["kernel"]) \
                + w["qkv"]["bias"]
            q, k, v = rope(qkv[:, 0]), rope(qkv[:, 1]), qkv[:, 2]
            rows[-1].append((k.reshape(T, -1), v.reshape(T, -1)))
            s = np.einsum("qnd,knd->nqk", q, k) / np.sqrt(q.shape[-1])
            s = np.where(np.tril(np.ones((T, T), bool))[None], s, -np.inf)
            s = np.exp(s - s.max(-1, keepdims=True))
            ctx = np.einsum("nqk,knd->qnd", s / s.sum(-1, keepdims=True), v)
            a = np.einsum("qnd,ndh->qh", ctx, w["out"]["kernel"]) \
                + w["out"]["bias"]
            h = h + rms(a, w["ln_attn_post"]["scale"])
            x = rms(h, w["ln_mlp"]["scale"])
            gate = x @ w["mlp_gate"]["kernel"]
            m = (gate / (1 + np.exp(-gate)) * (x @ w["mlp_in"]["kernel"])) \
                @ w["mlp_out"]["kernel"]
            h = h + rms(m, w["ln_mlp_post"]["scale"])
        h = rms(h, p["ln_final"]["scale"])
        lam.append(1 / (1 + np.exp(-(h @ p["exit_gate"]["kernel"][:, 0]
                                     + p["exit_gate"]["bias"][0]))))
    left, mass = np.ones(T), []
    for t in range(R - 1):
        mass.append(lam[t] * left)
        left = left * (1 - lam[t])
    logits = h @ p["lm_head"]["kernel"] + p["lm_head"]["bias"]
    return logits, np.stack(mass + [left]), rows


@pytest.fixture(scope="module")
def want(model_and_params):
    return [straight_line(model_and_params[1], row) for row in TOKENS]


# ------------------------------------------------------- the three paths


def test_call_is_the_straight_line_forward(model_and_params, want):
    model, params = model_and_params
    logits, aux = model.apply({"params": params}, jnp.asarray(TOKENS),
                              mutable=["loop"])
    (mass,), (ran,) = aux["loop"]["exit_mass"], aux["loop"]["steps_run"]
    assert mass.shape == (R, 2, 11) and ran.tolist() == [R, R]
    for b in range(2):
        assert np.abs(logits[b] - want[b][0]).max() < LOGIT_TOL
        assert np.abs(mass[:, b] - want[b][1]).max() < MASS_TOL
    assert np.allclose(np.asarray(mass).sum(0), 1.0, atol=1e-6)
    # the tree holds every layer ONCE, each with its four norms
    assert sorted(params) == ["exit_gate", "layer0", "layer1", "layer2",
                              "layer3", "lm_head", "ln_final", "word_emb"]
    assert sorted(k for k in params["layer0"] if k.startswith("ln_")) == [
        "ln_attn", "ln_attn_post", "ln_mlp", "ln_mlp_post"]
    assert gpt_lib.infer_arch_from_layer0(params["layer0"])[
        "norm_placement"] == "sandwich"


def paged(model, params, P, steps=1):
    """``TOKENS``' first ``P`` positions prefilled and landed on the pool as
    the engine lands them (a run of pages a loop step), then ``steps``
    tokens decoded through it.  Returns the last step's (logits, exit
    masses [R, 2]), the pools and the page tables."""
    caches = gpt_lib.init_kv_cache(CFG, 2, 12)
    assert [tuple(x.shape for x in e) for e in caches] == [
        ((R, 2, 12, HEADS, 8),) * 2] * L
    _, caches = model.apply({"params": params}, jnp.asarray(TOKENS[:, :P]),
                            caches, method=gpt_lib.GptLM.prefill)
    tables = jnp.asarray([[0, 1, 2, PAGES], [5, 3, 4, PAGES]])
    pools = gpt_lib.init_kv_pool(CFG, PAGES, PAGE)
    runs = gpt_lib.loop_step_pages(tables[None, :, :3],
                                   jnp.arange(R)[:, None, None],
                                   R * PAGES + 1, R)        # [R, 2, 3]
    pools = [tuple(pool.at[runs.reshape(-1)].set(
        c.reshape(R * 2 * 3, PAGE, -1)) for c, pool in zip(cache, entry))
        for cache, entry in zip(caches, pools)]
    logits = mass = None
    for j in range(steps):
        (logits, pools), aux = model.apply(
            {"params": params}, jnp.asarray(TOKENS[:, P + j]), pools,
            tables, jnp.full((2,), P + j), mutable=["loop"],
            method=gpt_lib.GptLM.decode_paged)
        mass = aux["loop"]["exit_mass"][0][..., 0]
    return (logits, mass), pools, tables


@pytest.mark.parametrize("P", [1, PAGE - 1, PAGE, 7])
def test_prefill_then_paged_decode_is_the_straight_line_forward(
        P, model_and_params, want):
    """Prefill (its returned logits too), then three tokens decoded through
    the pool: the last one's logits and exit masses."""
    model, params = model_and_params
    first, _ = model.apply(
        {"params": params}, jnp.asarray(TOKENS[:, :P]),
        gpt_lib.init_kv_cache(CFG, 2, 12), method=gpt_lib.GptLM.prefill)
    (logits, mass), _, _ = paged(model, params, P, steps=3)
    for b in range(2):
        assert np.abs(first[b] - want[b][0][P - 1]).max() < LOGIT_TOL
        assert np.abs(logits[b] - want[b][0][P + 2]).max() < LOGIT_TOL
        assert np.abs(mass[:, b] - want[b][1][:, P + 2]).max() < MASS_TOL


def test_row_t_l_holds_step_t_of_layer_l_and_nothing_else(
        model_and_params, want):
    """After a prefill of 6 and one decoded token, the pool of layer ``l``
    holds, in loop step ``t``'s run of pages, the straight-line forward's
    keys and values of application (t, l) at positions 0..6, and zeros
    everywhere else, the sentinel's page after the last run among them."""
    model, params = model_and_params
    _, pools, tables = paged(model, params, 6)
    assert [tuple(x.shape for x in e) for e in pools] == [
        ((R * PAGES + 1, PAGE, HIDDEN),) * 2] * L
    for layer, entry in enumerate(pools):
        for which, pool in enumerate(entry):
            held = np.zeros(pool.shape, bool)
            for t in range(R):
                for b in range(2):
                    rows = np.asarray(pool)[
                        t * PAGES + np.asarray(tables[b, :2])].reshape(
                            2 * PAGE, -1)[:7]
                    assert np.abs(rows - want[b][2][t][layer][which][
                        :7]).max() < LOGIT_TOL
                    held[t * PAGES + np.asarray(tables[b, :2])] = True
            held = held.reshape((R * PAGES + 1) * PAGE, -1)
            for b in range(2):     # position 7 of a lane's second page
                for t in range(R):
                    held[(t * PAGES + int(tables[b, 1])) * PAGE + 3] = False
            assert not np.asarray(pool).reshape(held.shape)[~held].any()


@pytest.mark.parametrize("t,layer", [(0, 0), (1, 2), (2, 3)])
def test_zeroing_row_t_l_moves_its_reader_and_what_follows_it(
        t, layer, model_and_params):
    """With the cached rows of (t, layer) zeroed, a decode step writes the
    same new rows as with them intact in every application up to and
    including (t, layer) (whose own new row is projected before it reads),
    and other rows in every application after it: application (t, layer)
    and no earlier one read them."""
    model, params = model_and_params
    _, pools, tables = paged(model, params, 6, steps=0)
    run = slice(t * PAGES, (t + 1) * PAGES)
    zeroed = [tuple(x.at[run].set(0) if i == layer else x for x in e)
              for i, e in enumerate(pools)]
    step = lambda pools: model.apply(        # noqa: E731
        {"params": params}, jnp.asarray(TOKENS[:, 6]), pools, tables,
        jnp.full((2,), 6), method=gpt_lib.GptLM.decode_paged)
    (a, intact), (b, moved) = step(pools), step(zeroed)
    assert np.abs(a - b).max() > 1e-3
    new = lambda pools, s, i: np.asarray(pools[i][0])[   # noqa: E731
        s * PAGES + int(tables[0, 1]), 2]                # position 6
    for s in range(R):
        for i in range(L):
            same = np.array_equal(new(intact, s, i), new(moved, s, i))
            assert same == ((s, i) <= (t, layer)), (s, i)


def test_a_cached_token_holds_a_row_a_step_a_layer():
    one = dataclasses.replace(CFG, loop_steps=1, exit_gate=False)
    layer_row = 2 * HEADS * 8 * 4                  # keys and values, f32
    assert gpt_lib.kv_row_bytes_per_token(one) == L * layer_row
    assert gpt_lib.kv_row_bytes_per_token(CFG) == R * L * layer_row
    assert gpt_lib.kv_row_bytes_per_token(CFG, "float8_e4m3fn") \
        == R * L * layer_row // 4
    # the not-allocated sentinel of a step's run is the pool's LAST page,
    # the one page of zeros after the last run; a write through it goes
    # one past the pool
    pages = jnp.asarray([[0, 11, 12]])
    runs = gpt_lib.loop_step_pages(pages, 2, 37, 3)
    assert runs.tolist() == [[24, 35, 36]]
    assert gpt_lib.written_pages(runs, 37).tolist() == [[24, 35, 37]]


# --------------------------------------------------------- the engine


class Rows:
    def __init__(self):
        self.rows = []

    def log(self, step, **fields):
        self.rows.append(fields)


def engine_of(model, params, records=None, **kw):
    return DecodeEngine(model, params, EngineConfig(
        num_slots=3, page_size=PAGE, num_pages=24, max_pages_per_seq=8,
        **kw), telemetry=None if records is None else Telemetry(records))


def test_the_engine_serves_the_straight_line_forwards_tokens(
        model_and_params, want):
    """Through ``DecodeEngine`` (a padded bucket, runs of pages a loop
    step, a slot reused): every served token is the straight-line
    forward's best at its position, and every dispatch wrote in place."""
    model, params = model_and_params
    engine = engine_of(model, params)
    assert engine.stats()["kv_pool"]["row_bytes_per_token"] \
        == R * L * 2 * HIDDEN * 4
    assert [tuple(x.shape for x in e) for e in engine.pools] == [
        ((R * 24 + 1, PAGE, HIDDEN),) * 2] * L
    for P in (5, 9, 6):
        req = Request(TOKENS[0, :P].tolist(), 11 - P)
        engine.validate(req)
        engine.admit(req)
        while engine.active_slots:
            engine.step()
        seq = np.asarray(req.prompt + req.tokens)
        logits = straight_line(params, seq)[0]
        gap = logits[P - 1:-1].max(-1) - np.take_along_axis(
            logits[P - 1:-1], seq[P:, None], 1)[:, 0]
        assert gap.max() < LOGIT_TOL
    stats = engine.stats()
    assert stats["pool_steps_copied"] == 0
    assert stats["pool_steps_in_place"] == stats["engine_step"] == 13


def test_the_steps_loop_counters_are_a_numpy_count(model_and_params):
    """Two live lanes beside an idle one: every record's three counters
    against the straight-line forward's exit masses at the position each
    lane fed, and the running sums against their total."""
    model, params = model_and_params
    records = Rows()
    engine = engine_of(model, params, records)
    a = Request(TOKENS[0, :7].tolist(), 5)
    b = Request(TOKENS[1, :4].tolist(), 2)
    for r in (a, b):
        engine.admit(r)
    while engine.active_slots:
        engine.step()
    steps = [r for r in records.rows if r.get("kind") == "serve_step"]
    assert len(steps) == 5
    mass = {r.id: straight_line(params, np.asarray(r.prompt + r.tokens))[1]
            for r in (a, b)}
    totals = dict.fromkeys(("loop_steps_run", "loop_tokens",
                            "exit_step_expected_milli"), 0)
    for j, rec in enumerate(steps):
        # step j feeds each lane still decoding its position P - 1 + j
        lanes = [r for r in (a, b) if j < len(r.tokens)]
        expected = sum(
            float(np.arange(1, R + 1) @ mass[r.id][:, len(r.prompt) - 1 + j])
            for r in lanes)
        assert rec["loop_tokens"] == len(lanes)
        assert rec["loop_steps_run"] == R * len(lanes)
        assert abs(rec["exit_step_expected_milli"] - 1e3 * expected) <= 1
        assert 1e3 * len(lanes) < rec["exit_step_expected_milli"] \
            < 1e3 * R * len(lanes)
        for k in totals:
            totals[k] += rec[k]
    assert engine.stats()["loop"] == totals
    assert totals["loop_steps_run"] == R * 7 and totals["loop_tokens"] == 7


def test_retire_region_and_prefill_span_say_what_the_loop_holds(
        model_and_params, monkeypatch):
    from distributed_tensorflow_tpu.utils import profiling, tracing
    seen = []
    real = profiling.annotate
    monkeypatch.setattr(profiling, "annotate", lambda name, **stats: (
        seen.append((name, stats)), real(name, **stats))[1])
    model, params = model_and_params
    spans = Rows()
    tracer = tracing.Tracer(Telemetry(spans), run_id="looped")
    tracing.install(tracer)
    try:
        engine = engine_of(model, params)
        engine.admit(Request(TOKENS[0, :9].tolist(), 2))
        while engine.active_slots:
            engine.step()
    finally:
        tracing.clear()
    retire = [s for n, s in seen if n == "serve.step.retire"]
    assert len(retire) == 2
    assert set(retire[0]) == {"pools_in_place", "sampled_lanes",
                              "table_pages", "table_pages_held",
                              "attn_pages_read", "attn_kernel_layers",
                              "lanes_live", "upload_us", "dispatch_us",
                              "steps_ahead", "steps_serial",
                              "lane_steps_discarded",
                              "loop_steps_run", "loop_tokens",
                              "exit_step_expected_milli"}
    assert retire[0]["loop_steps_run"] == R and retire[0]["loop_tokens"] == 1
    (prefill,) = [r for r in spans.rows if r.get("name") == "serve.prefill"]
    assert (prefill["loop_steps"], prefill["cache_rows"],
            prefill["row_bytes"]) == (R, R * L, R * L * 2 * HIDDEN * 4)
    # a model that walks its stack once: no loop counter, running sums 0
    seen.clear()
    dense = gpt_lib.GptLM(gpt_lib.GptConfig(vocab_size=64, num_layers=1))
    engine = engine_of(dense, dense.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    engine.admit(Request([1, 2, 3], 2))
    while engine.active_slots:
        engine.step()
    timed = ("upload_us", "dispatch_us")        # two clock readings
    assert [{k: v for k, v in s.items() if k not in timed}
            for n, s in seen if n == "serve.step.retire"] == [
        {"pools_in_place": 1, "sampled_lanes": 0, "table_pages": 3 * 8,
         "table_pages_held": 2, "attn_pages_read": 1,
         "attn_kernel_layers": 0, "lanes_live": 1, "steps_ahead": ahead,
         "steps_serial": 1 - ahead, "lane_steps_discarded": 0}
        for ahead in (0, 1)]
    assert engine.stats()["loop"]["loop_tokens"] == 0


# ------------------------------------------ every other cache path refuses

REFUSING = [
    ("GptLM.decode_step", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1,), jnp.int32), [], jnp.int32(0),
        method=gpt_lib.GptLM.decode_step)),
    ("GptLM.decode_chunk", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1,), jnp.int32), method=gpt_lib.GptLM.decode_chunk)),
    ("GptLM.decode_chunk_paged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.decode_chunk_paged)),
    ("GptLM.prefill_chunk_paged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.prefill_chunk_paged)),
    ("GptLM.decode_ragged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1,), jnp.int32), [],
        jnp.zeros((1,), jnp.int32), method=gpt_lib.GptLM.decode_ragged)),
    ("generate_cached", lambda m, p: gpt_lib.generate_cached(
        m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("beam_search_cached", lambda m, p: gpt_lib.beam_search_cached(
        m, p, jnp.zeros((1, 4), jnp.int32), 2, beam_size=2)),
    ("generate_cached_speculative", lambda m, p:
        gpt_lib.generate_cached_speculative(
            m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("generate_cached_speculative_device", lambda m, p:
        gpt_lib.generate_cached_speculative_device(
            m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("make_pipelined_gpt_apply", lambda m, p:
        gpt_lib.make_pipelined_gpt_apply(m.cfg, None, n_micro=1)),
    ("make_interleaved_gpt_apply", lambda m, p:
        gpt_lib.make_interleaved_gpt_apply(m.cfg)),
    ("make_1f1b_gpt_train_step_builder", lambda m, p:
        gpt_lib.make_1f1b_gpt_train_step_builder(m.cfg, n_micro=1)),
    ("DecodeEngine with EngineConfig.spec_k", lambda m, p: DecodeEngine(
        m, p, EngineConfig(spec_k=2))),
    ("DecodeEngine with EngineConfig.prefill_chunk", lambda m, p:
        DecodeEngine(m, p, EngineConfig(prefill_chunk=4))),
]


@pytest.mark.parametrize("path,call", REFUSING, ids=[r[0] for r in REFUSING])
def test_a_path_that_walks_the_stack_once_refuses_by_name(
        path, call, model_and_params):
    with pytest.raises(ValueError) as err:
        call(*model_and_params)
    assert path in str(err.value)
    assert f"GptConfig.loop_steps is {R}" in str(err.value)
    assert "GptLM.decode_paged" in str(err.value)


@pytest.mark.parametrize("fields,message", [
    ({"loop_steps": 0}, "loop_steps must be >= 1"),
    ({"loop_steps": 1}, "exit_gate needs a loop"),
    ({"norm_placement": "both"}, "Unknown norm_placement"),
    ({"attention_window": 8}, "composes with none of"),
    ({"layer_kinds": ("full_attention",) * L}, "composes with none of"),
    ({"num_experts": 4, "experts_per_token": 2,
      "expert_intermediate_size": 8, "norm_placement": "pre"},
     "composes with none of"),
])
def test_config_is_validated(fields, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **fields)


# --------------------------- a config without a loop is what it was

BASE = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=96, max_position=128)
TOYS = {
    "gpt2": {},
    "mistral": dict(pos_encoding="rope", kv_heads=2, activation="swiglu",
                    norm="rmsnorm"),
    "hybrid": dict(
        num_layers=4, pos_encoding="none", norm="rmsnorm",
        activation="swiglu", norm_placement="post", qk_norm=True,
        layer_kinds=("linear_attention",) * 3 + ("full_attention",),
        linear_num_heads=2, linear_key_head_dim=8, linear_value_head_dim=16),
    "latent": dict(
        num_layers=3, pos_encoding="none", activation="swiglu",
        norm="rmsnorm", rope_base=1e6, latent_kv_rank=32, latent_q_rank=48,
        qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
        num_experts=8, experts_per_token=2, expert_intermediate_size=32,
        num_shared_experts=1, routed_scaling_factor=1.8,
        first_dense_layers=1),
}
#: md5 of the parameter tree (paths and shapes) and of four lowered
#: programs of the toy form of each configuration the benchmark had before
#: this one (forward, gradient of ``lm_loss``, and the engine's decode step
#: and whole-bucket prefill as it jits them), taken ON THE PARENT (commit
#: cedd7c8, before ``loop_steps`` existed) by ``fingerprints`` below from a
#: ``git archive`` of it, with ``loop_steps`` and ``norm_placement`` left
#: out of the call.  They hold for this sandbox's jax.  (``forward``,
#: ``step`` and ``prefill`` of the first two are ``tests/test_hybrid_
#: decoder.py``'s ``DENSE_GOLDEN`` too.)  ``step`` and ``prefill``, the
#: programs that take a pool, were renewed in PR 39 (the sentinel's page:
#: see there); ``tree``, ``forward`` and ``gradient`` are that parent's.
#: The latent form's ``prefill`` was renewed in PR 50: its two cache writes
#: are traced before its MLP and not after it (a kind's forms end at the
#: mixer's residual add), the same lines in another order
#: (``tests/test_latent_decoder.py``'s ``LATENT_GOLDEN`` says how checked).
GOLDEN = {
    "gpt2": {"tree": "3e7b6f16765211549f82057f5cca19fc",
             "forward": "7137ce905cc4f0b2dfb44c057f4e4108",
             "gradient": "6f5db47e76edc4c825c9992a6e6b527b",
             "step": "5ec05fc1bc67d297e1edb18f3179c647",
             "prefill": "10f03c9dc28018088d5830757b5eff88"},
    "mistral": {"tree": "16b51521a654c46c4d36da276efcfa0f",
                "forward": "9bf6ceb33b379ccf6fc36228f229c7ae",
                "gradient": "2688c1abe66bf9aca517141d175d5a80",
                "step": "0e7c64373e54105cdfc2d83448201063",
                "prefill": "14102b8e4ea2976c947655f984d4ab96"},
    "hybrid": {"tree": "375fc190a908ae35f971b0d27989e1d8",
               "forward": "6b1d6ebd7623140ac611417744881995",
               "gradient": "cae4005cb5a97232d1b12fd9fea381ad",
               "step": "f8f3b12a6c8351a1e18a6e630f6ebfab",
               "prefill": "e2b5f13e4aece8dfa6f51981fbfaaa79"},
    "latent": {"tree": "5d2de07b3a1f89be550bee1201fd1d22",
               "forward": "00a4194facfe1108845c006fd25d6fba",
               "gradient": "0adc222b3f8690bbc25e41b55cff9bdb",
               "step": "ee52bc1e7e748e4438255a4e7baf6be9",
               "prefill": "d7ea1b26627c315773c554381c58e282"},
}


def fingerprints(name, **extra):
    md5 = lambda s: hashlib.md5(s.encode()).hexdigest()  # noqa: E731
    cfg = gpt_lib.GptConfig(**{**BASE, **TOYS[name], **extra})
    m = gpt_lib.GptLM(cfg)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 8), jnp.int32))["params"]
    eng = DecodeEngine(m, params, EngineConfig(
        num_slots=2, page_size=8, num_pages=16, max_pages_per_seq=4))
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    lane = (i32(), i32()) if cfg.has_state_layers else ()

    def loss(p, t):     # (its name is in the lowered text)
        return gpt_lib.lm_loss(m.apply({"params": p}, t), t)[0]

    return {
        "tree": md5(str([
            (jax.tree_util.keystr(p), x.shape)
            for p, x in jax.tree_util.tree_flatten_with_path(params)[0]])),
        "forward": md5(jax.jit(lambda p, t: m.apply({"params": p}, t)).lower(
            params, i32(2, 16)).as_text()),
        "gradient": md5(jax.jit(jax.grad(loss)).lower(
            params, i32(2, 16)).as_text()),
        "step": md5(eng._step_fn.lower(
            eng._tree, i32(2), i32(2), i32(2, 4), eng.pools, f32(2), i32(2),
            f32(2), i32(2)).as_text()),
        "prefill": md5(eng._prefill_fn(2).lower(
            eng._tree, i32(1, 16), eng.pools, i32(2), *lane).as_text()),
    }


@pytest.mark.parametrize("name", sorted(TOYS))
def test_without_a_loop_tree_and_programs_are_the_parents(name):
    """``loop_steps=1`` and the placement the toy had, said aloud: the
    parent's tree and the parent's lowered text, byte for byte."""
    placement = TOYS[name].get("norm_placement", "pre")
    assert fingerprints(name, loop_steps=1, exit_gate=False,
                        norm_placement=placement) == GOLDEN[name]
