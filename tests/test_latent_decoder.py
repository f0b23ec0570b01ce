"""A decoder of latent-attention layers (one cached row a token, EXPANDED
over a whole sequence and ABSORBED in paged decode) whose MLPs are routed
experts after a leading dense layer (``GptConfig.latent_kv_rank`` /
``num_experts``), against the benchmark's plain reference
(``perfbench/refs/glm-4.7-flash.py``, loaded by path: one reference, not
two) at the rehearsal size of ``perfbench/configs/glm-4.7-flash.json`` (three
layers, 64 wide, 8 experts, 2 a token) in float32.

Tolerances, with their reasons:

- ``LOGIT_TOL`` 2e-4 on logits of size about 1-3: program and reference are
  float32 throughout and differ in the order of their sums (rows sorted by
  expert against a masked loop, fused against separate projections, the
  absorbed against the expanded form); sound readings here are 1e-5 to
  5e-5.  A token whose second and third router scores lie closer than that
  would choose another expert on one side and read 0.1 or more: none of the
  prompts here has one, and a new seed that finds one has found no fault.
  bfloat16 anywhere reads 1e-2.
- ``GAP_TOL`` 1e-4 on a served token's logit gap below the reference's best:
  a greedy token IS the reference's best unless two logits lie closer than
  the above.
"""

import dataclasses
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils.telemetry import Telemetry
from perfbench import spec, weights, worker

CONFIG = os.path.join(spec.HERE, "configs", "glm-4.7-flash.json")
SEED = 2 ** 31 + 35
LOGIT_TOL, GAP_TOL = 2e-4, 1e-4
PAGE = 8


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


def engine_of(model, params, slots=3, records=None, **kw):
    return DecodeEngine(model, params, EngineConfig(
        num_slots=slots, page_size=PAGE, num_pages=96, max_pages_per_seq=12,
        **kw), telemetry=None if records is None else Telemetry(records))


def prompt(n, index=0):
    return np.random.default_rng([SEED, index]).integers(
        0, 512, n).tolist()


def serve(engine, *requests):
    for r in requests:
        engine.validate(r)
        engine.admit(r)
    while engine.active_slots:
        engine.step()
    return [r.tokens for r in requests]


def gaps(ref, cfg, *requests):
    return np.concatenate(ref.served_gaps(
        cfg, SEED, [{"prompt": r.prompt, "served": r.tokens}
                    for r in requests], 96))


def test_call_is_the_references_logits(cfg, ref, model_and_params):
    model, params = model_and_params
    assert model.cfg.kinds == ("latent_attention",) * 3
    assert model.cfg.sparse_layers == (False, True, True)
    toks = prompt(90)
    got = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))[0]
    want = ref.logits(cfg, SEED, toks)
    assert float(np.abs(want).max()) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


def test_expanded_and_absorbed_attention_agree(model_and_params):
    """One block on the same weights: the whole sequence through the
    expanded form against token after token through the absorbed form over
    the paged rows the expanded prefill wrote."""
    model, params = model_and_params
    cfg = model.cfg
    block = gpt_lib.GptBlock(cfg, gpt_lib.LATENT_ATTENTION, False)
    p = {"params": params["layer0"]}
    T, P = 40, 24
    x = jax.random.normal(jax.random.key(3), (2, T, cfg.hidden_size))
    want = block.apply(p, x)
    cache = gpt_lib.init_kv_cache(cfg, 2, P)[0]
    _, *rows = block.apply(p, x[:, :P], *cache,
                           method=gpt_lib.GptBlock.latent_prefill)
    assert [r.shape for r in rows] == [(2, P, 32), (2, P, 8)]
    assert cfg.latent_row_dim == 40
    tables = jnp.full((2, 6), 16, jnp.int32).at[:, :5].set(
        jnp.arange(10).reshape(2, 5))
    pools = [pool.at[tables[:, :3].reshape(-1)].set(r.reshape(6, PAGE, -1))
             for pool, r in zip(gpt_lib.init_kv_pool(cfg, 16, PAGE)[0], rows)]
    def through(blk, x, *where):
        # A form ends at the mixer's residual add; the MLP follows it.
        x, *pools = blk.latent_decode_step_paged(x, *where)
        return blk._mlp(x, True), *pools

    step = jax.jit(lambda x, pools, t: block.apply(
        p, x, *pools, tables, jnp.full((2,), t), method=through))
    for t in range(P, T):
        y, *pools = step(x[:, t:t + 1], pools, t)
        assert float(jnp.max(jnp.abs(y[:, 0] - want[:, t]))) < 2e-5


@pytest.mark.parametrize("P", [1, PAGE - 1, PAGE, PAGE + 1, 45],
                         ids=lambda p: f"prompt{p}")
def test_prefill_then_paged_decode_is_the_full_forward(
        P, cfg, ref, model_and_params):
    """Through ``DecodeEngine``: the prompt padded to its bucket (the
    padding's experts add nothing to another token), the first decode step
    processing token P - 1 again over the row the prefill wrote."""
    model, params = model_and_params
    req = Request(prompt(P, P), 12)
    serve(engine_of(model, params), req)
    assert len(req.tokens) == 12
    assert float(gaps(ref, cfg, req).max()) < GAP_TOL


def test_a_slot_reused_and_idle_neighbours_leave_no_stale_rows(
        cfg, ref, model_and_params):
    """Requests one after the other through ONE slot (the second takes the
    pages the first freed, rows and all), and a live lane beside idle ones,
    serve what the reference serves; every dispatch wrote in place."""
    model, params = model_and_params
    engine = engine_of(model, params, slots=1)
    first, second, third = (Request(prompt(n, n), 10) for n in (50, 21, 37))
    for r in (first, second, third):
        serve(engine, r)
    assert float(gaps(ref, cfg, first, second, third).max()) < GAP_TOL
    stats = engine.stats()
    assert stats["pool_steps_copied"] == 0
    assert stats["pool_steps_in_place"] == 30
    alone = Request(prompt(37, 37), 10)
    serve(engine_of(model, params, slots=3), alone)
    assert alone.tokens == third.tokens


def test_pool_is_one_row_a_token_and_the_allocator_says_its_bytes(
        model_and_params):
    """The row's two parts (latent, rotated key), no head axis, no values."""
    model, params = model_and_params
    cfg = model.cfg
    pools = gpt_lib.init_kv_pool(cfg, 96, PAGE)
    assert [tuple(x.shape for x in entry) for entry in pools] == [
        ((97, PAGE, 32), (97, PAGE, 8))] * 3
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    # (pages + the sentinel's) x page x (latent + rotary key) x itemsize
    # x layers
    assert total == 97 * PAGE * 40 * 4 * 3
    engine = engine_of(model, params)
    assert engine.stats()["kv_pool"]["row_bytes_per_token"] == 40 * 4 * 3
    fp8 = engine_of(model, params, quantize="int8", kv_dtype="float8")
    assert fp8.stats()["kv_pool"]["row_bytes_per_token"] == 40 * 3
    assert {x.dtype for x in jax.tree.leaves(fp8.pools)} == {
        jnp.dtype(jnp.float8_e4m3fn)}
    # the published widths: 576 entries x 2 B x 8 layers; mistral-7b as cut
    glm = spec.load_json(CONFIG)
    assert gpt_lib.kv_row_bytes_per_token(worker.gpt_config(
        {"config": glm, "config_file": CONFIG})) == 9216
    mistral = os.path.join(spec.HERE, "configs", "mistral-7b.json")
    assert gpt_lib.kv_row_bytes_per_token(worker.gpt_config(
        {"config": spec.load_json(mistral),
         "config_file": mistral})) == 65536


#: Latent layers at shapes that PACK the rotated keys (a rope of 64 in
#: pages of 16: two tokens a row of 128 lanes) and that the latent kernel
#: can walk (latents of 128, whole lane tiles).
PACKED = dict(vocab_size=64, hidden_size=40, num_layers=2, num_heads=5,
              intermediate_size=48, max_position=256, dtype="float32",
              pos_encoding="none", activation="swiglu", norm="rmsnorm",
              rope_base=1e6, latent_kv_rank=128, latent_q_rank=48,
              qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=96,
              attention_backend="pallas")


def test_rotated_keys_two_a_row_serve_the_full_forward_on_both_forms(
        monkeypatch):
    """At the published rope of 64 a page of 16 holds its rotated keys in 8
    rows of 128 lanes (PR 47), in the row's own bytes.  Through
    ``DecodeEngine`` (the prefill lands a prompt's keys packed, the step
    rewrites one token's half of a row) the plain form serves the argmax
    of the model's full forward, prompts that end inside a page, on its
    last row and in a row's second half; and steered onto the latent
    kernel (the TPU interpreter) the same engine serves the same tokens
    and counts its layers."""
    from distributed_tensorflow_tpu.ops.pallas import (
        paged_attention as paged_ops)
    model = gpt_lib.GptLM(gpt_lib.GptConfig(**PACKED))
    params = model.init(jax.random.key(47),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    econf = EngineConfig(num_slots=3, page_size=16, num_pages=24,
                         max_pages_per_seq=6)

    def served(engine):
        reqs = [Request(np.random.default_rng(n).integers(0, 64, n).tolist(),
                        9) for n in (5, 16, 29, 41)]
        serve(engine, *reqs[:3])
        serve(engine, reqs[3])                       # freed pages, reused
        return reqs

    engine = DecodeEngine(model, params, econf)
    assert [tuple(x.shape for x in e) for e in engine.pools] == [
        ((25, 16, 128), (25, 8, 128))] * 2
    assert engine.stats()["kv_pool"]["row_bytes_per_token"] == 192 * 4 * 2
    plain = served(engine)
    assert engine.stats()["attn_kernel_layers"] == 0
    full = jax.jit(lambda t: model.apply({"params": params}, t)[0])
    for r in plain:
        seq = r.prompt + r.tokens
        want = np.asarray(full(jnp.asarray([seq + [0] * (64 - len(seq))])))
        assert r.tokens == want[len(r.prompt) - 1:len(seq) - 1].argmax(
            -1).tolist()
    monkeypatch.setattr(
        gpt_lib, "paged_kernel_attends",
        lambda cfg, pool, key_pool=None: paged_ops.supports_latent(
            pool, key_pool))
    monkeypatch.setattr(paged_ops, "_CHUNK_MAX", 128)
    engine = DecodeEngine(model, params, econf)
    assert [r.tokens for r in served(engine)] == [r.tokens for r in plain]
    stats = engine.stats()
    assert stats["attn_kernel_layers"] == 2 * (
        stats["steps_ahead"] + stats["steps_serial"]) > 0


def test_kernel_layers_count_a_latent_layer_by_what_the_code_observes(
        monkeypatch):
    """``paged_kernel_layers`` (the record's ``attn_kernel_layers``) counts
    a LATENT layer where its step takes the kernel: a Pallas configuration,
    a TPU backend and pools the kernel can walk.  A CPU, another backend of
    attention, float8 rows (the lower-precision control) and rotated keys a
    row a token (the rehearsal's shapes) keep the plain form and count 0."""
    cfg = gpt_lib.GptConfig(**{**PACKED, "dtype": "bfloat16"})
    pools = lambda cfg, page=16, dtype=None: jax.eval_shape(  # noqa: E731
        lambda: gpt_lib.init_kv_pool(cfg, 24, page, dtype=dtype))
    assert gpt_lib.paged_kernel_layers(cfg, pools(cfg)) == 0     # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gpt_lib.paged_kernel_layers(cfg, pools(cfg)) == 2
    assert gpt_lib.paged_kernel_attends(cfg, *pools(cfg)[0])
    assert gpt_lib.paged_kernel_layers(
        cfg, pools(cfg, dtype=jnp.float8_e4m3fn)) == 0
    xla = dataclasses.replace(cfg, attention_backend="xla")
    assert gpt_lib.paged_kernel_layers(xla, pools(xla)) == 0
    narrow = dataclasses.replace(cfg, qk_nope_head_dim=88,
                                 qk_rope_head_dim=8)
    assert [x.shape for x in pools(narrow, 8)[0]] == [(25, 8, 128),
                                                      (25, 8, 8)]
    assert gpt_lib.paged_kernel_layers(narrow, pools(narrow, 8)) == 0


def test_the_lower_precision_control_runs_on_stacked_experts_and_rows(
        cfg, ref, model_and_params):
    """int8 weights (the experts' stacked kernels too) and float8 rows: the
    engine serves, and reads further from the reference than the sound
    program does."""
    model, params = model_and_params
    from distributed_tensorflow_tpu.ops.quant import prepare_inference_tree
    tree = prepare_inference_tree(params, "int8")
    assert tree["layer1"]["experts_gate"]["q"].dtype == jnp.int8
    assert tree["layer1"]["experts_gate"]["q"].shape == (8, 64, 32)
    sound, control = Request(prompt(45, 1), 12), Request(prompt(45, 1), 12)
    serve(engine_of(model, params), sound)
    serve(engine_of(model, params, quantize="int8", kv_dtype="float8"),
          control)
    assert len(control.tokens) == 12
    assert float(gaps(ref, cfg, sound).max()) < GAP_TOL


def chosen_by_position(model, params, seq):
    """[sparse layers, positions, k]: the experts each position of ``seq``
    chooses, from the router's logits in the model's own full forward (a
    causal model: no position's routing depends on what follows), sorted
    in NumPy."""
    cfg = model.cfg
    _, aux = model.apply({"params": params}, jnp.asarray([seq]),
                         mutable=["intermediates"],
                         capture_intermediates=lambda m, _: isinstance(
                             m, gpt_lib.nn.Dense) and m.name == "router")
    return np.stack([
        np.argsort(-np.asarray(aux["intermediates"][f"layer{i}"]["router"][
            "__call__"][0], np.float64), axis=-1)[:, :cfg.experts_per_token]
        for i, sparse in enumerate(cfg.sparse_layers) if sparse])


def test_the_steps_counters_are_a_numpy_count_of_its_routing(
        model_and_params):
    """Two live lanes beside an idle one: the record's four counters against
    a count made from the full forward of what each lane holds."""
    model, params = model_and_params
    records = Rows()
    engine = engine_of(model, params, slots=3, records=records)
    a, b = Request(prompt(19, 1), 6), Request(prompt(33, 2), 3)
    for r in (a, b):
        engine.admit(r)
    while engine.active_slots:
        engine.step()
    steps = [r for r in records.rows if r.get("kind") == "serve_step"]
    assert len(steps) == 6
    chosen = {r.id: chosen_by_position(model, params, r.prompt + r.tokens)
              for r in (a, b)}
    totals = dict.fromkeys(("experts_touched", "expert_slots",
                            "expert_tokens_max", "routed_tokens"), 0)
    for j, rec in enumerate(steps):
        # step j feeds each lane still decoding its position P - 1 + j
        lanes = [r for r in (a, b) if j < len(r.tokens)]
        hist = np.zeros((2, 8), np.int64)
        for r in lanes:
            for layer in range(2):
                hist[layer, chosen[r.id][layer, len(r.prompt) - 1 + j]] += 1
        want = {"experts_touched": int((hist > 0).sum()),
                "expert_slots": 2 * 8, "expert_tokens_max": int(hist.max()),
                "routed_tokens": len(lanes) * 2 * 2}
        assert {k: rec[k] for k in want} == want
        for k, v in want.items():
            totals[k] += v
    assert engine.stats()["moe"] == totals
    assert steps[0]["routed_tokens"] == 8 and steps[-1]["routed_tokens"] == 4


def untimed(stats: dict) -> dict:
    """A retire event's stats without the stage's two clock readings
    (PR 40), which are whole microseconds and differ from run to run."""
    assert all(isinstance(stats[k], int) and stats[k] >= 0
               for k in ("upload_us", "dispatch_us"))
    return {k: v for k, v in stats.items()
            if k not in ("upload_us", "dispatch_us")}


def test_retire_region_carries_the_counters_for_a_sparse_model_only(
        model_and_params, monkeypatch):
    from distributed_tensorflow_tpu.utils import profiling
    seen = []
    real = profiling.annotate
    monkeypatch.setattr(profiling, "annotate", lambda name, **stats: (
        seen.append((name, stats)), real(name, **stats))[1])
    model, params = model_and_params
    serve(engine_of(model, params), Request(prompt(9), 2))
    retire = [s for n, s in seen if n == "serve.step.retire"]
    assert len(retire) == 2
    assert set(retire[0]) == {"pools_in_place", "sampled_lanes",
                              "table_pages", "table_pages_held",
                              "attn_pages_read", "attn_kernel_layers",
                              "lanes_live", "upload_us", "dispatch_us",
                              "steps_ahead", "steps_serial",
                              "lane_steps_discarded",
                              "experts_touched", "expert_slots",
                              "expert_tokens_max", "routed_tokens"}
    assert retire[0]["pools_in_place"] == 1
    assert retire[0]["expert_slots"] == 16 and retire[0]["routed_tokens"] == 4
    seen.clear()
    dense = gpt_lib.GptLM(gpt_lib.GptConfig(vocab_size=64, num_layers=1))
    dparams = dense.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    engine = engine_of(dense, dparams)
    serve(engine, Request([1, 2, 3], 2))
    assert [untimed(s) for n, s in seen if n == "serve.step.retire"] == [
        {"pools_in_place": 1, "sampled_lanes": 0, "table_pages": 3 * 12,
         "table_pages_held": 1, "attn_pages_read": 1,
         "attn_kernel_layers": 0, "lanes_live": 1, "steps_ahead": ahead,
         "steps_serial": 1 - ahead, "lane_steps_discarded": 0}
        for ahead in (0, 1)]
    assert engine.stats()["moe"]["expert_slots"] == 0


def test_routing_is_balanced_under_the_configurations_draw(cfg):
    """The router drawn as the layout says, over the rehearsal's traffic
    (prompts of 32-96 random tokens): every expert of every sparse layer
    gets between 0.5 and 2 times its fair share."""
    gcfg = worker.gpt_config({"config": cfg, "config_file": CONFIG})
    model = gpt_lib.GptLM(gcfg)
    counts = jax.jit(lambda p, t: model.apply(
        {"params": p}, t, mutable=["routing"])[1]["routing"])
    maker = weights.Maker(cfg)
    for seed in (SEED, 7):
        params = weights.program_tree(seed, maker)
        hist = np.zeros((2, 8), np.int64)
        for i, n in enumerate((32, 48, 64, 96)):
            toks = np.random.default_rng([seed, i]).integers(0, 512, (3, n))
            got = counts(params, jnp.asarray(toks))
            for j, name in enumerate(("layer1", "layer2")):
                hist[j] += np.asarray(got[name]["counts"][0])
        fair = hist.sum(axis=1, keepdims=True) / 8
        assert hist.sum() == 2 * 2 * 3 * (32 + 48 + 64 + 96)
        assert (hist > 0.5 * fair).all() and (hist < 2.0 * fair).all(), hist


REFUSING = [
    ("GptLM.decode_step", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1,), jnp.int32), [], jnp.int32(0),
        method=gpt_lib.GptLM.decode_step)),
    ("GptLM.decode_chunk", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1,), jnp.int32), method=gpt_lib.GptLM.decode_chunk)),
    ("GptLM.decode_ragged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1,), jnp.int32), [],
        jnp.zeros((1,), jnp.int32), method=gpt_lib.GptLM.decode_ragged)),
    ("GptLM.decode_chunk_paged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.decode_chunk_paged)),
    ("generate_cached", lambda m, p: gpt_lib.generate_cached(
        m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("beam_search_cached", lambda m, p: gpt_lib.beam_search_cached(
        m, p, jnp.zeros((1, 4), jnp.int32), 2, beam_size=2)),
    ("make_pipelined_gpt_apply", lambda m, p:
        gpt_lib.make_pipelined_gpt_apply(m.cfg, None, n_micro=1)),
    ("DecodeEngine with EngineConfig.spec_k", lambda m, p: DecodeEngine(
        m, p, EngineConfig(spec_k=2))),
    ("DecodeEngine with EngineConfig.prefill_chunk", lambda m, p:
        DecodeEngine(m, p, EngineConfig(prefill_chunk=4))),
]


@pytest.mark.parametrize("path,call", REFUSING, ids=[r[0] for r in REFUSING])
@pytest.mark.parametrize("what", ["latent", "sparse"])
def test_a_path_that_carries_neither_refuses_by_name(what, path, call,
                                                     model_and_params):
    model, params = model_and_params
    if what == "sparse":    # routed experts behind plain attention
        gcfg = gpt_lib.GptConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=48, activation="swiglu", norm="rmsnorm",
            num_experts=4, experts_per_token=2, expert_intermediate_size=16)
        model, params = gpt_lib.GptLM(gcfg), {}
        named = "GptConfig.num_experts is 4"
    else:
        named = "GptConfig.latent_kv_rank is 32"
    with pytest.raises(ValueError) as err:
        call(model, params)
    assert path.split(" /")[0] in str(err.value) and named in str(err.value)
    assert "GptLM.decode_paged" in str(err.value)


@pytest.mark.parametrize("fields,message", [
    ({"latent_q_rank": 0}, "latent_kv_rank needs"),
    ({"v_head_dim": 24}, "one head size"),
    ({"pos_encoding": "rope"}, "composes with none of"),
    ({"kv_heads": 2}, "composes with none of"),
    ({"experts_per_token": 9}, "num_experts needs"),
    ({"activation": "gelu"}, "gated SiLU"),
    ({"first_dense_layers": 4}, "num_experts needs"),
])
def test_config_is_validated_like_layer_kinds(fields, message,
                                              model_and_params):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(model_and_params[0].cfg, **fields)


def test_default_config_keeps_its_tree_and_its_kinds():
    """``GptConfig()`` names no latent rank and no expert: the parent's
    leaves, the parent's kinds, (keys, values) pool entries of a flat row
    (4 heads of 32 side by side; PR 37) with the sentinel's page after the
    allocator's four (PR 39), and the parent's bytes a token."""
    cfg = gpt_lib.GptConfig()
    assert cfg.kinds == ("full_attention",) * 4
    assert cfg.sparse_layers == (False,) * 4 and cfg.rope_base == 10000.0
    tree = jax.eval_shape(lambda: gpt_lib.GptLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sorted(tree["layer0"]) == ["ln_attn", "ln_mlp", "mlp_in",
                                      "mlp_out", "out", "qkv"]
    assert sorted(tree) == ["layer0", "layer1", "layer2", "layer3",
                            "lm_head", "ln_final", "pos_emb", "word_emb"]
    pools = gpt_lib.init_kv_pool(cfg, 4, 8)
    assert [tuple(x.shape for x in e) for e in pools] == [
        ((4 + 1, 8, 128), (4 + 1, 8, 128))] * 4
    assert gpt_lib.kv_row_bytes_per_token(cfg) == 4 * 2 * 4 * 32 * 2


#: md5 of the engine's lowered decode step and whole-bucket prefill at the
#: rehearsal size as the configuration's file gives it (bfloat16), taken ON
#: THE PARENT of PR 37 (commit 6906e1f) from a ``git archive`` of it by this
#: function.  That PR holds a K/V pool's row flat and leaves the latent
#: branch of ``_rows_entry`` alone: this cell's programs were its control.
#: Renewed in PR 39, which gives the latent pools the sentinel's page of
#: zeros as it gives every paged pool (a sentinel entry of the table read
#: ANOTHER lane's latents before; ``tests/test_sentinel_page.py``).  PR 47
#: left them as they were: at the rehearsal's rope of 8 the rotated keys'
#: pool stays a row a token, and the plain form lowers as it did (at a rope
#: of 64 that pool holds two tokens a row of 128 lanes and the CPU's
#: lowering differs by that shape alone:
#: ``test_rotated_keys_two_a_row_serve_the_full_forward_on_both_forms``).
#: PR 50 renewed the PREFILL's two: a kind's forms end at the mixer's
#: residual add and ``_prefill_layers`` runs the MLP behind them, so the
#: latent prefill's two cache writes are traced before its MLP and not after
#: it: the same lines in another order (line for line equal as multisets
#: with the SSA names erased, checked against a ``git archive`` of a22fa08);
#: the step's two stand as they were.
#: They hold for this sandbox's jax.
LATENT_GOLDEN = {
    "": ("4c441d479ce85551e7cba276e1441bf1",
         "f603613b02381fdfe18b58b27bbcca87"),
    "float8": ("991e2639716c05df18e3c08ada3c31fd",
               "c276318b8220cc221acd5dc8662893b8"),
}


@pytest.mark.parametrize("kv_dtype", sorted(LATENT_GOLDEN),
                         ids=["bfloat16", "float8"])
def test_the_latent_cells_programs_are_the_parents(kv_dtype):
    config = spec.load_json(CONFIG)
    config = spec.deep_update(config, config["rehearsal"])
    model = gpt_lib.GptLM(worker.gpt_config(
        {"config": config, "config_file": CONFIG}))
    params = jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype), jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]))
    eng = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=8, num_pages=16, max_pages_per_seq=4,
        kv_dtype=kv_dtype))
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    step = eng._step_fn.lower(
        eng._tree, i32(2), i32(2), i32(2, 4), eng.pools, f32(2), i32(2),
        f32(2), i32(2))
    prefill = eng._prefill_fn(2).lower(eng._tree, i32(1, 16), eng.pools,
                                       i32(2))
    assert tuple(hashlib.md5(x.as_text().encode()).hexdigest()
                 for x in (step, prefill)) == LATENT_GOLDEN[kv_dtype]
