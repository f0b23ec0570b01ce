"""A decoder whose layers are linear-attention (gated delta rule) and
full-attention in one model (``GptConfig.layer_kinds``), against the
benchmark's plain reference (``perfbench/refs/olmo-hybrid-7b.py``, loaded by
path: one reference, not two) at the rehearsal size of
``perfbench/configs/olmo-hybrid-7b.json`` (one period, 64 wide) in float32.

Tolerances, with their reasons:

- ``LOGIT_TOL`` 2e-4 on logits of size about 1-3: program and reference
  are float32 throughout and differ in the order of their sums (chunked
  against token-by-token, fused against separate projections); sound
  readings here are 1e-5 to 4e-5.  bfloat16 anywhere reads 1e-2.
- ``GAP_TOL`` 1e-4 on a served token's logit gap below the reference's
  best: a greedy token IS the reference's best unless two logits lie
  closer than the above, so the gap is 0 or a near-tie's size.
- a seated state zeroed after prefill must read at least ``BROKEN`` 0.05,
  500 times ``GAP_TOL``: sound readings of that fault here are 0.3 to 2.
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

CONFIG = os.path.join(spec.HERE, "configs", "olmo-hybrid-7b.json")
SEED = 2 ** 31 + 29
LOGIT_TOL, GAP_TOL, BROKEN = 2e-4, 1e-4, 0.05
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


def engine_of(model, params, slots=3, **kw):
    return DecodeEngine(model, params, EngineConfig(
        num_slots=slots, page_size=PAGE, num_pages=96, max_pages_per_seq=12,
        **kw))


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
    assert model.cfg.kinds == ("linear_attention",) * 3 + (
        "full_attention",)
    toks = prompt(90)
    got = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))[0]
    want = ref.logits(cfg, SEED, toks)
    assert float(np.abs(want).max()) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


@pytest.mark.parametrize("P", [1, PAGE - 1, PAGE, PAGE + 1, 45],
                         ids=lambda p: f"prompt{p}")
def test_padded_prefill_then_paged_decode_is_the_full_forward(
        P, cfg, ref, model_and_params):
    """Through ``DecodeEngine``: the prompt padded to its bucket, the lane
    seated with the state after P - 1 tokens, token P - 1 processed again
    by the first decode step; 45 tokens are buckets of their own."""
    model, params = model_and_params
    req = Request(prompt(P, P), 12)
    serve(engine_of(model, params), req)
    assert len(req.tokens) == 12
    assert float(gaps(ref, cfg, req).max()) < GAP_TOL


def test_a_slot_reused_and_idle_neighbours_change_nothing(
        cfg, ref, model_and_params):
    """Two requests one after the other through ONE slot, and a live lane
    beside idle ones, serve what each serves alone in a fresh engine; a
    retired lane's state is left where it was (overwritten at the next
    admission, never reset by a dispatch of its own)."""
    model, params = model_and_params
    alone = [serve(engine_of(model, params, slots=1),
                   Request(prompt(n, n), 10))[0] for n in (30, 19)]
    engine = engine_of(model, params, slots=1)
    first = serve(engine, Request(prompt(30, 30), 10))[0]
    # (Copies, not views: the pools are donated to the next dispatch, and
    # a view of a leaf would turn that donation into a copy.)
    state_left = np.array(engine.pools[0][0], copy=True)
    assert np.abs(state_left).max() > 0           # not reset on retire
    second = serve(engine, Request(prompt(19, 19), 10))[0]
    assert [first, second] == alone
    wide = engine_of(model, params, slots=3)
    wide.admit(Request(prompt(30, 30), 10))       # slot 0; 1 and 2 idle
    before = [np.array(x, copy=True) for x in wide.pools[0]]
    wide.step()
    after = [np.array(x, copy=True) for x in wide.pools[0]]
    assert wide.stats()["pool_steps_copied"] == 0
    for b, a in zip(before, after):
        assert (b[1:] == a[1:]).all() and not (b[0] == a[0]).all()
    both = engine_of(model, params, slots=3)
    assert serve(both, Request(prompt(30, 30), 10),
                 Request(prompt(19, 19), 10)) == alone


def test_a_zeroed_state_fails_the_comparison(cfg, ref, model_and_params):
    """The test of the test, and of the weights' draw: were the state dead
    (decayed to nothing, or never read), zeroing it would change nothing."""
    model, params = model_and_params
    engine = engine_of(model, params, slots=1)
    req = Request(prompt(60, 1), 12)
    engine.admit(req)
    engine.pools = [tuple(jnp.zeros_like(x) for x in entry)
                    if kind == gpt_lib.LINEAR_ATTENTION else entry
                    for kind, entry in zip(model.cfg.kinds, engine.pools)]
    while engine.active_slots:
        engine.step()
    assert float(gaps(ref, cfg, req).max()) > BROKEN


def test_the_draw_leaves_the_state_alive(cfg):
    """A head's decay over 64 tokens is far from 0 and from 1 at the
    residual stream's rms of both ends of the stack."""
    lay = weights.layout(cfg)
    p = weights.layer_leaves(jax.random.key(1), 0, cfg["model"], cfg["init"],
                             jnp.float32, lay.layer(cfg["model"],
                                                    "linear_attention"))
    for rms in (1.0, 6.0):
        x = rms * jax.random.normal(jax.random.key(2), (64, 64))
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            x @ p["a_proj/kernel"] + p["dt_bias"])
        over64 = np.exp(np.asarray(jnp.sum(g, 0)))
        assert 0.03 < over64.min() and over64.max() < 0.9, over64
        assert 0.15 < np.median(over64) < 0.75


def test_state_is_accounted_with_the_pages(model_and_params):
    model, params = model_and_params
    per_slot = gpt_lib.state_bytes_per_slot(model.cfg)
    # three linear layers: 2 heads of 48 x 24 float32, and 3 x 192 of tail
    assert per_slot == 3 * (2 * 48 * 24 * 4 + 3 * 2 * (24 + 24 + 48) * 4)
    records = worker.Records()
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=3, page_size=PAGE, num_pages=96, max_pages_per_seq=12),
        telemetry=Telemetry(records))
    serve(engine, Request(prompt(20, 2), 3), Request(prompt(9, 3), 5))
    stats = engine.stats()
    assert stats["state_slots"] == 0 and stats["state_bytes"] == 0
    pool = stats["kv_pool"]
    assert pool["state_bytes_per_slot"] == per_slot
    assert pool["state_bytes_peak"] == 2 * per_slot
    steps = records.kind("serve_step")
    assert steps[0]["state_slots"] == 2
    assert steps[0]["state_bytes"] == 2 * per_slot
    assert steps[-1]["state_slots"] == 1


REFUSED = [
    ("GptLM.decode_step", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1,), jnp.int32),
        gpt_lib.init_kv_cache(m.cfg, 1, 8), jnp.int32(0),
        method=gpt_lib.GptLM.decode_step)),
    ("GptLM.decode_ragged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1,), jnp.int32),
        gpt_lib.init_kv_cache(m.cfg, 1, 8), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.decode_ragged)),
    ("GptLM.decode_chunk", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32),
        gpt_lib.init_kv_cache(m.cfg, 1, 8), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.decode_chunk)),
    ("GptLM.decode_chunk_paged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.decode_chunk_paged)),
    ("GptLM.prefill_chunk_paged", lambda m, p: m.apply(
        {"params": p}, jnp.zeros((1, 2), jnp.int32), [],
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
        method=gpt_lib.GptLM.prefill_chunk_paged)),
    ("generate_cached", lambda m, p: gpt_lib.generate_cached(
        m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("beam_search_cached", lambda m, p: gpt_lib.beam_search_cached(
        m, p, jnp.zeros((1, 4), jnp.int32), 2, beam_size=2)),
    ("generate_cached_speculative",
     lambda m, p: gpt_lib.generate_cached_speculative(
         m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("generate_cached_speculative_device",
     lambda m, p: gpt_lib.generate_cached_speculative_device(
         m, p, jnp.zeros((1, 4), jnp.int32), 2)),
    ("make_pipelined_gpt_apply",
     lambda m, p: gpt_lib.make_pipelined_gpt_apply(m.cfg, None, n_micro=1)),
    ("make_interleaved_gpt_apply",
     lambda m, p: gpt_lib.make_interleaved_gpt_apply(m.cfg)),
    ("make_1f1b_gpt_train_step_builder",
     lambda m, p: gpt_lib.make_1f1b_gpt_train_step_builder(
         m.cfg, n_micro=1)),
    ("EngineConfig.spec_k", lambda m, p: engine_of(m, p, spec_k=2)),
    ("EngineConfig.prefill_chunk",
     lambda m, p: engine_of(m, p, prefill_chunk=4)),
]


@pytest.mark.parametrize("path,call", REFUSED, ids=[r[0] for r in REFUSED])
def test_a_path_without_the_state_refuses_by_name(path, call,
                                                  model_and_params):
    with pytest.raises(ValueError) as e:
        call(*model_and_params)
    assert path in str(e.value) and "layer_kinds" in str(e.value)


@pytest.mark.parametrize("call,names", [
    (lambda m, p: gpt_lib.split_params_for_pipeline(p, 2, 4),
     ("split_params_for_pipeline", "layer_kinds")),
    (lambda m, p: gpt_lib.infer_arch_from_layer0(p["layer0"]),
     ("infer_arch_from_layer0", "layer_kinds")),
    (lambda m, p: m.apply({"params": p}, jnp.zeros((1, 8), jnp.int32),
                          gpt_lib.init_kv_cache(m.cfg, 1, 8),
                          method=gpt_lib.GptLM.prefill),
     ("GptLM.prefill", "lengths")),
    (lambda m, p: m.apply({"params": p}, jnp.zeros((1,), jnp.int32),
                          gpt_lib.init_kv_pool(m.cfg, 4, 8, num_slots=1),
                          jnp.zeros((1, 2), jnp.int32),
                          jnp.zeros((1,), jnp.int32),
                          method=gpt_lib.GptLM.decode_paged),
     ("GptLM.decode_paged", "live")),
    (lambda m, p: gpt_lib.init_kv_pool(m.cfg, 4, 8),
     ("init_kv_pool", "num_slots")),
    (lambda m, p: dataclasses.replace(m.cfg, layer_kinds=("full_attention",)),
     ("layer_kinds", "num_layers")),
    (lambda m, p: dataclasses.replace(m.cfg, attention_window=8),
     ("layer_kinds", "attention_window")),
    (lambda m, p: dataclasses.replace(m.cfg, linear_num_heads=0),
     ("linear_attention", "linear_num_heads")),
], ids=["pipeline_split", "infer_arch", "prefill_lengths", "decode_live",
        "pool_slots", "kinds_length", "window", "linear_sizes"])
def test_what_the_state_needs_is_asked_for_by_name(call, names,
                                                   model_and_params):
    with pytest.raises(ValueError) as e:
        call(*model_and_params)
    assert all(n in str(e.value) for n in names)


# ------------------------------------------ a dense config is what it was

#: md5 of a dense model's parameter tree (paths, shapes, sums) and of its
#: lowered programs, taken ON THE PARENT (commit d1e6394, before
#: ``layer_kinds`` existed) by this file's ``dense_fingerprints`` from a
#: ``git archive`` of it.  They hold for this sandbox's jax.  ``step`` and
#: ``prefill`` are the engine's programs as it jits them, and were renewed
#: when it began to donate its pools (each pool argument gained
#: ``tf.aliasing_output``); the same bodies jitted without donation are
#: still that parent's text (``prefill_undonated``: its ``prefill``).  Both
#: ``step`` texts were renewed once more when the sampler inside them
#: stopped gathering the vocabulary and went under a ``cond``
#: (``sample_logits_dynamic``; ``tests/test_sampler.py`` holds its tokens to
#: the old body's).  Every program that takes a pool was renewed in PR 37,
#: which holds a K/V pool's row flat ([pages, page, G * D]: the pool
#: arguments, the scatter's update and the gathered rows' reshape change
#: and nothing else; ``tests/test_serving.py`` holds logits and pool
#: contents to the contiguous-cache path bit for bit), and again in PR 39,
#: which gives every paged pool the sentinel's page of zeros (a row more in
#: the pool arguments, the gather without its fill, a write through the
#: sentinel sent past the pool; ``tests/test_sentinel_page.py`` and the same
#: ``tests/test_serving.py`` cases hold what that keeps); ``tree`` and
#: ``call`` are still that parent's.
DENSE_GOLDEN = {
    "gpt2": {"tree": "aaa1a7d60ae885e3d2d4d073dadd6d98",
             "step": "5ec05fc1bc67d297e1edb18f3179c647",
             "prefill": "10f03c9dc28018088d5830757b5eff88",
             "step_undonated": "09b3ef05876e3976c4aa5046b16f24b8",
             "prefill_undonated": "e7a3c9262b5c6d797f7e08527d39bf57",
             "call": "7137ce905cc4f0b2dfb44c057f4e4108",
             "decode_paged": "b3b2745df852210b96534af5b448bfea"},
    "mistral": {"tree": "bda3e6337e210318d71872269ca97b04",
                "step": "0e7c64373e54105cdfc2d83448201063",
                "prefill": "14102b8e4ea2976c947655f984d4ab96",
                "step_undonated": "61a96231c44fc330351e0e74729c6013",
                "prefill_undonated": "9ac1a5c0306a82e5eccdcac356d6db7e",
                "call": "9bf6ceb33b379ccf6fc36228f229c7ae",
                "decode_paged": "d39a1d686e2ead09b2a542b7a9e6937d"},
}
DENSE = {"gpt2": {}, "mistral": dict(pos_encoding="rope", kv_heads=2,
                                     activation="swiglu", norm="rmsnorm")}


def dense_fingerprints(name):
    md5 = lambda s: hashlib.md5(s.encode()).hexdigest()  # noqa: E731
    cfg = gpt_lib.GptConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, intermediate_size=96,
                            max_position=128, **DENSE[name])
    m = gpt_lib.GptLM(cfg)
    params = m.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 8), jnp.int32))["params"]
    out = {"tree": md5(str([
        (jax.tree_util.keystr(p), x.shape,
         float(jnp.sum(x.astype(jnp.float32))))
        for p, x in jax.tree_util.tree_flatten_with_path(params)[0]]))}
    eng = DecodeEngine(m, params, EngineConfig(
        num_slots=2, page_size=8, num_pages=16, max_pages_per_seq=4))
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    step_args = (eng._tree, i32(2), i32(2), i32(2, 4), eng.pools, f32(2),
                 i32(2), f32(2), i32(2))
    prefill_args = (eng._tree, i32(1, 16), eng.pools, i32(2))
    for key, fn, args in (("step", eng._step_fn, step_args),
                          ("prefill", eng._prefill_fn(2), prefill_args)):
        text = fn.lower(*args).as_text()
        assert text.count("tf.aliasing_output") == 2 * cfg.num_layers
        out[key] = md5(text)
        out[f"{key}_undonated"] = md5(
            jax.jit(fn.__wrapped__).lower(*args).as_text())
    out["call"] = md5(jax.jit(lambda p, t: m.apply({"params": p}, t)).lower(
        params, i32(2, 16)).as_text())
    out["decode_paged"] = md5(jax.jit(lambda p, *a: m.apply(
        {"params": p}, *a, method=gpt_lib.GptLM.decode_paged)).lower(
            params, i32(2), eng.pools, i32(2, 4), i32(2)).as_text())
    return out


@pytest.mark.parametrize("name", sorted(DENSE))
def test_a_dense_configs_tree_and_programs_are_the_parents(name):
    assert dense_fingerprints(name) == DENSE_GOLDEN[name]
