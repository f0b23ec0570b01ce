"""Device regions (``utils/profiling.region``, docs/observability.md,
"Device regions"): every form of the decoder and the training step carry
the names their work should, a region is metadata and nothing else (the
module JAX's compile cache hashes is the same with the regions switched
off), a name outside the vocabulary cannot be placed, and one decorated
function can be traced by several threads at once."""

import re
import threading

import jax
import jax.numpy as jnp
import optax
import pytest
from jax._src import cache_key

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.training.state import TrainState
from distributed_tensorflow_tpu.utils import profiling

BASE = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            intermediate_size=96, max_position=128)
#: The tiny form of each configuration the benchmark serves or trains.
FORMS = {
    "dense": dict(pos_encoding="rope", kv_heads=2, activation="swiglu",
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
    "looped": dict(pos_encoding="rope", activation="swiglu", norm="rmsnorm",
                   norm_placement="sandwich", loop_steps=3, exit_gate=True),
    "sliding": dict(
        num_layers=3, pos_encoding="rope", kv_heads=2, head_size=32,
        activation="swiglu", norm="rmsnorm", norm_placement="sandwich",
        layer_kinds=("sliding_attention",) * 2 + ("full_attention",),
        sliding_window=16, rope_kinds=("sliding_attention",),
        qk_head_norm=True, attn_output_gate=True, scale_embedding=True,
        num_experts=8, experts_per_token=2, expert_intermediate_size=32,
        num_shared_experts=1, routed_scaling_factor=2.826,
        first_dense_layers=1),
    "conv": dict(
        num_layers=3, pos_encoding="rope", kv_heads=2, activation="swiglu",
        norm="rmsnorm", rope_base=1e6, qk_head_norm=True,
        layer_kinds=("short_conv", "full_attention", "short_conv"),
        short_conv_kernel_dim=3, num_experts=8, experts_per_token=2,
        expert_intermediate_size=32, first_dense_layers=1),
}
SHARED = {"embed", "attn.qkv", "cache.write", "attn.out", "mlp"}
STEP = SHARED | {"cache.gather", "head", "sample"}
#: By form and program, the regions its lowered text must name.
NAMES = {
    ("dense", "step"): STEP | {"attn.scores"},
    ("dense", "prefill"): SHARED | {"attn.scores"},
    ("hybrid", "step"): STEP | {"attn.scores", "linear_attention.step"},
    # (its one full-attention layer is the last, whose context a prefill
    # never reads: JAX drops the scores before lowering)
    ("hybrid", "prefill"): SHARED | {"linear_attention.scan"},
    ("latent", "step"): STEP | {"mla.absorb", "moe.route", "moe.experts",
                                "moe.shared"},
    ("latent", "prefill"): SHARED | {"attn.scores", "mla.expand",
                                     "moe.route", "moe.experts",
                                     "moe.shared"},
    ("looped", "step"): STEP | {"attn.scores", "loop.step",
                                "loop.exit_gate"},
    ("looped", "prefill"): SHARED | {"attn.scores", "loop.step"},
    ("sliding", "step"): STEP | {"attn.scores", "attn.gate", "moe.route",
                                 "moe.experts", "moe.shared"},
    ("sliding", "prefill"): SHARED | {"attn.scores", "attn.gate",
                                      "moe.route", "moe.experts",
                                      "moe.shared"},
    ("conv", "step"): STEP | {"attn.scores", "short_conv.step",
                              "moe.route", "moe.experts"},
    ("conv", "prefill"): SHARED | {"attn.scores", "short_conv.mix",
                                   "moe.route", "moe.experts"},
    ("dense", "train"): (SHARED - {"cache.write"}) | {
        "attn.scores", "head", "loss", "optimizer"},
}


def lowered(form: str, program: str):
    cfg = gpt_lib.GptConfig(**{**BASE, **FORMS[form]})
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    if program == "train":
        apply_fn = lambda p, t: model.apply({"params": p}, t)  # noqa: E731

        def update(state, tokens):
            def loss(p):
                return gpt_lib.lm_loss(apply_fn(p, tokens), tokens)[0]
            return state.apply_gradients(jax.grad(loss)(state.params))

        state = TrainState.create(apply_fn, params, optax.adamw(1e-3))
        return jax.jit(update).lower(state, i32(2, 16))
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=8, num_pages=16, max_pages_per_seq=4))
    # A lane's ring beside its run of pages, where layers are windowed.
    rings = cfg.ring_pages(8) if cfg.window_layers else 0
    if program == "step":
        tables = (i32(2, 4), i32(2, rings)) if rings else i32(2, 4)
        return engine._step_fn.lower(
            engine._tree, i32(2), i32(2), tables, engine.pools, f32(2),
            i32(2), f32(2), i32(2))
    lane = (i32(), i32()) if cfg.has_state_layers else ()
    ring = {"ring": i32(min(2, rings))} if rings else {}
    return engine._prefill_fn(2).lower(
        engine._tree, i32(1, 16), engine.pools, i32(2), *lane, **ring)


def named_in(text: str) -> set:
    """The regions that stand as a whole path component in a lowered
    text's locations, bare or inside JAX's wrappers (``jvp(loss)``)."""
    return {r for r in profiling.REGIONS
            if re.search(rf"[/(]{re.escape(r)}[/)\"]", text)}


@pytest.mark.parametrize("form,program", sorted(NAMES))
def test_a_compiled_program_is_named(form, program):
    text = lowered(form, program).as_text(debug_info=True)
    assert named_in(text) == NAMES[form, program]


def test_every_region_stands_in_some_program():
    assert set().union(*NAMES.values()) == set(profiling.REGIONS)


@pytest.mark.parametrize("form,program", sorted(NAMES))
def test_a_region_never_changes_what_the_cache_key_hashes(form, program,
                                                          monkeypatch):
    """The canonical module (``strip-debuginfo``: what JAX's persistent
    cache hashes while ``jax_compilation_cache_include_metadata_in_key`` is
    false) is the same byte for byte with every region a no-op: a region
    cannot change a program, and cannot miss a cache."""
    def canonical(low):
        return cache_key._canonicalize_ir(
            low.compiler_ir("stablehlo"), cache_key.IgnoreCallbacks.NO)

    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    with_regions = lowered(form, program)
    text = with_regions.as_text(debug_info=True)
    monkeypatch.setattr(profiling._Region, "__enter__", lambda self: None)
    monkeypatch.setattr(profiling._Region, "__exit__",
                        lambda self, *exc: None)
    without = lowered(form, program)
    assert named_in(without.as_text(debug_info=True)) == set()
    assert named_in(text) and canonical(with_regions) == canonical(without)
    assert with_regions.as_text() == without.as_text()


def test_a_name_outside_the_vocabulary_raises_where_the_region_is_made():
    with pytest.raises(ValueError, match="no device region"):
        profiling.region("attn.softmax")
    with pytest.raises(ValueError, match="nope"):
        @profiling.region("nope")
        def never(x):
            return x
    assert len(set(profiling.REGIONS)) == len(profiling.REGIONS) == 23


def test_regions_nest_and_the_innermost_is_last():
    @profiling.region("mlp")
    def inner(x):
        with profiling.region("moe.experts"):
            return x * 2

    def outer(x):
        with profiling.region("loop.step"):
            return inner(x) + 1

    text = jax.jit(outer).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "loop.step/mlp/moe.experts/mul" in text
    assert "loop.step/add" in text and "mlp/add" not in text


def test_one_decorated_function_traced_from_two_threads_at_once():
    """A ``jax.named_scope`` object keeps the name stack it found on
    itself, so one instance shared by two tracing threads would restore
    the other thread's stack; ``region`` enters a scope of its own each
    time."""
    both_inside = threading.Barrier(2, timeout=30)

    @profiling.region("mlp")
    def shared(x):
        both_inside.wait()          # both threads inside the same region
        with profiling.region("moe.shared"):
            y = x * 3
        both_inside.wait()
        return y - 1

    texts, errors = {}, []

    def trace(name, outer):
        try:
            def fn(x):
                with profiling.region(outer):
                    return shared(x) + 2
            texts[name] = jax.jit(fn).lower(
                jnp.ones(3)).as_text(debug_info=True)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=trace, args=args)
               for args in (("a", "head"), ("b", "loss"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert errors == []
    for name, outer, other in (("a", "head", "loss"), ("b", "loss", "head")):
        text = texts[name]
        assert f"{outer}/mlp/moe.shared/mul" in text
        assert f"{outer}/mlp/sub" in text and f"{outer}/add" in text
        assert other not in named_in(text)
        assert "moe.shared/sub" not in text and "mlp/add" not in text
