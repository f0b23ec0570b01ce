"""Weight-only int8 quantization: roundtrip error bounds, tree selection,
quantized decode fidelity, and int8 export artifacts (``ops/quant.py``).
The reference had no quantization/serving story — its inference was the
training graph (``distributed.py:78-84``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops.quant import (
    dequantize_tree, quantize_leaf, quantize_tree, quantized_bytes)


@pytest.mark.smoke
def test_quantize_leaf_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((256, 128)).astype(np.float32))
    q = quantize_leaf(w)
    assert q["q"].dtype == jnp.int8 and q["s"].shape == (1, 128)
    back = np.asarray(q["q"], np.float32) * np.asarray(q["s"])
    # Symmetric int8: per-channel error bounded by half a quantization step.
    assert np.max(np.abs(back - np.asarray(w))) <= np.max(np.asarray(q["s"])) / 2 + 1e-7


def test_quantize_leaf_multi_axis_kernel_gets_per_channel_scales():
    """A fused DenseGeneral kernel (e.g. qkv [hidden, 3, H, D]) must get a
    distinct scale per (projection, head, channel), not one shared across
    Q/K/V — Q often dwarfs V in magnitude."""
    rng = np.random.default_rng(1)
    w = np.zeros((16, 3, 2, 8), np.float32)
    w[:, 0] = rng.standard_normal((16, 2, 8)) * 10.0  # big Q
    w[:, 2] = rng.standard_normal((16, 2, 8)) * 0.01  # tiny V
    q = quantize_leaf(jnp.asarray(w))
    assert q["s"].shape == (1, 3, 2, 8)
    back = np.asarray(q["q"], np.float32) * np.asarray(q["s"])
    # V's relative error stays small because it has its own scales.
    v_err = np.abs(back[:, 2] - w[:, 2]).max() / np.abs(w[:, 2]).max()
    assert v_err < 0.02
    # Multi-contraction DenseGeneral kernels ([H, D, out]) reduce BOTH
    # contraction axes — scales stay tiny next to the int8 payload.
    q3 = quantize_leaf(jnp.asarray(
        np.random.default_rng(2).standard_normal((16, 128, 64), np.float32)))
    assert q3["s"].shape == (1, 1, 64)


def test_quantize_tree_selects_large_float_matrices():
    tree = {"kernel": jnp.zeros((128, 64)),        # quantized (8192 elems)
            "bias": jnp.zeros((64,)),              # rank 1 -> passthrough
            "small": jnp.zeros((4, 4)),            # tiny -> passthrough
            "ids": jnp.zeros((128, 64), jnp.int32)}  # int -> passthrough
    q = quantize_tree(tree, min_size=4096)
    assert set(q["kernel"].keys()) == {"q", "s"}
    assert q["bias"].dtype == jnp.float32
    assert q["small"].shape == (4, 4)
    assert q["ids"].dtype == jnp.int32
    deq = dequantize_tree(q, jnp.float32)
    assert jax.tree.structure(deq) == jax.tree.structure(tree)


def test_a_consumed_tree_loses_the_leaves_it_quantized():
    """``consume``: a device leaf is deleted once its int8 form is made, a
    leaf that passes through stays alive (the quantized tree holds it), and
    the int8 tree is the one ``consume=False`` makes."""
    rng = np.random.default_rng(3)
    tree = {"kernel": jnp.asarray(rng.standard_normal((128, 64)),
                                  jnp.bfloat16),
            "bias": jnp.zeros((64,)),
            "host": rng.standard_normal((128, 64)).astype(np.float32)}
    want = quantize_tree(tree, min_size=4096)
    got = quantize_tree(tree, min_size=4096, consume=True)
    assert tree["kernel"].is_deleted() and not tree["bias"].is_deleted()
    assert got["bias"] is tree["bias"]
    for name in ("kernel", "host"):        # a host leaf has nothing to free
        for part in ("q", "s"):
            np.testing.assert_array_equal(np.asarray(got[name][part]),
                                          np.asarray(want[name][part]))


def test_quantized_bytes_shrink():
    tree = {"w": jnp.zeros((512, 512))}
    raw = 512 * 512 * 4
    q = quantize_tree(tree, min_size=1024)
    assert quantized_bytes(q) < raw / 3.5   # int8 + scales


@pytest.fixture(scope="module")
def trained_tiny_gpt():
    """A confidently-trained tiny GPT (the synthetic bigram stream is
    learned to near-determinism in ~100 steps) — the shared reference for
    decode-fidelity tests."""
    import optax

    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    cfg = dataclasses.replace(
        gpt_lib.mini(), vocab_size=32, hidden_size=32, num_layers=2,
        num_heads=2, intermediate_size=64, max_position=64, dtype="float32")
    model = gpt_lib.GptLM(cfg)
    batch = gpt_lib.synthetic_lm_batch(0, 32, 32, cfg)
    params = model.init(jax.random.PRNGKey(0), batch["tokens"])["params"]
    tx = optax.adam(3e-3)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, toks):
        def loss_fn(p):
            logits = model.apply({"params": p}, toks)
            loss, _ = gpt_lib.lm_loss(logits, toks)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    for i in range(120):
        toks = gpt_lib.synthetic_lm_batch(i, 32, 32, cfg)["tokens"]
        params, opt, loss = step(params, opt, jnp.asarray(toks))
    prompt = jnp.asarray(batch["tokens"][:2, :8])
    return model, params, prompt


def test_quantized_decode_matches_greedy(trained_tiny_gpt):
    """Per-channel int8 weights must not change the greedy decode."""
    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    model, params, prompt = trained_tiny_gpt
    full = gpt_lib.generate_cached(model, params, prompt, 12)
    quant = gpt_lib.generate_cached(model, params, prompt, 12,
                                    quantize="int8")
    agree = np.mean(np.asarray(full) == np.asarray(quant))
    assert agree > 0.9, (np.asarray(full), np.asarray(quant))


def test_float8_kv_cache_matches_greedy(trained_tiny_gpt):
    """A float8_e4m3fn KV cache (half of bf16's bytes, upcast on read) must
    keep the greedy decode of a confident model — and compose with int8
    weights."""
    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    model, params, prompt = trained_tiny_gpt
    full = gpt_lib.generate_cached(model, params, prompt, 12)
    fp8 = gpt_lib.generate_cached(model, params, prompt, 12,
                                  kv_dtype="float8")
    both = gpt_lib.generate_cached(model, params, prompt, 12,
                                   quantize="int8", kv_dtype="float8")
    assert np.mean(np.asarray(full) == np.asarray(fp8)) > 0.9
    assert np.mean(np.asarray(full) == np.asarray(both)) > 0.85
    # The caches really are fp8-backed.
    caches = gpt_lib.init_kv_cache(model.cfg, 2, 16,
                                   dtype=jnp.float8_e4m3fn)
    assert caches[0][0].dtype == jnp.float8_e4m3fn


def test_export_int8_artifact_smaller_and_close(tmp_path):
    """--quantize=int8 export: artifact shrinks ~3-4x and the served logits
    stay close to the float artifact's."""
    import optax

    from distributed_tensorflow_tpu.models.mlp import MnistMLP
    from distributed_tensorflow_tpu.tools import export_model as ex
    from distributed_tensorflow_tpu.training.state import TrainState
    from distributed_tensorflow_tpu.training.supervisor import Supervisor

    model = MnistMLP(hidden_units=256)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))["params"]
    state = TrainState.create(lambda p, x: None, params, optax.sgd(0.1))
    sv = Supervisor(is_chief=True, logdir=str(tmp_path), init_fn=lambda: state)
    st = sv.prepare_or_wait_for_state()
    sv.maybe_save(st, force=True)
    sv.close()

    f32, _ = ex.export_model("mnist_mlp", str(tmp_path), batch=4,
                             hidden_units=256, platforms=("cpu",))
    i8, meta = ex.export_model("mnist_mlp", str(tmp_path), batch=4,
                               hidden_units=256, platforms=("cpu",),
                               quantize="int8")
    assert meta["quantize"] == "int8"
    assert len(i8) < len(f32) / 2.5

    for blob, name in ((f32, "f.hlo"), (i8, "q.hlo")):
        (tmp_path / name).write_bytes(blob)
    x = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (4, 784)))
    out_f = np.asarray(ex.load_exported(tmp_path / "f.hlo").call(x))
    out_q = np.asarray(ex.load_exported(tmp_path / "q.hlo").call(x))
    # Logit agreement: int8 per-channel keeps argmax for a well-scaled MLP.
    assert np.array_equal(out_f.argmax(-1), out_q.argmax(-1))
    assert np.max(np.abs(out_f - out_q)) < 0.15
