"""Pallas flash attention vs. the dense XLA path (interpret mode on CPU).

The reference has no attention op (``distributed.py:65-87``); these tests pin
the framework's kernel: blockwise online-softmax equals dense softmax exactly
(fp32), padding masks and causal masks included, and the rematerializing VJP
matches dense gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.ops.attention import dot_product_attention
from distributed_tensorflow_tpu.ops.pallas.flash_attention import (
    ambient_mesh, flash_attention)
from distributed_tensorflow_tpu.parallel import mesh as mesh_lib

from helpers import kernel_placement, primitives


def _qkv(key, B=2, S=32, H=2, D=8, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(kq, (B, S, H, D), dtype),
            jax.random.normal(kk, (B, S, H, D), dtype),
            jax.random.normal(kv, (B, S, H, D), dtype))


@pytest.mark.smoke
def test_flash_matches_dense():
    q, k, v = _qkv(0)
    np.testing.assert_allclose(flash_attention(q, k, v),
                               dot_product_attention(q, k, v),
                               rtol=1e-5, atol=1e-5)


def test_flash_padding_mask():
    q, k, v = _qkv(1)
    kv_mask = (jax.random.uniform(jax.random.PRNGKey(7), (2, 32)) > 0.4)
    kv_mask = kv_mask.at[:, 0].set(True)
    np.testing.assert_allclose(
        flash_attention(q, k, v, kv_mask=kv_mask),
        dot_product_attention(q, k, v, kv_mask=kv_mask),
        rtol=1e-5, atol=1e-5)


def test_flash_causal():
    q, k, v = _qkv(2)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True),
        dot_product_attention(q, k, v, causal=True),
        rtol=1e-5, atol=1e-5)


def test_flash_fully_masked_rows_zero():
    q, k, v = _qkv(3)
    kv_mask = jnp.zeros((2, 32), bool).at[1:].set(True)
    out = flash_attention(q, k, v, kv_mask=kv_mask)
    assert not np.any(np.isnan(out))
    np.testing.assert_allclose(out[0], np.zeros_like(out[0]), atol=1e-6)


def test_flash_grad_matches_dense():
    q, k, v = _qkv(4, S=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_bf16():
    q, k, v = _qkv(5, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), rtol=0.05, atol=0.05)


def test_flash_odd_seq_falls_back_to_dense():
    q, k, v = _qkv(6, S=12)  # 12 % 8 != 0 -> dense path
    np.testing.assert_allclose(flash_attention(q, k, v),
                               dot_product_attention(q, k, v),
                               rtol=1e-5, atol=1e-5)


def test_bert_pallas_backend_runs():
    from distributed_tensorflow_tpu.models import bert as bert_lib

    cfg = bert_lib.BertConfig(vocab_size=64, hidden_size=16, num_layers=1,
                              num_heads=2, intermediate_size=32,
                              attention_backend="pallas")
    model = bert_lib.BertForMLM(cfg)
    ids = jnp.ones((2, 16), jnp.int32)
    mask = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids, mask)["params"]
    logits = model.apply({"params": params}, ids, mask)
    assert logits.shape == (2, 16, 64)
    assert not np.any(np.isnan(logits))


def test_unknown_backend_rejected():
    q, k, v = _qkv(7)
    with pytest.raises(ValueError, match="Unknown attention backend"):
        dot_product_attention(q, k, v, backend="cuda")


def test_flash_grad_with_padding_mask_matches_dense():
    """Blockwise pallas backward under a padding mask (dv/dk zero at masked
    keys; masked-row q gradients zero)."""
    q, k, v = _qkv(6, S=32)
    kv_mask = (jax.random.uniform(jax.random.PRNGKey(3), (2, 32)) > 0.3)
    kv_mask = kv_mask.at[:, 0].set(True)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, kv_mask=kv_mask)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dot_product_attention(q, k, v, kv_mask=kv_mask)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # Masked keys receive zero dk/dv.
    dk, dv = np.asarray(gf[1]), np.asarray(gf[2])
    dead = ~np.asarray(kv_mask)
    assert np.all(dk[dead] == 0) and np.all(dv[dead] == 0)


def test_flash_grad_multiblock():
    """S large enough for several q/k blocks (real accumulation paths)."""
    q, k, v = _qkv(7, S=128, D=16)

    def loss(att):
        def f(q, k, v):
            return jnp.sum(att(q, k, v) ** 2)
        return f

    gf = jax.grad(loss(lambda *a: flash_attention(*a, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda *a: dot_product_attention(*a, causal=True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_flash_grad_bf16_finite_and_close():
    q, k, v = _qkv(8, S=32, dtype=jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    assert all(g.dtype == jnp.bfloat16 for g in gf)

    def loss_dense(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0.1, atol=0.1)


def test_flash_grad_fully_masked_row_is_zero_not_nan():
    q, k, v = _qkv(9)
    kv_mask = jnp.zeros((2, 32), bool).at[1:].set(True)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, kv_mask=kv_mask) ** 2)

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert not np.any(np.isnan(np.asarray(g)))
    np.testing.assert_allclose(np.asarray(gq[0]), 0.0, atol=1e-6)


def test_flash_1024_block_branch_matches_dense():
    """S >= 4096 selects the 1024 block cap (r4 retune); cover that branch
    in interpret mode so a block-size-specific break (VMEM spec, lane
    alignment, band math at block=1024) fails in CI, not on the chip.
    Tiny B/H/D keep the 4096-row interpret run cheap."""
    q, k, v = _qkv(5, B=1, S=4096, H=1, D=8)
    got = flash_attention(q, k, v, causal=True)
    want = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # The windowed kernel must KEEP the 512 cap at long S (a 1024 block
    # over-fetches the band) — and stay exact.
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fa
    assert fa._pick_block(4096) == 1024
    assert fa._pick_block(4096, window=1024) == 512
    got_w = flash_attention(q, k, v, causal=True, window=512)
    want_w = dot_product_attention(q, k, v, causal=True, window=512)
    np.testing.assert_allclose(got_w, want_w, rtol=1e-5, atol=1e-5)


# ------------------------------- one backward kernel where the dq row fits VMEM

def _backward_forms(monkeypatch, q, k, v, kv_mask, g, causal, window):
    """The gradients by the one kernel and by the dk/dv and dq kernels."""
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fa
    o, lse = fa._flash_forward(q, k, v, kv_mask, causal=causal, window=window)
    args = (q, k, v, kv_mask, o, lse, g)
    one = fa._flash_backward(*args, causal=causal, window=window)
    monkeypatch.setattr(fa, "_DQ_ROW_BYTES", 0)     # no row fits: two kernels
    two = fa._flash_backward(*args, causal=causal, window=window)
    return one, two


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("D", [64, 128])
@pytest.mark.parametrize("S", [64, 1024], ids=["one_block", "two_blocks"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kv_mask"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_one_backward_kernel_equals_the_two_bit_for_bit(
        monkeypatch, causal, masked, S, D, dtype):
    """A tile's p and ds are rebuilt once and feed all three gradients; the
    sums run in the two kernels' order, so nothing may differ at all."""
    ks = jax.random.split(jax.random.PRNGKey(S + D), 4)
    q, k, v, g = (jax.random.normal(x, (2, S, 2, D), dtype) for x in ks)
    kv_mask = None
    if masked:   # row 0 fully masked, row 1 ragged
        kv_mask = (jax.random.uniform(jax.random.PRNGKey(5), (2, S)) > 0.3)
        kv_mask = kv_mask.at[1, 0].set(True).at[0].set(False)
    one, two = _backward_forms(monkeypatch, q, k, v, kv_mask, g, causal, 0)
    for a, b in zip(one, two):
        assert a.dtype == b.dtype == dtype
        assert not np.any(np.isnan(np.asarray(a, np.float32)))
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    if masked:
        np.testing.assert_array_equal(np.asarray(one[0][0], np.float32), 0.0)


@pytest.mark.parametrize("window", [128, 512, 700])
def test_one_backward_kernel_walks_a_band_as_the_two_do(monkeypatch, window):
    """The sliding window's banded grid (q blocks ik .. ik + band - 1 a K
    block, clipped at the top edge) through the same body."""
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q, k, v, g = (jax.random.normal(x, (1, 2048, 2, 64), jnp.bfloat16)
                  for x in ks)
    kv_mask = jnp.ones((1, 2048), bool).at[:, 2000:].set(False)
    one, two = _backward_forms(monkeypatch, q, k, v, kv_mask, g, True, window)
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("shape,window,calls", [
    ((8, 1024, 16, 64), 0, 1),      # the training cells' row: 512 KB of lanes
    ((1, 8192, 16, 128), 1024, 1),  # 4 MiB: the largest row the kernel keeps
    ((1, 16384, 2, 128), 0, 2),     # 8 MiB: dk/dv and dq kernels
    ((1, 16384, 2, 64), 1024, 2),   # heads of 64 fill whole lanes in VMEM
], ids=["s1024_d64", "s8192_d128_window", "s16384_d128", "s16384_d64_window"])
def test_the_rows_bytes_choose_the_backward_form(shape, window, calls):
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fa
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    B, S, H, D = shape
    stats = jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, o, lse, g: fa._flash_backward(
        q, k, v, None, o, lse, g, causal=True, window=window))(
            x, x, x, x, stats, x)
    assert [name for name, _ in primitives(jaxpr.jaxpr)].count(
        "pallas_call") == calls


# ------------------------------------ the call site on a mesh of several devices

@pytest.mark.parametrize("axes,batch,mapped", [
    (dict(data=4), 8, True),
    (dict(data=4), 6, False),             # rows do not divide over the axis
    (dict(data=2, model=2), 8, False),    # heads over `model`: a later PR's
    (dict(data=2, seq=2), 8, False),      # the ring path's mesh
    (dict(data=1), 8, False),             # one device: the plain call
], ids=["data4", "data4_rows6", "data2_model2", "data2_seq2", "one_device"])
def test_flash_maps_the_kernel_over_the_ambient_meshs_batch_axes(
        axes, batch, mapped):
    n = int(np.prod(list(axes.values())))
    mesh = mesh_lib.create_mesh(devices=jax.devices()[:n], **axes)
    q, k, v = _qkv(11, B=batch)
    kv_mask = jnp.ones((batch, 32), bool).at[:, 29:].set(False)

    def fwd_bwd(q, k, v, kv_mask):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, kv_mask=kv_mask,
                                           causal=True) ** 2)
        with ambient_mesh(mesh):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    # One kernel forward, one backward (dq, dk and dv from one walk).
    assert kernel_placement(fwd_bwd, q, k, v, kv_mask) == (
        (2, 0) if mapped else (0, 2))
    # No mesh ambient: the plain call, as ever.
    assert kernel_placement(
        lambda *a: flash_attention(*a, causal=True), q, k, v) == (0, 1)

    got = jax.jit(fwd_bwd)(q, k, v, kv_mask)
    want = jax.value_and_grad(
        lambda q, k, v: jnp.sum(dot_product_attention(
            q, k, v, kv_mask=kv_mask, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_inside_a_shard_map_is_not_mapped_again():
    from jax.sharding import PartitionSpec as P
    mesh = mesh_lib.create_mesh(data=4, devices=jax.devices()[:4])
    q, k, v = _qkv(12, B=8)

    def whole_step(q, k, v):
        with ambient_mesh(mesh):
            return jax.shard_map(
                lambda q, k, v: flash_attention(q, k, v, causal=True),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                check_vma=False)(q, k, v)

    prims = primitives(jax.make_jaxpr(whole_step)(q, k, v).jaxpr)
    assert [name for name, _ in prims].count("shard_map") == 1
    np.testing.assert_allclose(
        jax.jit(whole_step)(q, k, v),
        dot_product_attention(q, k, v, causal=True), rtol=1e-5, atol=1e-5)
