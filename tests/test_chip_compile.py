"""The main path's kernels, compiled for a DESCRIBED TPU v5e at real widths.

No chip is attached here: ``jax.experimental.topologies`` describes one and
the installed TPU compiler compiles for it, raising what the chip's
compiler would raise (a slice not aligned to the tiling, more fast memory
than a kernel may use).  Nothing runs, so this says nothing about results
or times — it guards every later PR against kernels the chip would refuse,
at no chip time.  A compile that passes is not a chip run.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.ops import quant_train
from distributed_tensorflow_tpu.ops.pallas import flash_attention as flash_lib
from distributed_tensorflow_tpu.ops.pallas import layer_norm as ln_lib


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def as_on_the_chip(monkeypatch):
    """The kernels ask ``jax.default_backend()`` (compiled Mosaic vs the
    interpreter) and ``jax.device_count()`` (the GSPMD hazard) — which see
    this host's CPUs, not the described chip.  Steer them here, in the
    test, not through an option of the program.  A compile for a described
    chip is written to a persistent cache but cannot be read back without
    one, so the cache is off around these compiles."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def mosaic_calls(fn, *shapes) -> int:
    return jax.jit(fn).lower(*shapes).compile().as_text().count(
        "tpu_custom_call")


def flash_fwd_bwd(window):
    def loss(q, k, v):
        return flash_lib.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("shape,window", [
    ((8, 1024, 16, 128), 0),      # the 406M GPT's training shape
    ((1, 8192, 16, 128), 1024),   # long sequence, sliding window
], ids=["s1024_causal", "s8192_window1024"])
def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip, shape, window):
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    # One Mosaic call forward, the dq and dk/dv kernels backward.
    assert mosaic_calls(flash_fwd_bwd(window), qkv, qkv, qkv) >= 3


def test_fused_layer_norm_fwd_compiles_for_v5e(one_chip):
    x = jax.ShapeDtypeStruct((8192, 2048), jnp.bfloat16, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    assert mosaic_calls(ln_lib.fused_layer_norm, x, vec, vec) == 1


def test_int8_gelu_mlp_fwd_bwd_compiles_for_v5e(one_chip):
    M, H, I = 8192, 2048, 8192
    assert quant_train.use_fused_mlp(M, H, I)

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w_in, b_in, w_out, b_out):
        return quant_train.int8_gelu_mlp(
            x, w_in, b_in, w_out, b_out).astype(jnp.float32).sum()

    # Two fused matmuls forward, two NT dgrads backward.
    fwd_bwd = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    assert mosaic_calls(fwd_bwd, arg(M, H, dtype=jnp.bfloat16), arg(H, I),
                        arg(I), arg(I, H), arg(H)) == 4
