"""The main path's kernels, compiled for a DESCRIBED TPU v5e at real widths.

No chip is attached here: ``jax.experimental.topologies`` describes one and
the installed TPU compiler compiles for it, raising what the chip's
compiler would raise (a slice not aligned to the tiling, more fast memory
than a kernel may use).  Nothing runs, so this says nothing about results
or times — it guards every later PR against kernels the chip would refuse,
at no chip time.  A compile that passes is not a chip run.
"""

import dataclasses
import math
import os
import re
import warnings

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.ops import quant_train
from distributed_tensorflow_tpu.ops.pallas import flash_attention as flash_lib
from distributed_tensorflow_tpu.ops.pallas import layer_norm as ln_lib
from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
from distributed_tensorflow_tpu.parallel import sync as sync_lib
from distributed_tensorflow_tpu.training.optimizers import make_optimizer
from distributed_tensorflow_tpu.training.state import TrainState


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The four described devices, for ``mesh_lib.create_mesh(devices=...)``."""
    assert len(topo.devices) == 4
    return list(topo.devices)


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


@pytest.mark.parametrize("shape,window,calls", [
    ((8, 1024, 16, 64), 0, 2),       # gpt2-medium's: the training cells' shape
    ((8, 1024, 16, 128), 0, 2),      # the 406M GPT's training shape
    # Long sequence, sliding window: the band takes the one backward body,
    # its dq row (4 MiB) the largest that does.
    ((1, 8192, 16, 128), 1024, 2),
    ((1, 8192, 16, 128), 0, 2),      # blocks of 1,024 beside that row
    ((1, 16384, 8, 128), 0, 3),      # a row of 8 MiB: the dq and dk/dv kernels
], ids=["s1024_head64", "s1024_causal", "s8192_window1024", "s8192_causal",
        "s16384_causal"])
def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip, shape, window,
                                                  calls):
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    # One Mosaic call forward and ONE backward where the dq row fits VMEM,
    # else the dq and dk/dv kernels: counted exactly, so that neither a
    # kernel that fell away nor a row that took the wrong form passes.
    assert mosaic_calls(flash_fwd_bwd(window), qkv, qkv, qkv) == calls


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


# ------------------------------------------------- the sync step, four chips

_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
_COLLECTIVE = re.compile(
    r" = (?P<shape>.*?) (?P<kind>all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute|collective-broadcast)(?:-start)?\(")


def collectives(text: str) -> dict:
    """Kind -> sorted [(dtype, bytes)] over the operands of every collective
    in a compiled program's text (tuple-shaped ones count each member)."""
    out: dict = {}
    for line in text.splitlines():
        m = _COLLECTIVE.search(line)
        if m is None:
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m["shape"]):
            n = math.prod(int(d) for d in dims.split(",") if d)
            out.setdefault(m["kind"], []).append((dtype, n * _BYTES[dtype]))
    return {k: sorted(v) for k, v in out.items()}


def compile_wide_step(devices, **axes):
    """``build_sync_train_step`` over a 2-layer ``GptLM`` at gpt2-medium's
    widths, 16 rows of 1024 tokens, compiled for a mesh of the described
    devices as ``perfbench/worker.py:run_train`` compiles its cell's."""
    mesh = mesh_lib.create_mesh(devices=devices, **axes)
    cfg = gpt_lib.GptConfig(
        vocab_size=50257, hidden_size=1024, num_layers=2, num_heads=16,
        intermediate_size=4096, max_position=1024, dtype="bfloat16",
        attention_backend="pallas", pos_encoding="learned")
    model = gpt_lib.GptLM(cfg)

    def loss_fn(params, batch):
        loss, acc = gpt_lib.lm_loss(model.apply({"params": params}, batch),
                                    batch)
        return loss, {"accuracy": acc}

    def fresh_state():   # for its shapes: the same whatever the attention
        params = gpt_lib.GptLM(dataclasses.replace(
            cfg, attention_backend="xla")).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState.create(None, params, make_optimizer("adam", 3e-4))

    everywhere = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere),
        jax.eval_shape(fresh_state))
    tokens = jax.ShapeDtypeStruct((16, 1024), jnp.int32,
                                  sharding=mesh_lib.batch_sharding(mesh))
    step = sync_lib.build_sync_train_step(mesh, loss_fn)
    return step.lower(state, tokens).compile()


@pytest.fixture
def four_visible(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    monkeypatch.setattr(flash_lib, "_warned", set())


def test_sync_step_keeps_the_flash_kernel_on_four_chips(
        four_chips, four_visible, monkeypatch):
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # no dense fallback, so no warning
        mapped = compile_wide_step(four_chips, data=4)
    # With no mesh ambient the op takes the dense fallback, as before PR 27.
    monkeypatch.setattr(flash_lib, "_batch_axes", lambda batch: None)
    with pytest.warns(UserWarning, match="GSPMD cannot partition"):
        dense = compile_wide_step(four_chips, data=4)

    # A layer: one kernel forward, one backward (dq, dk and dv together).
    assert mapped.as_text().count("tpu_custom_call") == 4
    assert dense.as_text().count("tpu_custom_call") == 0
    # The gradient reduction is GSPMD's, byte for byte: the blocks' gradients
    # in bf16 where the backward pass made them, embedding and head in f32.
    reduced = collectives(mapped.as_text())
    assert set(reduced) == {"all-reduce"}, reduced
    assert reduced == collectives(dense.as_text())
    assert {dtype for dtype, _ in reduced["all-reduce"]} >= {"bf16", "f32"}
    # No f32 scores written and read per layer.
    assert (mapped.memory_analysis().temp_size_in_bytes
            < dense.memory_analysis().temp_size_in_bytes)


def test_sync_step_with_a_model_axis_keeps_the_dense_fallback(
        four_chips, four_visible):
    with pytest.warns(UserWarning, match="GSPMD cannot partition") as caught:
        compiled = compile_wide_step(four_chips, data=2, model=2)
    assert compiled.as_text().count("tpu_custom_call") == 0
    # Four attention call sites traced (two layers, and their transposes
    # come from the same trace), one warning.
    assert len([w for w in caught
                if "GSPMD cannot partition" in str(w.message)]) == 1


# ------------------------------------- a recurrent state beside the pages


def bare_engine(model, econf, stateful=False, sparse_layers=0):
    """A ``DecodeEngine`` that holds its closures and no array: what
    ``_build_step`` and ``_prefill_fn`` read of ``self``."""
    from distributed_tensorflow_tpu.serving.engine import DecodeEngine
    engine = DecodeEngine.__new__(DecodeEngine)
    engine._jax, engine._jnp, engine.model, engine.config = (
        jax, jnp, model, econf)
    engine._cache_dtype = None
    engine.geometry = geo = gpt_lib.pool_geometry(model.cfg, econf.page_size)
    assert (bool(geo.state_layers), geo.sparse_layers) == (
        stateful, sparse_layers)
    engine._prefill_fns, engine._prefill_evictions = {}, 0
    return engine


def serving_programs(one_chip, cfg, buckets, stateful, num_pages=1856,
                     max_pages_per_seq=232):
    """``cfg`` as the engine compiles it under ``longprompt_closed16``'s
    settings (or another pool's): the decode step over 8 slots and a
    whole-bucket prefill a page count, with the engine's own closures
    (their shapes described, nothing placed)."""
    from distributed_tensorflow_tpu.serving.engine import EngineConfig
    model = gpt_lib.GptLM(cfg)
    econf = EngineConfig(num_slots=8, page_size=16, num_pages=num_pages,
                         max_pages_per_seq=max_pages_per_seq)

    def described(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    engine = bare_engine(model, econf, stateful)
    tree = described(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    pools = described(jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        cfg, econf.num_pages, econf.page_size, num_slots=econf.num_slots)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    B, MP = econf.num_slots, econf.max_pages_per_seq
    step = engine._build_step().lower(
        tree, i32(B), i32(B), i32(B, MP), pools, f32(B), i32(B), f32(B),
        i32(B)).compile()
    lane = (i32(), i32()) if stateful else ()       # slot, absorb
    prefills = {
        n: engine._prefill_fn(n).lower(
            tree, i32(1, n * econf.page_size), pools, i32(n), *lane).compile()
        for n in buckets}
    return step, prefills, pools


def hybrid_serving_programs(one_chip, buckets):
    """One period (three linear-attention layers, one full) and the next
    period's first layer at the published widths of
    ``perfbench/configs/olmo-hybrid-7b.json``."""
    kinds = (gpt_lib.LINEAR_ATTENTION,) * 3 + (
        gpt_lib.FULL_ATTENTION, gpt_lib.LINEAR_ATTENTION)
    cfg = gpt_lib.GptConfig(
        vocab_size=100352, hidden_size=3840, num_layers=5, num_heads=30,
        intermediate_size=11008, max_position=4096, dtype="bfloat16",
        attention_backend="pallas", pos_encoding="none",
        activation="swiglu", norm="rmsnorm", norm_placement="post",
        qk_norm=True, layer_kinds=kinds, linear_num_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_allow_neg_eigval=True)
    return serving_programs(one_chip, cfg, buckets, stateful=True)


def relayouts(program, floor: int, bodies: bool = False) -> list:
    """The instructions of a compiled program's ENTRY computation that only
    re-lay an array out (``copy``, a ``reshape`` that is no bitcast,
    ``transpose``: each a pass of its own over its operand on the chip)
    and whose result holds ``floor`` bytes or more.  With ``bodies``, of
    every computation that is no fusion's: a program whose layers run
    inside a ``while`` has them in its body, not in ENTRY."""
    text = program.as_text()
    if bodies:
        text = "\n".join(
            comp for comp in re.split(
                r"\n(?=(?:ENTRY )?%\S+ \(.*\) -> .* \{\n)", text)
            if not comp.lstrip().startswith("%fused_computation"))
    else:
        text = text[text.index("\nENTRY "):]
    return _instructions(text, "copy|reshape|transpose", floor)


def _instructions(text: str, kinds: str, floor: int) -> list:
    """The lines of HLO ``text`` whose instruction is one of ``kinds`` (a
    regex alternation) and whose result holds ``floor`` bytes or more."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* "
                     rf"({kinds})\(", line)
        if m and math.prod(int(d) for d in m[2].split(",") if d) * _BYTES.get(
                m[1], 1) >= floor:
            found.append(line.strip()[:160])
    return found


def gathered_selects(program, floor: int) -> list:
    """The ``select`` instructions of a compiled program, in a fusion's
    body or outside one, whose result holds ``floor`` bytes or more: a
    pass over the rows a decode step gathered through its page table that
    blanks a sentinel entry's (``jnp.take(..., mode="fill")``: two a full
    layer before PR 39, 1.4 ms each in the hybrid's step as a fusion of
    their own, folded into the scores' read in the dense and the looped
    step).  The step's other selects (the mask over the scores, the
    embedding's take) are a hundredth of that size."""
    return _instructions(program.as_text(), "select", floor)


def table_gathers(program, floor: int) -> list:
    """The ``gather`` instructions of a compiled program whose result
    holds ``floor`` bytes or more: a page table's pages side by side
    (``gpt_lib.gather_pages``), what every paged K/V layer built twice a
    step until the paged-attention kernel read a lane's held pages where
    they lie (PR 45).  The step's other gathers (a token's embedding, a
    table's entry) are rows, not pages."""
    return _instructions(program.as_text(), "gather", floor)


def pools_donated_and_uncopied(programs, pools, n_leaves, temp_below):
    """The engine donates its pools: every leaf comes out in the buffer it
    went in by, and nothing else does; and no program re-lays out as many
    bytes as the decode step gathers of a K/V pool (8 lanes of 232 pages:
    all but one page of it), nor blanks them, nor (the step) gathers a
    lane's share of them: the hybrid's sixteen pool
    copies a step before PR 37, the two passes over the gathered rows that
    a head axis split off them AFTER the gather costs instead, the dense
    model's heads-major copy of them, and the zero-fill of sentinel
    entries before PR 39 (the step is ``programs[0]``)."""
    leaves = jax.tree.leaves(pools)
    floor = min(x.size * x.dtype.itemsize for x in leaves
                if x.shape[0] == 1857) * 1856 // 1857
    assert len(leaves) == n_leaves
    # (Bytes as the chip lays an array out: the last axis in whole lanes
    # of 128, which pads the state's keys of 96 and nothing else here.)
    pool_bytes = sum(x.size // x.shape[-1] * -(-x.shape[-1] // 128) * 128
                     * x.dtype.itemsize for x in leaves)
    for program in programs:
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < temp_below
        assert mem.alias_size_in_bytes == pool_bytes
        header = program.as_text().split("\n", 1)[0]   # input_output_alias
        assert header.count("may-alias") + header.count(
            "must-alias") == len(leaves)
        assert relayouts(program, floor) == []
    assert gathered_selects(programs[0], floor) == []
    # ... nor gathers them at all (PR 45): the kernel copies held pages.
    assert table_gathers(programs[0], floor // 8) == []


def test_hybrid_serving_programs_compile_for_v5e(one_chip):
    """The one-token rule lowers for the chip at 30 heads of 96 x 192 and
    a prefill's chunked rule is ONE Mosaic call a linear layer (PR 51:
    ``ops/pallas/gated_delta.py``; until then sixteen fusions, XLA's
    blockwise inverse and a ``while`` of a turn a chunk over float32
    temporaries of [N, B, H, 64, *]), with the flash kernel in the full
    layer's prefill where the bucket's length lets it in: 1,024 tokens do,
    1,600 do not (``_layout_ok``; D6's silent fallback).  The K/V pools'
    row is flat, [1856 + 1, 16, 30 * 128] with the sentinel's page of
    zeros, the chip keeps it as the step indexes it and the step's ONE
    full layer reads it through the paged-attention kernel, one Mosaic
    call (PR 45): no pool-sized relayout (four copies a full layer with a
    head axis of 30; PR 37), no pass that blanks gathered rows (two a
    full layer; PR 39) and no gather of the table."""
    step, prefills, pools = hybrid_serving_programs(one_chip, (64, 100))
    assert step.as_text().count("tpu_custom_call") == 1
    # (A full layer that is the model's LAST layer loses its call: its
    # attention output feeds only logits the prefill throws away, so XLA
    # keeps its K/V and drops the rest.  Here a linear layer follows it,
    # and a linear layer's call stays wherever it stands: its state is the
    # prefill's to return.)
    linear = 4
    assert prefills[64].as_text().count("tpu_custom_call") == 1 + linear
    assert prefills[100].as_text().count("tpu_custom_call") == 0 + linear
    for n, prefill in prefills.items():
        text = prefill.as_text()
        assert " while(" not in text
        scan = [line for line in text.splitlines()
                if "linear_attention.scan" in line]
        assert sum("tpu_custom_call" in line for line in scan) == linear
        # chunks-major temporaries, [N, 1, 30, 64, *]: none is left, and
        # the heads-major operands and result of the kernel are the
        # producers' and the consumer's own layout, not a copy's
        chunks = -(-n * 16 // 64)
        assert not [line for line in scan
                    if f"f32[{chunks},1,30,64" in line.split(" = ", 1)[-1]]
        assert not [line for line in scan if re.search(
            r" = \S+ (copy|transpose)\(", line)]
    assert [x.shape for x in pools[3]] == [(1857, 16, 3840)] * 2
    pools_donated_and_uncopied((step, *prefills.values()), pools,
                               n_leaves=2 * 5, temp_below=4e9)


@pytest.mark.parametrize("tokens", [1024, 1600, 2400, 3584])
def test_gated_delta_kernel_compiles_for_v5e(one_chip, tokens):
    """The chunked rule's kernel alone at the hybrid cell's four buckets,
    30 heads of 96 x 192: a VMEM or a tiling refusal shows here."""
    from distributed_tensorflow_tpu.ops import linear_attention

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    q, v = f32(1, tokens, 30, 96), f32(1, tokens, 30, 192)
    gate, state = f32(1, tokens, 30), f32(1, 30, 192, 96)
    program = jax.jit(linear_attention.gated_delta_chunked).lower(
        q, q, v, gate, gate, state).compile()
    text = program.as_text()
    assert text.count("tpu_custom_call") == 1 and " while(" not in text
    # the operands as they come, the output and nothing a chunk's size more
    assert program.memory_analysis().temp_size_in_bytes < 3 * 4 * (
        tokens + 64) * 32 * (2 * 128 + 256)


def test_dense_serving_programs_keep_their_pools_for_v5e(one_chip):
    """The same step and prefill at ``perfbench/configs/mistral-7b.json``'s
    widths (8 K/V heads of 128, two layers): the flat row [1857, 16, 1024]
    is scattered into in place as its four-axis form was, and the step
    reads it where it lies, one paged-attention call a layer (PR 45), so a
    later pool shape or gather cannot bring a relayout, a blanking pass or
    a table-wide gather to either configuration without a red test."""
    cfg = gpt_lib.GptConfig(
        vocab_size=32000, hidden_size=4096, num_layers=2, num_heads=32,
        kv_heads=8, intermediate_size=14336, max_position=4096,
        dtype="bfloat16", attention_backend="pallas", pos_encoding="rope",
        activation="swiglu", norm="rmsnorm")
    step, prefills, pools = serving_programs(one_chip, cfg, (64,),
                                             stateful=False)
    assert [x.shape for x in jax.tree.leaves(pools)] == [(1857, 16, 1024)] * 4
    # The flash kernel in both layers' prefill but the last's (above).
    assert prefills[64].as_text().count("tpu_custom_call") == 1
    assert step.as_text().count("tpu_custom_call") == 2
    pools_donated_and_uncopied((step, *prefills.values()), pools,
                               n_leaves=2 * 2, temp_below=2e9)


# ------------------------ one latent row a token, and routed experts


def test_latent_and_routed_expert_programs_compile_for_v5e(one_chip):
    """The leading dense layer and two sparse layers at the published widths
    of ``perfbench/configs/glm-4.7-flash.json``, as the engine compiles them:
    the decode step over 16 slots of 528 pages (absorbed attention over the
    rows as they were gathered, three grouped products a sparse layer, the
    histogram behind the tokens) and a whole-bucket prefill (the flash
    kernel at a head of 256, the grouped products at 4 x 1,024 rows), pools
    donated."""
    from distributed_tensorflow_tpu.serving.engine import EngineConfig
    from perfbench import spec, worker
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         "glm-4.7-flash.json"))
    cfg = dataclasses.replace(worker.gpt_config(
        {"config": config, "config_file": "glm-4.7-flash.json"}),
        num_layers=3, vocab_size=32768)
    model = gpt_lib.GptLM(cfg)
    econf = EngineConfig(num_slots=16, page_size=16, num_pages=8448,
                         max_pages_per_seq=528)

    def described(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    engine = bare_engine(model, econf, sparse_layers=2)
    tree = described(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    pools = described(jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        cfg, econf.num_pages, econf.page_size)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    B, MP = econf.num_slots, econf.max_pages_per_seq
    lowered = engine._build_step().lower(
        tree, i32(B), i32(B), i32(B, MP), pools, f32(B), i32(B), f32(B),
        i32(B))
    # 16 tokens and behind them 2 layers x 64 experts of histogram
    assert lowered.out_info[0].shape == (B + 2 * 64,)
    step = lowered.compile()
    prefill = engine._prefill_fn(64).lower(
        tree, i32(1, 1024), pools, i32(64)).compile()
    # Three grouped products a sparse layer and, since PR 47, a call of
    # the latent paged-attention kernel a layer.
    assert step.as_text().count("tpu_custom_call") == 2 * 3 + 3
    assert gpt_lib.paged_kernel_layers(cfg, pools) == 3
    # (what the step gathered of a latent pool: 16 lanes of 528 pages; of
    # the rotated keys' an eighth of that)
    gathered = 16 * 528 * 16 * 512 * 2
    assert gathered_selects(step, gathered) == []
    assert table_gathers(step, gathered // 8 // 16) == []
    # The last layer's mixer and experts feed only logits the prefill
    # throws away: two flash calls and ONE layer's grouped products stay.
    assert prefill.as_text().count("tpu_custom_call") == 2 + 3
    leaves = jax.tree.leaves(pools)
    # (The rotated keys two tokens a row of 128 lanes: at [8449, 16, 64]
    # the chip laid that pool out with the PAGES minor-most, and the step
    # copied it into the indexed order and back, sixteen copies a step:
    # PR 47.  Now every pool is held as it is indexed and as the kernel
    # reads it, in its own bytes.)
    assert [x.shape for x in leaves] == [(8449, 16, 512),
                                         (8449, 8, 128)] * 3
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert pool_bytes == 3 * 8449 * 16 * 1152
    for program in (step, prefill):
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < 2e9
        assert mem.alias_size_in_bytes == pool_bytes
        header = program.as_text().split("\n", 1)[0]
        assert header.count("may-alias") + header.count(
            "must-alias") == len(leaves)
        # no copy of a pool, the keys' (a ninth of a layer's row) included
        assert relayouts(program, 8448 * 8 * 128 * 2) == []


# ------------------------- a stack walked four times over the same weights


def test_looped_serving_programs_compile_for_v5e(one_chip):
    """Two of the 48 layers at the published widths of
    ``perfbench/configs/ouro-2.6b.json`` under ``reasoning_closed16``'s
    engine settings, walked four times: the decode step is ONE ``while``
    around the layers (not four copies of them), a layer's pool holds its
    four runs of pages and the sentinel's page in one array [4 x 384 + 1,
    16, 2048] which the loop carries in place (donated, aliased, no
    relayout of a pool's size in the body or outside it, no pass that
    blanks what a loop step gathered of its run, and since PR 45 no gather
    of a run: a paged-attention call a layer inside the loop, walking the
    offset table), and the prefill keeps the flash kernel in every layer
    of the loop."""
    from perfbench import spec, worker
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         "ouro-2.6b.json"))
    cfg = dataclasses.replace(worker.gpt_config(
        {"config": config, "config_file": "ouro-2.6b.json"}),
        num_layers=2, vocab_size=8192)
    step, prefills, pools = serving_programs(
        one_chip, cfg, (16,), stateful=False, num_pages=384,
        max_pages_per_seq=48)
    leaves = jax.tree.leaves(pools)
    assert [x.shape for x in leaves] == [(4 * 384 + 1, 16, 2048)] * 4
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    for program in (step, prefills[16]):
        text = program.as_text()
        assert text.count(" while(") == 1
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < 1e9
        assert mem.alias_size_in_bytes == pool_bytes
        header = text.split("\n", 1)[0]
        assert header.count("may-alias") + header.count(
            "must-alias") == len(leaves)
        assert relayouts(program, pool_bytes // len(leaves),
                         bodies=True) == []
    assert step.as_text().count("tpu_custom_call") == 2
    # (what a loop step gathered of a pool: 8 lanes of 48 pages)
    assert gathered_selects(step, 8 * 48 * 16 * 2048 * 2) == []
    assert table_gathers(step, 48 * 16 * 2048 * 2) == []
    assert prefills[16].as_text().count("tpu_custom_call") == 2


# ---------------------------- a ring of pages a lane beside the paged pool


def test_window_and_full_serving_programs_compile_for_v5e(one_chip):
    """The leading dense layer (sliding), one sparse sliding layer and the
    sparse full layer at the published widths of
    ``perfbench/configs/trinity-mini.json`` under ``longdoc_closed32``'s
    engine settings: the decode step over 16 slots reads A RING of 129
    pages a lane in a window layer (2,064 rows, whatever the context) and
    the held pages of a table of 2,080 in the full layer, each through a
    paged-attention call (PR 45: until then a gather of either, whole),
    each pool donated and aliased; the prefill keeps the
    flash kernel (a band of 2,048) in both sliding layers and drops the
    last layer's mixer and experts, whose context a prefill never reads."""
    from distributed_tensorflow_tpu.serving.engine import EngineConfig
    from perfbench import spec, worker
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         "trinity-mini.json"))
    kinds = (gpt_lib.SLIDING_ATTENTION,) * 2 + (gpt_lib.FULL_ATTENTION,)
    cfg = dataclasses.replace(worker.gpt_config(
        {"config": config, "config_file": "trinity-mini.json"}),
        num_layers=3, layer_kinds=kinds, vocab_size=32768)
    model = gpt_lib.GptLM(cfg)
    traffic = spec.load_json(os.path.join(spec.HERE, "traffic",
                                          "longdoc_closed32.json"))
    econf = EngineConfig(**traffic["engine"])

    def described(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    engine = bare_engine(model, econf, sparse_layers=2)
    assert (engine.geometry.window_layers,
            engine.geometry.ring_pages) == (2, 129)
    tree = described(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    pools = described(jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        cfg, econf.num_pages, econf.page_size, num_slots=econf.num_slots)))
    leaves = jax.tree.leaves(pools)
    # a window layer: 16 rings of 129 pages and its sentinel's page
    assert [x.shape for x in leaves] == [(16 * 129 + 1, 16, 512)] * 4 + [
        (33280 + 1, 16, 512)] * 2
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    B, MP, RP = econf.num_slots, econf.max_pages_per_seq, 129
    lowered = engine._build_step().lower(
        tree, i32(B), i32(B), (i32(B, MP), i32(B, RP)), pools, f32(B),
        i32(B), f32(B), i32(B))
    # 16 tokens and behind them 2 layers x 128 experts of histogram
    assert lowered.out_info[0].shape == (B + 2 * 128,)
    step = lowered.compile()
    text = step.as_text()
    # three grouped products a sparse layer, a paged-attention call a layer
    assert text.count("tpu_custom_call") == 2 * 3 + 3
    # neither kind of layer gathers its pool (a lane's ring: 129 pages), and
    # nothing is left to blank or to mask at the table's size
    assert table_gathers(step, 129 * 16 * 512 * 2) == []
    assert gathered_selects(step, 16 * 129 * 16 * 512 * 2) == []
    prefill = engine._prefill_fn(256).lower(
        tree, i32(1, 4096), pools, i32(256), ring=i32(129)).compile()
    # two banded flash calls and ONE sparse layer's grouped products
    assert prefill.as_text().count("tpu_custom_call") == 2 + 3
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    for program in (step, prefill):
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < 2e9
        assert mem.alias_size_in_bytes == pool_bytes
        header = program.as_text().split("\n", 1)[0]
        assert header.count("may-alias") + header.count(
            "must-alias") == len(leaves)
        assert relayouts(program, leaves[0].size * 2) == []


def test_conv_and_head64_serving_programs_compile_for_v5e(one_chip):
    """The leading dense convolution layer, one sparse attention layer and
    one sparse convolution layer at the published widths of
    ``perfbench/configs/lfm2-24b-a2b.json`` under ``agents_closed128``'s
    engine settings: the decode step reads the attention layer's pool
    through ONE paged-attention call at heads of 64 (two kv heads a lane
    tile: the kernel sees 4 kv "heads" of 128 in the flat row of 512) and
    gathers no table; a convolution layer's entry is a tail a slot, [slots,
    2, 2048], donated and aliased like the pools; the prefill keeps the
    flash kernel at head 64 and drops the last layer's out projection and
    experts, of which a prefill needs the tail alone."""
    from distributed_tensorflow_tpu.serving.engine import EngineConfig
    from perfbench import spec, worker
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         "lfm2-24b-a2b.json"))
    kinds = (gpt_lib.SHORT_CONV, gpt_lib.FULL_ATTENTION, gpt_lib.SHORT_CONV)
    cfg = dataclasses.replace(worker.gpt_config(
        {"config": config, "config_file": "lfm2-24b-a2b.json"}),
        num_layers=3, layer_kinds=kinds, vocab_size=32768)
    assert cfg.head_dim == 64 and cfg.num_kv_heads == 8
    model = gpt_lib.GptLM(cfg)
    traffic = spec.load_json(os.path.join(spec.HERE, "traffic",
                                          "agents_closed128.json"))
    econf = EngineConfig(**traffic["engine"])

    def described(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    engine = bare_engine(model, econf, stateful=True, sparse_layers=2)
    tree = described(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    pools = described(jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        cfg, econf.num_pages, econf.page_size, num_slots=econf.num_slots)))
    leaves = jax.tree.leaves(pools)
    B, MP = econf.num_slots, econf.max_pages_per_seq
    assert [x.shape for x in leaves] == [
        (B, 2, 2048), (12288 + 1, 16, 512), (12288 + 1, 16, 512),
        (B, 2, 2048)]
    assert gpt_lib.paged_kernel_layers(cfg, pools) == 1
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    lowered = engine._build_step().lower(
        tree, i32(B), i32(B), i32(B, MP), pools, f32(B), i32(B), f32(B),
        i32(B))
    # the lanes' tokens and behind them 2 layers x 64 experts of histogram
    assert lowered.out_info[0].shape == (B + 2 * 64,)
    step = lowered.compile()
    # three grouped products a sparse layer, ONE paged-attention call
    assert step.as_text().count("tpu_custom_call") == 2 * 3 + 1
    # no lane's table is gathered (192 pages of 16 rows of 512), nothing
    # is blanked or masked at that size
    assert table_gathers(step, MP * 16 * 512 * 2) == []
    assert gathered_selects(step, B * MP * 16 * 512 * 2) == []
    prefill = engine._prefill_fn(128).lower(
        tree, i32(1, 2048), pools, i32(128), i32(), i32()).compile()
    # one flash call (head 64) and ONE sparse layer's grouped products:
    # the last layer's experts feed the logits alone
    assert prefill.as_text().count("tpu_custom_call") == 1 + 3
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    for program in (step, prefill):
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < 2e9
        assert mem.alias_size_in_bytes == pool_bytes
        header = program.as_text().split("\n", 1)[0]
        assert header.count("may-alias") + header.count(
            "must-alias") == len(leaves)
        assert relayouts(program, leaves[1].size * 2) == []


def test_route_ahead_and_heads_of_28_serving_programs_compile_for_v5e(
        one_chip):
    """One whole period (full, sliding, sliding, sliding) at the published
    widths of ``perfbench/configs/smallthinker-21b-a3b.json`` under
    ``mixed_closed32``'s engine settings: the decode step over 16 slots
    reads each layer's pool through ONE paged-attention call at 28 query
    heads in groups of seven, the block as it stands ([16, 28, 128]: Mosaic
    takes 28 rows, no padding in the wrapper), a ring of 257 pages a lane in
    a window layer and the held pages of a table of 832 in the full one,
    gathering neither; each layer's router reads the block's input, and in
    the compiled program's order its top 6 of 64 stand AHEAD of that
    layer's attention call.  The
    12,288-token prefill keeps the flash kernel in the full layer (scored
    whole, no rotation) and in two bands of 4,096, drops the LAST layer's
    mixer and experts, and holds under 2 GB of temporaries."""
    from distributed_tensorflow_tpu.serving.engine import EngineConfig
    from perfbench import spec, worker
    config = spec.load_json(os.path.join(spec.HERE, "configs",
                                         "smallthinker-21b-a3b.json"))
    whole = worker.gpt_config({"config": config,
                               "config_file": "smallthinker-21b-a3b.json"})
    cfg = dataclasses.replace(whole, num_layers=4,
                              layer_kinds=whole.layer_kinds[:4],
                              vocab_size=32768)
    assert cfg.layer_kinds == (gpt_lib.FULL_ATTENTION,) + (
        gpt_lib.SLIDING_ATTENTION,) * 3
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (28, 4, 128)
    model = gpt_lib.GptLM(cfg)
    traffic = spec.load_json(os.path.join(spec.HERE, "traffic",
                                          "mixed_closed32.json"))
    econf = EngineConfig(**traffic["engine"])

    def described(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=one_chip), tree)

    engine = bare_engine(model, econf, sparse_layers=4)
    geo = engine.geometry
    assert (geo.window_layers, geo.ring_pages, geo.route_ahead_layers) == (
        3, 257, 4)
    tree = described(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    pools = described(jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        cfg, econf.num_pages, econf.page_size, num_slots=econf.num_slots)))
    leaves = jax.tree.leaves(pools)
    assert [x.shape for x in leaves] == [(13312 + 1, 16, 512)] * 2 + [
        (16 * 257 + 1, 16, 512)] * 6
    assert gpt_lib.paged_kernel_layers(cfg, pools) == 4
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    B, MP, RP = econf.num_slots, econf.max_pages_per_seq, 257
    lowered = engine._build_step().lower(
        tree, i32(B), i32(B), (i32(B, MP), i32(B, RP)), pools, f32(B),
        i32(B), f32(B), i32(B))
    # 16 tokens and behind them 4 layers x 64 experts of histogram
    assert lowered.out_info[0].shape == (B + 4 * 64,)
    step = lowered.compile()
    text = step.as_text()
    # three grouped products and a paged-attention call a layer
    assert text.count("tpu_custom_call") == 4 * 3 + 4
    assert table_gathers(step, 257 * 16 * 512 * 2) == []
    assert gathered_selects(step, 16 * 257 * 16 * 512 * 2) == []
    # the compiled order, layer by layer: the route's first operation,
    # then the attention's call, then the experts' grouped products
    entry = text[text.index("ENTRY"):].splitlines()

    def first(layer, region, what=""):
        return next(i for i, line in enumerate(entry)
                    if f"layer{layer}." in line and region in line
                    and what in line)
    for layer in range(4):
        route = first(layer, "moe.route", " sort(")
        attend = first(layer, "attn.scores", "tpu_custom_call")
        experts = first(layer, "moe.experts", "tpu_custom_call")
        assert route < attend < experts, (layer, route, attend, experts)
    prefill = engine._prefill_fn(768).lower(
        tree, i32(1, 12288), pools, i32(768), ring=i32(257)).compile()
    # the full layer's flash call and two bands', and THREE layers'
    # grouped products: the last layer's mixer and experts feed the logits
    # alone
    assert prefill.as_text().count("tpu_custom_call") == 3 + 3 * 3
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    for program in (step, prefill):
        mem = program.memory_analysis()
        assert mem.temp_size_in_bytes < 2e9
        assert mem.alias_size_in_bytes == pool_bytes
        header = program.as_text().split("\n", 1)[0]
        assert header.count("may-alias") + header.count(
            "must-alias") == len(leaves)
    assert relayouts(step, leaves[2].size * 2) == []
    # No pool is re-laid out by the prefill either.  What it does re-lay
    # out, once a whole layer, is the experts' rows put back in token order
    # on their way to the weighted sum, [12288 x 6, 2560] -> [12288, 6,
    # 2560] in float32 (755 MB): SIX experts a token fill no sublane tile
    # of 8, where the 8 and the 4 of the other configurations make that
    # reshape a bitcast (ROADMAP R1: a measured line for a later PR).
    moved = relayouts(prefill, leaves[2].size * 2)
    assert len(moved) == 3 and all(
        "f32[12288,6,2560]" in line and "._mlp/mlp/" in line
        for line in moved), moved
