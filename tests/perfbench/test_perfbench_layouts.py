"""What the harness knows about an architecture it finds through the
configuration's file (PR 28): the leaves (``layout``), the operations and
bytes of a step (``costs``), as it already found the ``reference``.

Two halves.  The configurations the benchmark has name neither key and get
what they got before, bit for bit: ``GOLDEN`` holds a checksum of every
leaf, taken ON THE PARENT (commit 763996b, where ``perfbench/weights.py``
held the one closed list of leaves) at rehearsal sizes by

    mkdir -p /root/scratch/parent && git archive 763996b | tar -x -C /root/scratch/parent
    cd /root/scratch/parent && JAX_PLATFORMS=cpu python -c "
    import sys; sys.path[:0] = ['.', '/root/repo/tests/perfbench']
    import test_perfbench_layouts as t; t.print_golden()"

And a configuration of another shape needs new files only: a toy layout of
two kinds of layer and a toy costs file, both under ``tests/perfbench/data``.
"""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import costs, peaks, spec, weights, worker
from perfbench.metrics import _common

DATA = "tests/perfbench/data"
SEEDS = [7, 2 ** 31 + 77]
CONFIGS = ["gpt2-medium", "mistral-7b"]


def rehearsal_config(name):
    cfg = spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))
    return spec.deep_update(cfg, cfg["rehearsal"])


def flat_tree(maker, seed):
    """Every leaf the maker makes, ``layer{i}/name`` and top-level."""
    halves = weights.seed_halves(seed)
    flat = dict(maker.top(halves))
    for i in range(maker.num_layers):
        flat.update({f"layer{i}/{n}": v
                     for n, v in maker.layer(halves, i).items()})
    return flat


def checksums(name, seed):
    flat = flat_tree(weights.Maker(rehearsal_config(name)), seed)
    return {n: f"{v.dtype}{list(v.shape)}:"
               f"{zlib.crc32(np.asarray(v).tobytes()):08x}"
            for n, v in sorted(flat.items())}


def print_golden():
    print(json.dumps({f"{n}@{s}": checksums(n, s)
                      for n in CONFIGS for s in SEEDS}, indent=1))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_every_leaf_of_the_benchmarks_configurations_is_the_parents(name,
                                                                    seed):
    cfg = rehearsal_config(name)
    assert "layout" not in cfg and "costs" not in cfg
    got, want = checksums(name, seed), GOLDEN[f"{name}@{seed}"]
    assert sorted(got) == sorted(want)
    assert {n: c for n, c in got.items() if c != want[n]} == {}


# ------------------------------------------------------- the toy layout


def toy_config(types=("scan", "scan", "attn", "scan"), **over):
    model = {"num_layers": len(types), "hidden_size": 64, "heads": 2,
             "vocab_size": 32, "layer_types": list(types)}
    return {"model": model, "param_dtype": "float32",
            "init": {"bias_std": 0.02, "embedding_std": 0.5},
            "layout": f"{DATA}/toy_layout.py",
            "costs": f"{DATA}/toy_costs.py", **over}


class CompileCount:
    """Programs compiled, or loaded from a persistent cache, while open."""

    def __init__(self):
        self.n, self.open = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if self.open and event in worker.Compiles.EVENTS:
            self.n += 1

    def __enter__(self):
        self.n, self.open = 0, True
        return self

    def __exit__(self, *exc):
        self.open = False


def test_one_program_a_kind_and_a_second_seed_compiles_nothing():
    maker = weights.Maker(toy_config())
    assert maker.kinds == ["scan", "scan", "attn", "scan"]
    assert maker.num_layers == 4 and sorted(maker._layer) == ["attn", "scan"]
    halves = [weights.seed_halves(s) for s in SEEDS]
    jax.block_until_ready(halves)
    count = CompileCount()
    with count:
        first = flat_tree(maker, SEEDS[0])
    assert count.n == 3          # two kinds of layer and the top, not 4 + 1
    with count:
        second = flat_tree(maker, SEEDS[1])
        again = flat_tree(maker, SEEDS[0])
    assert count.n == 0
    assert all(np.array_equal(first[n], again[n]) for n in first)
    assert not np.array_equal(first["layer0/proj/kernel"],
                              second["layer0/proj/kernel"])


def test_program_tree_nests_each_layer_by_its_kind():
    cfg = toy_config()
    lay = weights.layout(cfg)
    tree = weights.program_tree(SEEDS[1], weights.Maker(cfg))
    assert sorted(tree) == ["layer0", "layer1", "layer2", "layer3",
                            "ln_final", "word_emb"]
    for i, kind in enumerate(cfg["model"]["layer_types"]):
        got = {"/".join(k.key for k in path): x.shape for path, x in
               jax.tree_util.tree_flatten_with_path(tree[f"layer{i}"])[0]}
        assert got == {n: tuple(d["shape"] if isinstance(d, dict) else d)
                       for n, d in lay.layer(cfg["model"], kind).items()}
    # The same leaf name at another shape in the other kind.
    assert tree["layer2"]["proj"]["kernel"].shape == (64, 64)
    assert tree["layer0"]["proj"]["kernel"].shape == (64, 128)
    assert tree["layer0"]["decay"].shape == (2,)    # a leaf with no parent


def test_a_leaf_depends_on_seed_index_and_name_and_on_nothing_else():
    a = flat_tree(weights.Maker(toy_config()), SEEDS[0])
    # Another order of kinds: layers 1 and 3 are "scan" in both.
    b = flat_tree(weights.Maker(toy_config(("attn", "scan", "scan", "scan"))),
                  SEEDS[0])
    for i in (1, 3):
        for n in ("decay", "conv/kernel", "proj/kernel"):
            assert np.array_equal(a[f"layer{i}/{n}"], b[f"layer{i}/{n}"])
    # A neighbour of the same kind holds other numbers.
    assert not np.array_equal(a["layer0/proj/kernel"], a["layer1/proj/kernel"])
    assert not np.array_equal(a["layer0/decay"], a["layer1/decay"])
    # And what the plain reference would ask for by name is the same leaf.
    cfg = toy_config()
    direct = weights.layer_leaves(
        weights.base_key_from(weights.seed_halves(SEEDS[0])), 3, cfg["model"],
        cfg["init"], jnp.float32,
        weights.layout(cfg).layer(cfg["model"], "scan"))
    assert np.array_equal(direct["conv/kernel"], a["layer3/conv/kernel"])


def test_leaves_of_a_new_sort_are_drawn_as_the_layout_says():
    flat = flat_tree(weights.Maker(toy_config()), SEEDS[0])
    decay = np.concatenate([flat[f"layer{i}/decay"] for i in (0, 1, 3)])
    assert decay.min() >= 0.5 and decay.max() < 2.0 and decay.std() > 0
    assert np.array_equal(flat["layer0/gate_norm/scale"], np.ones(64))
    # fan_in 4, not the first axis (64): a spread of 1/2, not 1/8.
    assert 0.4 < flat["layer0/conv/kernel"].std() < 0.6
    # Today's four sorts, by name, where the layout gives a bare shape.
    assert np.array_equal(flat["layer2/ln_attn/scale"], np.ones(64))
    assert 0.1 < flat["layer2/proj/kernel"].std() < 0.15        # 1/8
    assert 0.015 < flat["layer2/proj/bias"].std() < 0.025       # bias_std
    assert 0.4 < flat["word_emb/embedding"].std() < 0.6


def test_a_layout_out_of_step_is_named():
    with pytest.raises(ValueError, match="toy_layout.py gives 4 layers"):
        cfg = toy_config()
        cfg["model"]["num_layers"] = 6
        weights.Maker(cfg)
    with pytest.raises(ValueError, match="'decay'.*'lognormal'"):
        weights.leaf(jax.random.key(0), "decay",
                     {"shape": (2,), "lognormal": 1.0}, {}, jnp.float32)
    # The program's tree against a layout that is not its own.
    cell = {"config": rehearsal_config("gpt2-medium"), "config_file": "x"}
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = gpt_lib.GptLM(worker.gpt_config(cell))
    params = weights.program_tree(SEEDS[0], weights.Maker(toy_config()))
    with pytest.raises(SystemExit, match="toy_layout.py does not match"):
        worker.check_tree(jax, model, params, toy_config())
    assert worker.check_tree(
        jax, model, weights.program_tree(
            SEEDS[0], weights.Maker(cell["config"])), cell["config"]) > 0


# --------------------------------------------------------- the counts


def traced_context(cfg, kind):
    """One whole piece of work of each sort inside a traced slice."""
    ctx = {"kind": kind, "config": cfg, "chips": 1,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"busy_s": 4.0, "t0": 10.0, "t1": 20.0},
           "counters": {"rows": 2, "seq": 64, "n_params": 10 ** 6}}
    if kind == "train":
        ctx["steps"] = [{"t_start": 11.0, "t_end": 12.0},
                        {"t_start": 19.5, "t_end": 20.5}]      # cut: left out
    else:
        ctx["steps"] = [{"admits": [[11.0, 11.5, 100]], "context": [100, 7],
                         "t_decode": 11.5, "t_end": 12.0}]
    return ctx


@pytest.mark.parametrize("kind,pieces", [("train", 1), ("serve", 2)])
def test_the_roofline_reads_the_counts_the_configuration_names(kind, pieces):
    toy = traced_context(toy_config(), kind)
    assert _common.costs_of(toy["config"]).__file__.endswith("toy_costs.py")
    # The toy counts a second of the matmul peak for every piece of work.
    seconds, by = _common.traced_least_seconds(toy)
    assert seconds == pytest.approx(pieces) and by["memory"] == 0.0
    assert _common.step_roofline_pct(toy) == pytest.approx(25.0 * pieces)


@pytest.mark.parametrize("name,kind", [("gpt2-medium", "train"),
                                       ("mistral-7b", "serve")])
def test_without_the_key_the_counts_are_perfbench_costs(name, kind):
    cfg = spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))
    assert _common.costs_of(cfg) is costs
    ctx = traced_context(cfg, kind)
    pk = peaks.peaks_for("TPU v5 lite")
    if kind == "train":
        want = [costs.train_step(cfg, 2, 64, 10 ** 6)]
    else:
        want = [costs.prefill(cfg, 100), costs.decode_step(cfg, [100, 7])]
    assert _common.traced_least_seconds(ctx)[0] == pytest.approx(
        sum(costs.least_time(c, pk)["seconds"] for c in want))


# ------------------------------------------- the way into the program


def test_gpt_config_turns_lists_into_tuples():
    cfg = rehearsal_config("mistral-7b")
    cfg["model"]["attention_window"] = [0, 0, [64, 0]]
    gcfg = worker.gpt_config({"config": cfg, "config_file": "x.json"})
    assert gcfg.attention_window == (0, 0, (64, 0))
    assert hash(gcfg) == hash(worker.gpt_config(
        {"config": cfg, "config_file": "x.json"}))
    assert not hasattr(gcfg, "norm_eps")


def test_a_key_the_program_lacks_stops_the_run_by_name():
    cfg = rehearsal_config("mistral-7b")
    cfg["model"]["layer_types"] = ["linear_attention", "full_attention"]
    with pytest.raises(SystemExit) as e:
        worker.gpt_config({"config": cfg,
                           "config_file": "perfbench/configs/some.json"})
    assert "'layer_types'" in str(e.value)
    assert "perfbench/configs/some.json" in str(e.value)


# Taken on the parent by the command in this file's docstring.
GOLDEN = {
    "gpt2-medium@7": {
        "layer0/ln_attn/bias": "float32[64]:0d968558",
        "layer0/ln_attn/scale": "float32[64]:b68dcaa8",
        "layer0/ln_mlp/bias": "float32[64]:0d968558",
        "layer0/ln_mlp/scale": "float32[64]:b68dcaa8",
        "layer0/mlp_in/bias": "float32[128]:1d793f79",
        "layer0/mlp_in/kernel": "float32[64, 128]:69e0cbb1",
        "layer0/mlp_out/bias": "float32[64]:e6128474",
        "layer0/mlp_out/kernel": "float32[128, 64]:f25e076f",
        "layer0/out/bias": "float32[64]:4524eb12",
        "layer0/out/kernel": "float32[4, 16, 64]:46996426",
        "layer0/qkv/bias": "float32[3, 4, 16]:59a35962",
        "layer0/qkv/kernel": "float32[64, 3, 4, 16]:8ff4b7ce",
        "layer1/ln_attn/bias": "float32[64]:0d968558",
        "layer1/ln_attn/scale": "float32[64]:b68dcaa8",
        "layer1/ln_mlp/bias": "float32[64]:0d968558",
        "layer1/ln_mlp/scale": "float32[64]:b68dcaa8",
        "layer1/mlp_in/bias": "float32[128]:51adef7f",
        "layer1/mlp_in/kernel": "float32[64, 128]:85e33233",
        "layer1/mlp_out/bias": "float32[64]:8c48d200",
        "layer1/mlp_out/kernel": "float32[128, 64]:f39703fb",
        "layer1/out/bias": "float32[64]:0d064090",
        "layer1/out/kernel": "float32[4, 16, 64]:abf893ab",
        "layer1/qkv/bias": "float32[3, 4, 16]:5956721d",
        "layer1/qkv/kernel": "float32[64, 3, 4, 16]:217e6b5c",
        "lm_head/bias": "float32[512]:df8afdbb",
        "lm_head/kernel": "float32[64, 512]:d69bc520",
        "ln_final/bias": "float32[64]:0d968558",
        "ln_final/scale": "float32[64]:b68dcaa8",
        "pos_emb/embedding": "float32[64, 64]:efb3977a",
        "word_emb/embedding": "float32[512, 64]:92268c66",
    },
    "gpt2-medium@2147483725": {
        "layer0/ln_attn/bias": "float32[64]:0d968558",
        "layer0/ln_attn/scale": "float32[64]:b68dcaa8",
        "layer0/ln_mlp/bias": "float32[64]:0d968558",
        "layer0/ln_mlp/scale": "float32[64]:b68dcaa8",
        "layer0/mlp_in/bias": "float32[128]:b52f6dda",
        "layer0/mlp_in/kernel": "float32[64, 128]:a2ee9c93",
        "layer0/mlp_out/bias": "float32[64]:72e5e3b8",
        "layer0/mlp_out/kernel": "float32[128, 64]:2368a44d",
        "layer0/out/bias": "float32[64]:486e827c",
        "layer0/out/kernel": "float32[4, 16, 64]:3fb6528d",
        "layer0/qkv/bias": "float32[3, 4, 16]:5000b4a8",
        "layer0/qkv/kernel": "float32[64, 3, 4, 16]:bcf199b3",
        "layer1/ln_attn/bias": "float32[64]:0d968558",
        "layer1/ln_attn/scale": "float32[64]:b68dcaa8",
        "layer1/ln_mlp/bias": "float32[64]:0d968558",
        "layer1/ln_mlp/scale": "float32[64]:b68dcaa8",
        "layer1/mlp_in/bias": "float32[128]:29b2f493",
        "layer1/mlp_in/kernel": "float32[64, 128]:faed3db9",
        "layer1/mlp_out/bias": "float32[64]:8e246fc5",
        "layer1/mlp_out/kernel": "float32[128, 64]:b1b3f87c",
        "layer1/out/bias": "float32[64]:ce6e2c2b",
        "layer1/out/kernel": "float32[4, 16, 64]:85ae79f8",
        "layer1/qkv/bias": "float32[3, 4, 16]:e6f8559a",
        "layer1/qkv/kernel": "float32[64, 3, 4, 16]:ced63c95",
        "lm_head/bias": "float32[512]:2bd104a6",
        "lm_head/kernel": "float32[64, 512]:c878b7f2",
        "ln_final/bias": "float32[64]:0d968558",
        "ln_final/scale": "float32[64]:b68dcaa8",
        "pos_emb/embedding": "float32[64, 64]:4c7a5bd2",
        "word_emb/embedding": "float32[512, 64]:0d52cd63",
    },
    "mistral-7b@7": {
        "layer0/kv_proj/bias": "bfloat16[2, 2, 16]:e1ace9e4",
        "layer0/kv_proj/kernel": "bfloat16[64, 2, 2, 16]:c9d85100",
        "layer0/ln_attn/scale": "bfloat16[64]:3d51dd98",
        "layer0/ln_mlp/scale": "bfloat16[64]:3d51dd98",
        "layer0/mlp_gate/kernel": "bfloat16[64, 128]:ac2f8759",
        "layer0/mlp_in/kernel": "bfloat16[64, 128]:2d17dd28",
        "layer0/mlp_out/kernel": "bfloat16[128, 64]:cb6a9d65",
        "layer0/out/bias": "bfloat16[64]:ae80b5b7",
        "layer0/out/kernel": "bfloat16[4, 16, 64]:79e5b5df",
        "layer0/q_proj/bias": "bfloat16[4, 16]:f3bae832",
        "layer0/q_proj/kernel": "bfloat16[64, 4, 16]:b1408114",
        "layer1/kv_proj/bias": "bfloat16[2, 2, 16]:034a12c4",
        "layer1/kv_proj/kernel": "bfloat16[64, 2, 2, 16]:00ae81bf",
        "layer1/ln_attn/scale": "bfloat16[64]:3d51dd98",
        "layer1/ln_mlp/scale": "bfloat16[64]:3d51dd98",
        "layer1/mlp_gate/kernel": "bfloat16[64, 128]:4e6142ea",
        "layer1/mlp_in/kernel": "bfloat16[64, 128]:ad4bddc9",
        "layer1/mlp_out/kernel": "bfloat16[128, 64]:3945c394",
        "layer1/out/bias": "bfloat16[64]:4648d159",
        "layer1/out/kernel": "bfloat16[4, 16, 64]:57961a94",
        "layer1/q_proj/bias": "bfloat16[4, 16]:f2d2a240",
        "layer1/q_proj/kernel": "bfloat16[64, 4, 16]:5cea9d3e",
        "lm_head/bias": "bfloat16[512]:c4922bfb",
        "lm_head/kernel": "bfloat16[64, 512]:d7107ad1",
        "ln_final/scale": "bfloat16[64]:3d51dd98",
        "word_emb/embedding": "bfloat16[512, 64]:6ca02e38",
    },
    "mistral-7b@2147483725": {
        "layer0/kv_proj/bias": "bfloat16[2, 2, 16]:9f311af4",
        "layer0/kv_proj/kernel": "bfloat16[64, 2, 2, 16]:33a9d5d2",
        "layer0/ln_attn/scale": "bfloat16[64]:3d51dd98",
        "layer0/ln_mlp/scale": "bfloat16[64]:3d51dd98",
        "layer0/mlp_gate/kernel": "bfloat16[64, 128]:dac568ec",
        "layer0/mlp_in/kernel": "bfloat16[64, 128]:ed71bfa7",
        "layer0/mlp_out/kernel": "bfloat16[128, 64]:79f69b31",
        "layer0/out/bias": "bfloat16[64]:19be2c18",
        "layer0/out/kernel": "bfloat16[4, 16, 64]:662349af",
        "layer0/q_proj/bias": "bfloat16[4, 16]:eb1cdbb1",
        "layer0/q_proj/kernel": "bfloat16[64, 4, 16]:e7fabab3",
        "layer1/kv_proj/bias": "bfloat16[2, 2, 16]:28b5bb2c",
        "layer1/kv_proj/kernel": "bfloat16[64, 2, 2, 16]:b1c9a13f",
        "layer1/ln_attn/scale": "bfloat16[64]:3d51dd98",
        "layer1/ln_mlp/scale": "bfloat16[64]:3d51dd98",
        "layer1/mlp_gate/kernel": "bfloat16[64, 128]:43554251",
        "layer1/mlp_in/kernel": "bfloat16[64, 128]:5c265a50",
        "layer1/mlp_out/kernel": "bfloat16[128, 64]:783e8b5f",
        "layer1/out/bias": "bfloat16[64]:12163a71",
        "layer1/out/kernel": "bfloat16[4, 16, 64]:ff5bfcad",
        "layer1/q_proj/bias": "bfloat16[4, 16]:d4e768a4",
        "layer1/q_proj/kernel": "bfloat16[64, 4, 16]:01ac7c55",
        "lm_head/bias": "bfloat16[512]:3bc632ff",
        "lm_head/kernel": "bfloat16[64, 512]:700a281b",
        "ln_final/scale": "bfloat16[64]:3d51dd98",
        "word_emb/embedding": "bfloat16[512, 64]:66149072",
    },
}
