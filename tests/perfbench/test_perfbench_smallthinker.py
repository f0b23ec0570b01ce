"""What ``smallthinker-21b-a3b`` brings to the benchmark as new files: its
configuration against the published one (the catalog row's every number but
what ``reduced`` names), its layout against the program's own tree and its
``deployment``'s bytes against that tree, its counts at the published widths
(hand counts at two shapes, and never under a brute-force count of what the
reference touches), the readers of every ``st_`` metric over a fixture, the
cell's rehearsal end to end, and the message a program without the fields
stops with.  Every new entry of ``BENCHMARK.json`` is pinned BY NAME: nothing
here counts the benchmark's cells or metrics, nor looks at them by position.
"""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from perfbench import peaks, spec, traffic, weights, worker
from perfbench.metrics import _common

CELL = "serve_smallthinker_mixedqueue"
NAME = "smallthinker-21b-a3b"
CONFIG_FILE = f"perfbench/configs/{NAME}.json"
CONFIG = spec.load_json(os.path.join(spec.ROOT, CONFIG_FILE))
COSTS = spec.named_module(CONFIG, "costs")
TRAFFIC = spec.load_json(os.path.join(spec.HERE, "traffic",
                                      "mixed_closed32.json"))
SLIDING, FULL = "sliding_attention", "full_attention"
# Seventeen of the issue's nineteen: ``BENCHMARK.json`` may hold 128
# per-layer metrics (the builder's contract with the driver; pinned in
# ``test_the_new_entries_by_name``) and held 111.  Left out, as worth the
# least: ``st_lanes_live_pct`` (100 by construction: 32 callers over 16
# slots; ``lanes_live`` is still the wrapped share's denominator) and
# ``st_expert_load_peak`` (a balanced draw's chance imbalance).
ST_METRICS = [
    "st_compile_s", "st_compiles_in_window", "st_decode_attn_ms",
    "st_decode_experts_ms", "st_decode_matmul_ms", "st_decode_route_ms",
    "st_decode_unnamed_ms", "st_device_idle_pct", "st_experts_touched_pct",
    "st_hbm_peak_gib", "st_kv_pages_peak_pct", "st_prefill_attn_pct",
    "st_prefill_share_pct", "st_step_roofline",
    "st_window_lanes_wrapped_pct", "st_window_pages_peak_pct",
    "st_window_table_held_pct"]


def reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics", name + ".py"))


def entry_of(group, name):
    return next(e for e in spec.benchmark()[group] if e["name"] == name)


# ------------------------------------------------------- the configuration


def test_the_file_holds_the_published_config_but_what_reduced_names():
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert {k: CONFIG[k] for k in published} == published
    entry = entry_of("configs", NAME)
    assert entry["file"] == CONFIG_FILE and entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert {k: CONFIG[k] for k in entry["reduced"]} == {
        "num_hidden_layers": 12, "rope_layout": [0, 1, 1, 1] * 3,
        "sliding_window_layout": [0, 1, 1, 1] * 3}
    assert CONFIG["published"] == {
        "num_hidden_layers": 52, "rope_layout": "(0, 1, 1, 1) x 13",
        "sliding_window_layout": "(0, 1, 1, 1) x 13"}
    # no width among the cut keys, and the program's config is the
    # published one, key for key
    m = CONFIG["model"]
    assert (m["hidden_size"], m["num_heads"], m["kv_heads"], m["head_size"],
            m["vocab_size"], m["num_layers"], m["max_position"]) == (
        2560, 28, 4, 128, 151936, 12, 16384)
    assert (m["num_experts"], m["experts_per_token"],
            m["expert_intermediate_size"], m["num_shared_experts"],
            m["first_dense_layers"]) == (64, 6, 768, 0, 0)
    assert (m["router_input"], m["router_score"],
            m["expert_activation"]) == ("mixer_in", "softmax", "relu")
    # a period BEGINS with its full layer; rotation on the windowed ones
    assert m["layer_kinds"] == [FULL, SLIDING, SLIDING, SLIDING] * 3
    assert [int(k == SLIDING) for k in m["layer_kinds"]] \
        == CONFIG["rope_layout"] == CONFIG["sliding_window_layout"]
    assert (m["sliding_window"], m["rope_base"], m["rope_kinds"]) == (
        4096, 1.5e6, [SLIDING])
    assert m["norm_placement"] == "pre" and m["norm"] == "rmsnorm"
    assert m["norm_eps"] == CONFIG["rms_norm_eps"]
    assert "routed_scaling_factor" not in m and "qk_head_norm" not in m
    assert "chips that share a layer: 1" in CONFIG["deployment"]
    assert "layers 0-11" in CONFIG["deployment"]
    # the engine frees each bfloat16 leaf as it quantizes it: the worker
    # still holds the tree, and 11.12 + 5.56 GB do not fit beside the pools
    assert CONFIG["lower_precision"] == {
        "quantize": "int8", "kv_dtype": "float8", "consume_params": True}
    said = " ".join(CONFIG["assumed"])
    for what in ("input_layernorm", "softmax over", "ReLU", "no bias",
                 "split-half", "rope_layout 0", "including the query's own",
                 "not re-read"):
        assert what in said, what


def test_the_catalog_row_is_the_file_where_the_catalog_is_at_hand():
    """Every number of the catalog entry's ``config`` under the same key,
    but the keys ``reduced`` names."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside this checkout")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert row["source_url"] == CONFIG["source"]
    reduced = set(entry_of("configs", NAME)["reduced"])
    for key, value in row["config"].items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    assert CONFIG["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"] == 52
    # the cut is three whole published periods, from the first layer on
    for key in ("rope_layout", "sliding_window_layout"):
        assert CONFIG[key] == row["config"][key][:12]
        assert row["config"][key] == [0, 1, 1, 1] * 13


def test_the_traffic_is_the_issues_and_both_pools_hold_every_lane():
    eng = TRAFFIC["engine"]
    assert (TRAFFIC["kind"], TRAFFIC["loop"], TRAFFIC["callers"]) == (
        "serve", "closed", 32)
    assert eng == {"num_slots": 16, "page_size": 16, "num_pages": 13312,
                   "max_pages_per_seq": 832, "prefill_chunk": 0,
                   "prefill_cache_cap": 8}
    assert TRAFFIC["prompt"]["values"] == [512, 2048, 6144, 12288]
    assert TRAFFIC["output"]["values"] == [128, 256, 512, 1024]
    assert (TRAFFIC["check_sample"], TRAFFIC["check_pad"]) == (8, 13312)
    cap = eng["page_size"] * eng["max_pages_per_seq"]
    worst = max(TRAFFIC["prompt"]["values"]) + max(
        TRAFFIC["output"]["values"])
    assert worst == cap == TRAFFIC["check_pad"] \
        <= CONFIG["model"]["max_position"]
    # a Latin square: means 5,248 in and 480 out
    combos = traffic._combos(TRAFFIC)
    assert len(set(combos)) == 16
    assert sum(p for p, _ in combos) / 16 == 5248
    assert sum(o for _, o in combos) / 16 == 480
    # one prefill program a prompt length, all resident
    assert len(traffic.serve_buckets(TRAFFIC, eng["page_size"])) == 4 \
        <= eng["prefill_cache_cap"]
    # half the prompts end inside the window, half one to three deep in it
    window = CONFIG["model"]["sliding_window"]
    assert sorted(p // window for p in TRAFFIC["prompt"]["values"]) \
        == [0, 0, 1, 3]
    gcfg = worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    assert gcfg.ring_pages(eng["page_size"]) == 257
    assert gcfg.window_layers == 9
    # bytes: a token 2,048 B a layer; the full pool 1.31 GB, the rings 1.21
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    assert gpt_lib.kv_row_bytes_per_token(gcfg) == 3 * 2048
    assert gpt_lib.kv_row_bytes_per_token(gcfg, window=True) == 9 * 2048
    assert eng["num_pages"] * 16 * 3 * 2048 == pytest.approx(1.31e9,
                                                             rel=0.01)
    assert 16 * 257 * 16 * 9 * 2048 == pytest.approx(1.21e9, rel=0.01)
    # every bucket is a multiple of 512: the flash kernel's layout holds
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fl
    assert all(fl._layout_ok(p) for p in TRAFFIC["prompt"]["values"])
    # the rehearsal, too, holds lanes inside ITS window beside lanes past
    # the rows of its ring
    small = spec.cell(CELL, rehearse=True)
    ring = (small["config"]["model"]["sliding_window"]
            // small["traffic"]["engine"]["page_size"] + 1) \
        * small["traffic"]["engine"]["page_size"]
    prompts = small["traffic"]["prompt"]["values"]
    assert min(prompts) + max(small["traffic"]["output"]["values"]) < ring \
        < max(prompts)
    assert (small["traffic"]["callers"],
            small["traffic"]["engine"]["num_slots"]) == (4, 2)


def test_the_layout_is_the_programs_tree_at_rehearsal_size():
    cfg = spec.deep_update(CONFIG, CONFIG["rehearsal"])
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = gpt_lib.GptLM(worker.gpt_config(
        {"config": cfg, "config_file": CONFIG_FILE}))
    maker = weights.Maker(cfg)
    assert maker.kinds == ["sparse." + FULL] + ["sparse." + SLIDING] * 3 \
        + (["sparse." + FULL] + ["sparse." + SLIDING] * 3) * 2
    params = weights.program_tree(7, maker)
    assert worker.check_tree(jax, model, params, cfg) > 0
    layer = params["layer1"]
    assert set(layer) == {"ln_attn", "ln_mlp", "q_proj", "kv_proj", "out",
                          "router", "experts_gate", "experts_up",
                          "experts_down"}
    assert layer["experts_gate"].shape == (64, 64, 16)
    assert layer["q_proj"]["kernel"].shape == (64, 14, 16)      # groups of 7
    assert layer["kv_proj"]["kernel"].shape == (64, 2, 2, 16)
    assert layer["router"]["kernel"].shape == (64, 64)
    assert float(jnp.max(jnp.abs(layer["q_proj"]["bias"]))) == 0.0
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))  # noqa: E731
    assert std(layer["experts_gate"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(layer["experts_down"]) == pytest.approx(16 ** -0.5, rel=0.05)
    assert std(layer["router"]["kernel"]) == pytest.approx(64 ** -0.5,
                                                           rel=0.05)
    # a full and a sliding layer have the same leaves, other values
    assert jax.tree.map(jnp.shape, params["layer0"]) == jax.tree.map(
        jnp.shape, params["layer1"])
    assert not jnp.array_equal(params["layer0"]["experts_up"],
                               params["layer1"]["experts_up"])


def test_the_deployments_bytes_are_the_built_trees():
    """The bytes ``deployment`` states, re-reckoned from the tree the
    program builds at the published widths (shapes only: nothing is
    made)."""
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    gcfg = worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    model = gpt_lib.GptLM(gcfg)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    size = lambda t: sum(math.prod(x.shape)  # noqa: E731
                         for x in jax.tree.leaves(t))
    layer = size(tree["layer0"])
    assert all(size(tree[f"layer{i}"]) == layer for i in range(12))
    top = size(tree) - 12 * layer
    assert (layer, top, size(tree)) == (398_635_008, 778_066_816,
                                        5_561_686_912)
    assert size(tree["layer0"]["experts_gate"]) * 3 == 377_487_360
    said = CONFIG["deployment"]
    for number in ("5,561.7 M", "11.12 GB", "398.6 M", "778.1 M",
                   "1.31 GB", "1.21 GB", "13.64 GB"):
        assert number in said, number
    eng = TRAFFIC["engine"]
    pools = jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        gcfg, eng["num_pages"], eng["page_size"],
        num_slots=eng["num_slots"]))
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree.leaves(pools))
    assert pool_bytes == pytest.approx(1.31e9 + 1.21e9, rel=0.005)
    resident = 2 * size(tree) + pool_bytes
    assert resident == pytest.approx(13.64e9, rel=0.002)
    assert resident > 0.6 * 16e9


# --------------------------------------------------------------- the counts


def test_counts_at_published_widths():
    d = COSTS.dims(CONFIG)
    assert (d["L"], d["window"], d["heads"], d["kv"], d["D"]) == (
        12, 4096, 28, 4, 128)
    assert d["attn_params"] == 2 * 2560 * 3584 + 2 * 2560 * 512 == 20_971_520
    assert d["expert_params"] == 3 * 2560 * 768 == 5_898_240
    layer = d["attn_params"] + 64 * d["expert_params"] + d["router_params"]
    assert layer == 398_622_720          # the tree's 398.6 M less its
    #                                      norms and zero biases
    assert COSTS.active_params(d) == 12 * (20_971_520 + 163_840
                                           + 6 * 5_898_240)


@pytest.mark.parametrize("lanes,touched", [(1, 6.0), (4, 20.8),
                                           (16, 50.7)])
def test_a_decode_step_counts_the_window_and_the_experts_expected(lanes,
                                                                  touched):
    d = COSTS.dims(CONFIG)
    assert COSTS.experts_touched(64, 6, lanes) == pytest.approx(touched,
                                                                abs=0.06)
    ctx = [9000] * lanes
    step = COSTS.decode_step(CONFIG, ctx)
    outside = 2.0 * (12 * (d["attn_params"] + d["router_params"])
                     + d["head_params"])
    experts_b = 2.0 * 12 * COSTS.experts_touched(64, 6, lanes) \
        * d["expert_params"]
    # nine layers read the window, three the whole context; a row 2,048 B
    rows = lanes * (9 * 4096 + 3 * 9000)
    rows_b = 2048.0 * (rows + lanes * 12)
    assert step["bytes"] == pytest.approx(outside + experts_b + rows_b)
    assert experts_b <= 2.0 * 12 * min(64, 6 * lanes) * d["expert_params"]
    assert step["flops"] == pytest.approx(
        2.0 * lanes * (COSTS.active_params(d) + d["head_params"])
        + 2.0 * 2.0 * 28 * 128 * rows)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["bound"] == "memory"
    # a lane inside the window reads what it has, in every layer
    assert COSTS.rows_attended(d, 500) == 12 * 500
    assert COSTS.rows_attended(d, 4096) == 12 * 4096
    assert COSTS.rows_attended(d, 4097) == 9 * 4096 + 3 * 4097


def test_the_step_the_issue_reckons():
    """16 full lanes at the mix's mean context: 50.7 of 64 experts a layer
    (7.2 GB of the experts' 9.1), rings and tables about 1.4 GB, everything
    else 1.3 GB: 12 ms at the chip's bandwidth."""
    d = COSTS.dims(CONFIG)
    step = COSTS.decode_step(CONFIG, [5488] * 16)
    experts_b = 2.0 * 12 * COSTS.experts_touched(64, 6, 16) \
        * d["expert_params"]
    assert experts_b == pytest.approx(7.18e9, rel=0.005)
    rest = 2.0 * (12 * (d["attn_params"] + d["router_params"])
                  + d["head_params"])
    assert rest == pytest.approx(1.29e9, rel=0.01)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["seconds"] == pytest.approx(
        12.0e-3, rel=0.05)


def test_a_prefill_counts_bands_whole_full_layers_and_the_last_layers_rows():
    d = COSTS.dims(CONFIG)
    pk = peaks.peaks_for("TPU v5 lite")
    # all 64 experts of eleven layers are read whatever the prompt (8.3
    # GB, 10 ms): a prompt of 512 is bound by that, from 2,048 on by the
    # operations
    assert [_common.costs.least_time(COSTS.prefill(CONFIG, p), pk)["bound"]
            for p in TRAFFIC["prompt"]["values"]] == [
        "memory", "compute", "compute", "compute"]
    assert COSTS.band_pairs(1000, 4096) == 1000 * 1000 / 2.0
    assert COSTS.band_pairs(12288, 4096) == 12288 * 4096 - 4096 * 4096 / 2
    # by hand at 12,288: eleven whole layers, of which three score the
    # lower triangle and EIGHT a band (the twelfth layer, a sliding one,
    # gives its rows only)
    p = 12288.0
    whole = 11 * (d["attn_params"] + d["router_params"]
                  + 6 * d["expert_params"]) + 2 * 2560 * 512
    pairs = 3 * p * p / 2 + 8 * (p * 4096 - 4096 * 4096 / 2)
    want = 2.0 * p * whole + 4.0 * 28 * 128 * pairs
    assert COSTS.prefill(CONFIG, 12288)["flops"] == pytest.approx(want)
    # and at 512, inside the window: every scored layer a triangle
    p = 512.0
    want = 2.0 * p * whole + 4.0 * 28 * 128 * 11 * p * p / 2
    assert COSTS.prefill(CONFIG, 512)["flops"] == pytest.approx(want)
    # a window layer keeps its last 4,096 rows, a full layer all
    rows = lambda p: COSTS.prefill(CONFIG, p)["bytes"]  # noqa: E731
    assert rows(12288) - rows(6144) == 2048.0 * 3 * 6144
    assert rows(2048) - rows(512) == 2048.0 * 12 * 1536


def test_the_counts_are_never_over_what_the_reference_touches():
    """A brute-force count of what the plain reference multiplies and
    reads for the same work (every expert over every token under a mask;
    the whole score matrix; every layer whole, the last too; the head):
    the least counts may never pass it, or a share could pass 100%."""
    m = CONFIG["model"]
    h, heads, kv, dd = 2560, 28, 4, 128
    for p in (512, 12288):
        per_layer = 2.0 * p * (2 * h * heads * dd + 2 * h * kv * dd
                               + h * 64 + 64 * 3 * h * 768) \
            + 4.0 * heads * dd * p * p
        brute = m["num_layers"] * per_layer + 2.0 * p * h * m["vocab_size"]
        got = COSTS.prefill(CONFIG, p)
        assert 0 < got["flops"] < brute
        touched = 2.0 * (m["num_layers"] * (
            2 * h * heads * dd + 2 * h * kv * dd + h * 64
            + 64 * 3 * h * 768) + h * m["vocab_size"]) \
            + 2.0 * 2 * kv * dd * p * m["num_layers"]
        assert 0 < got["bytes"] < touched
    for lanes, ctx in ((1, 100), (16, 5000), (16, 13312)):
        # a step of the reference's: every expert read, every row of the
        # whole context in every layer
        brute_bytes = 2.0 * (m["num_layers"] * (
            2 * h * heads * dd + 2 * h * kv * dd + h * 64
            + 64 * 3 * h * 768) + h * m["vocab_size"]) \
            + 2.0 * 2 * kv * dd * lanes * (ctx + 1) * m["num_layers"]
        got = COSTS.decode_step(CONFIG, [ctx] * lanes)
        assert 0 < got["bytes"] <= brute_bytes
        brute_flops = lanes * (2.0 * (m["num_layers"] * (
            2 * h * heads * dd + 2 * h * kv * dd + h * 64
            + 64 * 3 * h * 768) + h * m["vocab_size"])
            + 4.0 * heads * dd * ctx * m["num_layers"])
        assert 0 < got["flops"] <= brute_flops


def test_the_roofline_reads_this_configurations_counts():
    assert _common.costs_of(CONFIG) is not _common.costs
    assert _common.costs_of(CONFIG).__file__.endswith(f"costs/{NAME}.py")
    ctx = {"kind": "serve", "config": CONFIG,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"busy_s": 0.6, "t0": 0.0, "t1": 10.0},
           "steps": [{"admits": [(1.0, 1.4, 12288)], "context": [4200] * 16,
                      "t_decode": 1.4, "t_end": 1.5}]}
    pk = peaks.peaks_for("TPU v5 lite")
    least = sum(_common.costs.least_time(c, pk)["seconds"] for c in (
        COSTS.prefill(CONFIG, 12288), COSTS.decode_step(CONFIG,
                                                        [4200] * 16)))
    assert reader("st_step_roofline").read(ctx) == pytest.approx(
        100.0 * least / 0.6)
    assert reader("st_step_roofline").read(dict(ctx, trace=None)) is None


# ------------------------------------------------------------ the harness


def test_a_program_without_the_fields_stops_with_the_config_message(
        monkeypatch):
    """What the parent commit does with the new files laid over it: its
    ``GptConfig`` lacks the fields, and ``worker.gpt_config`` says so (at
    once: before any weight is made or any program compiled)."""
    import dataclasses
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    parent = dataclasses.make_dataclass("ParentConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(gpt_lib.GptConfig)
        if f.name not in ("router_input", "router_score",
                          "expert_activation")], frozen=True)
    monkeypatch.setattr(gpt_lib, "GptConfig", parent)
    with pytest.raises(SystemExit) as err:
        worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    message = str(err.value)
    assert CONFIG_FILE in message
    assert "['expert_activation', 'router_input', 'router_score']" in message
    assert "which the program's GptConfig does not have" in message


def canned_trace(tmp_path, monkeypatch, steps):
    """A profile taken here with the program's retire region and ``steps``'
    stats on it, where the readers look for the cell's trace."""
    from distributed_tensorflow_tpu.utils import profiling
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), "trace", CELL)
    os.makedirs(trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for stats in steps:
        with profiling.annotate("serve.step.retire", **stats):
            jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()


def test_the_counters_metrics_read_the_programs_retire_region(
        tmp_path, monkeypatch):
    ctx = {"cell": CELL, "trace": {"busy_s": 1.0}, "traffic": TRAFFIC}
    names = ("st_window_pages_peak_pct", "st_window_table_held_pct",
             "st_window_lanes_wrapped_pct", "st_experts_touched_pct")
    for name in names:
        assert reader(name).read(dict(ctx, trace=None)) is None   # untraced
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    for name in names:
        assert reader(name).read(ctx) is None                # no trace file
    base = dict(pools_in_place=1, table_pages=13312, window_table_pages=4112,
                expert_slots=768)
    canned_trace(tmp_path, monkeypatch, [
        dict(base, lanes_live=16, window_lanes_wrapped=8,
             window_table_pages_held=2400, window_pages_peak=2500,
             experts_touched=608, expert_tokens_max=6, routed_tokens=1152),
        dict(base, lanes_live=12, window_lanes_wrapped=3,
             window_table_pages_held=2000, window_pages_peak=2500,
             experts_touched=560, expert_tokens_max=9, routed_tokens=864),
        # a step with no seated lane divides nothing
        dict(base, lanes_live=0, window_lanes_wrapped=0,
             window_table_pages_held=0, window_pages_peak=2500,
             experts_touched=0, expert_tokens_max=0, routed_tokens=0)])
    read = lambda name: reader(name).read(ctx)  # noqa: E731
    assert read("st_window_lanes_wrapped_pct") == pytest.approx(
        100.0 * (8 / 16 + 3 / 12) / 2)
    assert read("st_window_table_held_pct") == pytest.approx(
        100.0 * (2400 + 2000 + 0) / 3 / 4112)
    assert read("st_window_pages_peak_pct") == pytest.approx(
        100.0 * 2500 / 4112)
    assert read("st_experts_touched_pct") == pytest.approx(
        100.0 * (608 + 560 + 0) / 3 / 768)
    # a program that places no such stat (the parent of the PR that added
    # ``window_lanes_wrapped``): nothing to read, and nothing raised
    other = dict(ctx, cell="other")
    assert reader("st_window_lanes_wrapped_pct").read(other) is None


def test_the_clock_and_region_metrics_read_a_fixture(monkeypatch):
    """The readers that take the worker's own records, and those that take
    the regions' reduction (``perfbench/regions.py``, stubbed here with a
    reduction as it returns one)."""
    from perfbench import regions
    ctx = {
        "cell": CELL, "kind": "serve", "config": CONFIG, "traffic": TRAFFIC,
        "setup": {"compile_s": 31.5},
        "counters": {"kv_pages_peak": 6656, "kv_pages_total": 13312,
                     "compiles_in_window": 0},
        "window": {"t0": 0.0, "t1": 10.0},
        "steps": [{"t_start": 1.0, "t_end": 2.0, "t_decode": 1.6,
                   "prefill_s": 0.6, "admits": [], "context": [5] * 16,
                   "tokens": 16}],
        "trace": {"busy_s": 3.0, "window_s": 4.0, "t0": 0.0, "t1": 4.0},
        "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 15 * 2 ** 30}}
    assert reader("st_compile_s").read(ctx) == 31.5
    assert reader("st_compiles_in_window").read(ctx) == 0.0
    assert reader("st_hbm_peak_gib").read(ctx) == pytest.approx(15.0)
    assert reader("st_kv_pages_peak_pct").read(ctx) == pytest.approx(50.0)
    assert reader("st_prefill_share_pct").read(ctx) == pytest.approx(60.0)
    assert reader("st_device_idle_pct").read(ctx) == pytest.approx(25.0)
    reduction = {"found": True, "programs": {
        "jit_step": {"executions": 100, "seconds": 1.5,
                     "names": ["moe.route", "moe.experts", "attn.scores"],
                     "regions": {"moe.route": 0.06, "moe.experts": 0.84,
                                 "attn.scores": 0.2, "cache.write": 0.04,
                                 "attn.qkv": 0.16, "head": 0.1,
                                 "unnamed": 0.1}},
        "jit_prefill": {"executions": 4, "seconds": 2.0,
                        "names": ["attn.scores"],
                        "regions": {"attn.scores": 0.9, "moe.experts": 1.0,
                                    "unnamed": 0.1}}}}
    monkeypatch.setattr(regions, "of_run", lambda ctx: reduction)
    read = lambda name: reader(name).read(ctx)  # noqa: E731
    assert read("st_decode_route_ms") == pytest.approx(0.6)
    assert read("st_decode_experts_ms") == pytest.approx(9.0)
    # the route is a PART of the experts' time, the experts' of the matmuls'
    assert read("st_decode_experts_ms") - read("st_decode_route_ms") \
        == pytest.approx(1e3 * 0.84 / 100)
    assert read("st_decode_attn_ms") == pytest.approx(2.4)
    assert read("st_decode_unnamed_ms") == pytest.approx(1.0)
    assert read("st_decode_matmul_ms") == pytest.approx(15.0 - 2.4 - 1.0)
    assert read("st_prefill_attn_pct") == pytest.approx(45.0)
    # a program that places no region (the parent; a CPU rehearsal)
    monkeypatch.setattr(regions, "of_run", lambda ctx: None)
    for name in ("st_decode_route_ms", "st_decode_experts_ms",
                 "st_decode_attn_ms", "st_decode_matmul_ms",
                 "st_decode_unnamed_ms", "st_prefill_attn_pct"):
        assert read(name) is None


def test_the_new_entries_by_name():
    """The configuration, the cell and the seventeen metrics, each found
    by its name wherever later entries put it."""
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "mixed_closed32"
    assert cell["config_name"] == NAME
    assert sorted(cell["metrics"]["end_to_end"]) == ["serve_tokens_per_s",
                                                     "setup_s"]
    mine = [m for m in spec.benchmark()["per_layer"]
            if m["name"].startswith("st_")]
    assert sorted(m["name"] for m in mine) == ST_METRICS
    # the most the driver takes; nothing else in the repository says it
    assert len(spec.benchmark()["per_layer"]) <= 128
    assert set(ST_METRICS) <= set(cell["metrics"]["per_layer"])
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(m["moves"] == ("setup_s" if m["name"] == "st_compile_s"
                              else "serve_tokens_per_s") for m in mine)
    for m in mine:
        assert os.path.exists(os.path.join(spec.HERE, "metrics",
                                           m["name"] + ".py")), m["name"]
    by = {m["name"]: m for m in mine}
    assert by["st_step_roofline"]["unit"] == "%"
    assert by["st_step_roofline"]["source"] == "device_trace"
    assert by["st_decode_route_ms"]["layer"] == "kernels"
    assert by["st_window_lanes_wrapped_pct"]["source"] == "program_counter"
    # every layer named is one the benchmark already had
    before = {m["layer"] for m in spec.benchmark()["per_layer"]
              if not m["name"].startswith("st_")}
    assert {m["layer"] for m in mine} <= before
    # the region metrics read the vocabulary the program has
    from perfbench import regions
    assert {"moe.route", "moe.experts"} <= regions.vocabulary()
    for size in ("chip", "rehearsal"):
        limits = spec.load_json(os.path.join(
            spec.HERE, "limits", CELL + ".json"))[size]
        assert set(limits) == {"served_logit_gap_mean",
                               "served_logit_gap_widest"}
        assert all(isinstance(v, float) and v > 0 for v in limits.values())
    assert CELL in entry_of("end_to_end", "serve_tokens_per_s")["workloads"]
    entry = entry_of("workloads", CELL)
    assert len(entry["why"]) <= 200
    assert len(entry_of("configs", NAME)["why"]) <= 200


def rehearse(*extra):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2 ** 31 + 50), "--seconds", "5", *extra,
         "--rehearse"], cwd=spec.ROOT, env=env, text=True,
        capture_output=True, timeout=600)
    assert proc.returncode == spec.REHEARSAL_EXIT, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_traced_rehearsal_is_correct_and_reads_what_a_cpu_can():
    """The cell end to end on the CPU, traced: ``correct`` by the
    rehearsal's limits, no request failed, and every metric that needs no
    device read; those that need one are left out, not zero.  (The
    untraced rehearsal is ``test_perfbench_run.py``'s, with every cell's.
    The lower-precision control the CPU cannot separate at this size: one
    flipped token of some twenty moves the mean by a twentieth of its gap;
    the chip's readings are in the limits file's note.)"""
    line = rehearse("--trace", "1")
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 8
    assert line["rehearsal"] and line["device"]["platform"] == "cpu"
    read = set(line["metrics"])
    assert {"st_compile_s", "st_prefill_share_pct", "st_kv_pages_peak_pct",
            "st_window_pages_peak_pct", "st_window_table_held_pct",
            "st_window_lanes_wrapped_pct", "st_experts_touched_pct",
            "st_compiles_in_window"} <= read
    assert not read & {"st_step_roofline", "st_device_idle_pct",
                       "st_decode_route_ms", "st_decode_experts_ms"}
    assert set(line["check"]) == {"served_logit_gap_mean",
                                  "served_logit_gap_widest", "failed"}
