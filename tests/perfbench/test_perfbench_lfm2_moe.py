"""What ``lfm2-24b-a2b`` brings to the benchmark as new files: its
configuration against the published one (the catalog row's every number but
what ``reduced`` names), its layout against the program's own tree, its
counts at the published widths (a convolution layer reads two rows a lane
and writes one, an attention layer each lane's held rows once, a decode
step the experts EXPECTED touched), the readers of the counters and regions
the configuration adds to the program (``lfm_lanes_live_pct``,
``lfm_state_peak_mib``, ``lfm_decode_conv_ms``, ``lfm_decode_experts_ms``),
and the message a program without the kind stops with.  The cell's
rehearsal runs with every other cell's in ``test_perfbench_run.py``.
Nothing here pins how many cells the benchmark has, nor another cell's
metrics, nor where an entry stands.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import peaks, spec, traffic, weights, worker
from perfbench.metrics import _common

CELL = "serve_lfm2moe_agents64"
CONFIG_FILE = "perfbench/configs/lfm2-24b-a2b.json"
CONFIG = spec.load_json(os.path.join(spec.ROOT, CONFIG_FILE))
COSTS = spec.named_module(CONFIG, "costs")
TRAFFIC = spec.load_json(os.path.join(spec.HERE, "traffic",
                                      "agents_closed128.json"))
CONV, FULL = "short_conv", "full_attention"
KINDS = [CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV]
REDUCED = ["layer_types", "max_position_embeddings", "norm_eps",
           "num_dense_layers", "num_hidden_layers"]


def reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics", name + ".py"))


def test_the_file_holds_the_published_config_but_what_reduced_names():
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: CONFIG[k] for k in published} == published
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "lfm2-24b-a2b")
    assert entry["file"] == CONFIG_FILE and entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == REDUCED
    types = [{CONV: "conv"}.get(k, k) for k in KINDS]
    assert {k: CONFIG[k] for k in REDUCED} == {
        "num_hidden_layers": 9, "layer_types": types, "num_dense_layers": 1,
        "max_position_embeddings": 3072, "norm_eps": 1e-06}
    assert {k: v for k, v in CONFIG["published"].items()
            if k != "layer_types"} == {
        "num_hidden_layers": 40, "num_dense_layers": 2,
        "max_position_embeddings": 128000, "norm_eps": 1e-05}
    assert "(conv, conv, full_attention, conv) x 10" \
        in CONFIG["published"]["layer_types"]
    # no width among the cut keys, and the program's config is the
    # published one, key for key
    m = CONFIG["model"]
    assert (m["hidden_size"], m["num_heads"], m["kv_heads"],
            m["intermediate_size"], m["vocab_size"], m["num_layers"]) == (
        2048, 32, 8, 11776, 65536, 9)
    assert "head_size" not in m and 2048 // 32 == 64
    assert (m["num_experts"], m["experts_per_token"],
            m["expert_intermediate_size"], m["num_shared_experts"],
            m["routed_scaling_factor"], m["first_dense_layers"]) == (
        64, 4, 1536, 0, 1.0, 1)
    assert m["layer_kinds"] == KINDS and m["short_conv_kernel_dim"] == 3
    assert (m["pos_encoding"], m["rope_base"], m["norm_placement"]) == (
        "rope", 1e6, "pre")
    assert m["qk_head_norm"] and m["norm_eps"] == CONFIG["norm_eps"]
    assert m["max_position"] == CONFIG["max_position_embeddings"]
    assert "chips that share a layer: 1" in CONFIG["deployment"]
    assert "layers 1 and 2-9" in CONFIG["deployment"]
    assert "untied lm_head" in CONFIG["deployment"]
    assert CONFIG["lower_precision"] == {"quantize": "int8",
                                         "kv_dtype": "float8"}
    said = " ".join(CONFIG["assumed"])
    for what in ("[B | C | X]", "C multiplies AFTER", "each HEAD's 64",
                 "split-half", "two RMSNorms", "ZERO", "THE HEAD",
                 "sqrt(3)", "1e-6", "modeling_lfm2_moe.py"):
        assert what in said, what


def test_the_catalog_row_is_the_file_where_the_catalog_is_at_hand():
    """Every number of the catalog entry's ``config`` under the same key,
    but the keys ``reduced`` names."""
    import json
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside this checkout")
    with open(path) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "LFM2-24B-A2B")
    assert row["source_url"] == CONFIG["source"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert CONFIG[key] == value, key
    assert CONFIG["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]
    # the cut is one leading dense layer and two whole published periods
    assert CONFIG["layer_types"] == row["config"]["layer_types"][1:10]
    assert CONFIG["layer_types"][1:5] == CONFIG["layer_types"][5:9]


def test_the_traffic_fits_the_engine_and_the_pool_holds_every_lane():
    eng = TRAFFIC["engine"]
    cap = eng["page_size"] * eng["max_pages_per_seq"]
    worst = max(TRAFFIC["prompt"]["values"]) + max(
        TRAFFIC["output"]["values"])
    assert worst == cap == TRAFFIC["check_pad"] == 3072
    assert worst <= CONFIG["model"]["max_position"]
    assert TRAFFIC["prompt"]["values"] == [256, 512, 1024, 2048]
    assert TRAFFIC["output"]["values"] == [128, 256, 512, 1024]
    assert (eng["page_size"], eng["max_pages_per_seq"], eng["num_pages"],
            eng["prefill_cache_cap"], TRAFFIC["check_sample"]) == (
        16, 192, 12288, 8, 8)
    # ISSUE 46's one fallback: 64 callers over 32 slots, nothing else
    # changing (a tenant's queue holds 64 waiting requests, and 128 callers
    # over 64 slots put a 65th there whenever two lanes leave in one step)
    assert (TRAFFIC["callers"], eng["num_slots"]) == (64, 32)
    assert "queue is at its bound (64)" in TRAFFIC["note"]
    assert eng["num_pages"] >= eng["num_slots"] * eng["max_pages_per_seq"]
    # one prefill program a prompt length, all resident
    assert len(traffic.serve_buckets(TRAFFIC, eng["page_size"])) == 4 \
        <= eng["prefill_cache_cap"]
    # bytes: a cached token 4,096 B over the two attention layers, a lane's
    # seven tails 57,344 B, the pool 0.81 GB
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    gcfg = worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    assert gcfg.head_dim == 64 and gcfg.conv_layers == 7
    assert gpt_lib.kv_row_bytes_per_token(gcfg) == 4096
    assert gpt_lib.state_bytes_per_slot(gcfg) == 57344
    assert eng["num_pages"] * 16 * 4096 == pytest.approx(0.81e9, rel=0.01)
    # every bucket keeps the flash kernel's layout
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fl
    assert all(fl._layout_ok(p) for p in TRAFFIC["prompt"]["values"])
    # the rehearsal seats a prompt shorter than its bucket
    small = spec.cell(CELL, rehearse=True)["traffic"]
    assert any(p % small["engine"]["page_size"]
               for p in small["prompt"]["values"])


def test_the_layout_is_the_programs_tree_at_rehearsal_size():
    cfg = spec.deep_update(CONFIG, CONFIG["rehearsal"])
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = gpt_lib.GptLM(worker.gpt_config(
        {"config": cfg, "config_file": CONFIG_FILE}))
    maker = weights.Maker(cfg)
    # the leading dense layer and ONE period at rehearsal size
    assert cfg["model"]["layer_kinds"] == KINDS[:5]
    assert maker.kinds == ["dense." + CONV] + [
        "sparse." + k for k in KINDS[1:5]]
    params = weights.program_tree(7, maker)
    assert worker.check_tree(jax, model, params, cfg) > 0
    conv, attn = params["layer2"], params["layer1"]
    assert conv["in_proj"]["kernel"].shape == (64, 192)
    assert set(conv["in_proj"]) == set(conv["out"]) == {"kernel"}
    assert conv["conv_taps"].shape == (3, 64)
    assert conv["out"]["kernel"].shape == (64, 64)
    assert conv["experts_gate"].shape == (16, 64, 16)
    assert attn["q_proj"]["kernel"].shape == (64, 4, 16)
    assert attn["kv_proj"]["kernel"].shape == (64, 2, 2, 16)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert float(jnp.min(attn["k_norm"]["scale"])) == 1.0
    assert float(jnp.max(jnp.abs(attn["router_bias"]))) == 0.0
    assert "shared_in" not in attn and "shared_in" not in conv
    assert set(params["layer0"]) == {"ln_attn", "ln_mlp", "in_proj",
                                     "conv_taps", "out", "mlp_in",
                                     "mlp_gate", "mlp_out"}
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))  # noqa: E731
    assert std(conv["conv_taps"]) == pytest.approx(3 ** -0.5, rel=0.15)
    assert std(conv["experts_gate"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(params["word_emb"]["embedding"]) == pytest.approx(1.0,
                                                                 rel=0.05)
    # two convolution layers have the same leaves, other values
    assert jax.tree.map(jnp.shape, params["layer3"]) == jax.tree.map(
        jnp.shape, params["layer4"])
    assert not jnp.array_equal(params["layer3"]["conv_taps"],
                               params["layer4"]["conv_taps"])


def test_counts_at_published_widths():
    d = COSTS.dims(CONFIG)
    assert (d["n_dense"], d["n_sparse"], d["n_conv"], d["n_attn"]) == (
        1, 8, 7, 2)
    assert d["conv_params"] == 16_783_360      # in 12.58 M, out 4.19 M, taps
    assert d["attn_params"] == 10_485_760      # q, out 4.19 M; k, v 1.05 M
    assert d["expert_params"] == 9_437_184
    sparse_conv = d["conv_params"] + 64 * d["expert_params"] \
        + d["router_params"]
    sparse_attn = d["attn_params"] + 64 * d["expert_params"] \
        + d["router_params"]
    assert sparse_conv == pytest.approx(620.9e6, rel=1e-4)
    assert sparse_attn == pytest.approx(614.6e6, rel=1e-4)
    total = (d["conv_params"] + d["dense_mlp_params"] + 6 * sparse_conv
             + 2 * sparse_attn + 2 * d["head_params"])
    assert total == pytest.approx(5312.1e6, rel=1e-5)    # 10.62 GB
    assert 2 * total > 10.6e9 > 0.25 * 16e9
    assert COSTS.active_params(d) == pytest.approx(513.8e6, rel=1e-3)


@pytest.mark.parametrize("lanes,touched", [(8, 25.8), (32, 55.9),
                                           (64, 63.0)])
def test_a_decode_step_counts_held_rows_tails_and_the_experts_expected(
        lanes, touched):
    d = COSTS.dims(CONFIG)
    assert COSTS.experts_touched(64, 4, lanes) == pytest.approx(touched,
                                                                abs=0.06)
    step = COSTS.decode_step(CONFIG, [1500] * lanes)
    outside = 2.0 * (7 * d["conv_params"] + 2 * d["attn_params"]
                     + d["dense_mlp_params"] + 8 * d["router_params"]
                     + d["head_params"])
    experts_b = 2.0 * 8 * COSTS.experts_touched(64, 4, lanes) \
        * d["expert_params"]
    # two layers read each lane's held rows and write one, 1,024 entries of
    # 2 B; seven read two rows of 2,048 a lane and write one
    rows_b = 2.0 * 1024 * (2 * 1500 * lanes + 2 * lanes)
    tails_b = 2.0 * 2048 * 3 * lanes * 7
    assert step["bytes"] == pytest.approx(outside + experts_b + rows_b
                                          + tails_b)
    assert experts_b < 2.0 * 8 * min(64, 4 * lanes) * d["expert_params"]
    assert step["flops"] == pytest.approx(
        2.0 * lanes * (COSTS.active_params(d) + d["head_params"])
        + 2.0 * 2.0 * 32 * 64 * 2 * 1500 * lanes)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["bound"] == "memory"


def test_costs_grow_with_lanes_context_and_prompt():
    one = lambda lanes, ctx: COSTS.decode_step(  # noqa: E731
        CONFIG, [ctx] * lanes)
    for key in ("flops", "bytes"):
        assert one(8, 1000)[key] < one(16, 1000)[key] < one(32, 1000)[key]
        assert one(32, 500)[key] < one(32, 3000)[key]
        assert COSTS.prefill(CONFIG, 256)[key] < COSTS.prefill(
            CONFIG, 2048)[key]
    # 32 full lanes at the mix's mean context: 9.1 GB of weights (55.9
    # experts a layer) and 0.19 GB of rows, 11.4 ms at the chip's bandwidth;
    # 64 lanes 12.9 ms (ISSUE 46's 13.0)
    pk = peaks.peaks_for("TPU v5 lite")
    least = lambda c: _common.costs.least_time(c, pk)  # noqa: E731
    assert one(32, 1440)["bytes"] == pytest.approx(9.32e9, rel=0.01)
    assert least(one(32, 1440))["seconds"] == pytest.approx(11.4e-3,
                                                            rel=0.01)
    assert least(one(64, 1440))["seconds"] == pytest.approx(12.9e-3,
                                                            rel=0.01)
    # context costs little: 4,096 B a token against 9 GB of experts
    assert one(32, 3072)["bytes"] - one(32, 1)["bytes"] == pytest.approx(
        4096.0 * 32 * 3071)
    # a prefill reads every expert of the seven sparse layers it computes
    # whole (the last layer's feed the logits alone), so it is bound by
    # the same bytes at every prompt length of the cell: 10.8 ms
    for p in TRAFFIC["prompt"]["values"]:
        cost = COSTS.prefill(CONFIG, p)
        assert least(cost)["bound"] == "memory"
        assert least(cost)["seconds"] == pytest.approx(10.83e-3, rel=0.01)
    d = COSTS.dims(CONFIG)
    a, b = COSTS.prefill(CONFIG, 1024), COSTS.prefill(CONFIG, 2048)
    # the scores are the lower triangle in two layers: they grow with p^2
    tri = lambda p: 2.0 * 2.0 * 32 * 64 * 2 * p * p / 2.0  # noqa: E731
    assert b["flops"] - 2 * a["flops"] == pytest.approx(
        tri(2048) - 2 * tri(1024) - 2.0 * 2 * 2.0 * d["H"] * d["H"])
    assert b["bytes"] - a["bytes"] == 4096.0 * 1024


def test_the_roofline_reads_this_configurations_counts():
    assert _common.costs_of(CONFIG) is not _common.costs
    assert _common.costs_of(CONFIG).__file__.endswith(
        "costs/lfm2-24b-a2b.py")
    ctx = {"kind": "serve", "config": CONFIG,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"busy_s": 0.2, "t0": 0.0, "t1": 10.0},
           "steps": [{"admits": [(1.0, 1.2, 2048)], "context": [900] * 30,
                      "t_decode": 1.2, "t_end": 1.3}]}
    pk = peaks.peaks_for("TPU v5 lite")
    least = sum(_common.costs.least_time(c, pk)["seconds"] for c in (
        COSTS.prefill(CONFIG, 2048), COSTS.decode_step(CONFIG, [900] * 30)))
    assert _common.step_roofline_pct(ctx) == pytest.approx(
        100.0 * least / 0.2)


def test_a_program_without_the_kind_stops_with_the_config_message(
        monkeypatch):
    """What the parent commit does with the new files laid over it: its
    ``GptConfig`` lacks the field, and ``worker.gpt_config`` says so (at
    once: before any weight is made or any program compiled)."""
    import dataclasses
    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    @dataclasses.dataclass(frozen=True)
    class ParentConfig:
        vocab_size: int = 256
        hidden_size: int = 128
        layer_kinds: tuple = ()

    monkeypatch.setattr(gpt_lib, "GptConfig", ParentConfig)
    with pytest.raises(SystemExit) as err:
        worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    message = str(err.value)
    assert CONFIG_FILE in message and "short_conv_kernel_dim" in message
    assert "which the program's GptConfig does not have" in message


def test_the_new_counters_are_read_from_the_programs_retire_region(
        tmp_path, monkeypatch):
    """A canned traced run: the program's region with its stats, as
    ``serving/engine.py`` places them, in a profile taken here."""
    from distributed_tensorflow_tpu.utils import profiling
    live, state = reader("lfm_lanes_live_pct"), reader("lfm_state_peak_mib")
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), "trace", CELL)
    os.makedirs(trace_dir)
    ctx = {"cell": CELL, "trace": {"busy_s": 1.0}, "traffic": TRAFFIC}
    for r in (live, state):
        assert r.read(dict(ctx, trace=None)) is None     # untraced
        assert r.read(ctx) is None                       # no trace file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for lanes, seated in ((32, 32), (28, 30), (30, 31)):
        with profiling.annotate("serve.step.retire", pools_in_place=1,
                                table_pages=6144, table_pages_held=2000,
                                lanes_live=lanes, state_slots=seated,
                                state_bytes=seated * 57344):
            jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    assert live.read(ctx) == pytest.approx(100.0 * (32 + 28 + 30) / 3 / 32)
    assert state.read(ctx) == pytest.approx(32 * 57344 / 2 ** 20)   # 1.75
    # a program that places no such stats (the parent): nothing to read
    other = os.path.join(str(tmp_path), "trace", "other")
    os.makedirs(other)
    jax.profiler.start_trace(other, profiler_options=options)
    with profiling.annotate("serve.step.retire", pools_in_place=1,
                            table_pages=6144, table_pages_held=2000):
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    for r in (live, state):
        assert r.read(dict(ctx, cell="other")) is None


def test_the_region_metrics_read_parts_of_the_matmul_number(monkeypatch):
    """``lfm_decode_experts_ms`` and ``lfm_decode_conv_ms`` are PARTS of
    ``lfm_decode_matmul_ms`` (their regions are not attention's), and the
    three that add up still do; a program without the convolution's region
    gives ``lfm_decode_conv_ms`` nothing to read."""
    from perfbench import regions
    by = {"moe.experts": 0.30, "moe.route": 0.02, "short_conv.step": 0.01,
          "attn.qkv": 0.05, "attn.scores": 0.04, "cache.write": 0.01,
          "head": 0.03, regions.UNNAMED: 0.02}
    monkeypatch.setattr(regions, "_of", lambda ctx, programs: (
        (40.0, sum(by.values()), by) if programs == regions.DECODE
        else None))
    read = lambda name: reader(name).read({})  # noqa: E731
    assert read("lfm_decode_experts_ms") == pytest.approx(8.0)
    assert read("lfm_decode_conv_ms") == pytest.approx(0.25)
    assert read("lfm_decode_attn_ms") == pytest.approx(1.25)
    assert read("lfm_decode_unnamed_ms") == pytest.approx(0.5)
    assert read("lfm_decode_matmul_ms") == pytest.approx(
        1e3 * (0.30 + 0.02 + 0.01 + 0.05 + 0.03) / 40)
    assert read("lfm_decode_attn_ms") + read("lfm_decode_matmul_ms") \
        + read("lfm_decode_unnamed_ms") == pytest.approx(
            1e3 * sum(by.values()) / 40)
    assert {"short_conv.mix", "short_conv.step"} <= regions.vocabulary()
    assert not {"short_conv.step", "moe.experts"} & set(regions.ATTENTION)
    del by["short_conv.step"]
    assert read("lfm_decode_conv_ms") is None


def test_the_cells_metrics_and_limits():
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "agents_closed128"
    assert cell["config_name"] == "lfm2-24b-a2b"
    assert sorted(cell["metrics"]["end_to_end"]) == ["serve_tokens_per_s",
                                                     "setup_s"]
    mine = [m for m in spec.benchmark()["per_layer"]
            if m["name"].startswith("lfm_")]
    assert sorted(cell["metrics"]["per_layer"]) == sorted(
        m["name"] for m in mine) == [
        "lfm_compile_s", "lfm_compiles_in_window", "lfm_decode_attn_ms",
        "lfm_decode_conv_ms", "lfm_decode_experts_ms",
        "lfm_decode_matmul_ms", "lfm_decode_unnamed_ms",
        "lfm_device_idle_pct", "lfm_expert_load_peak",
        "lfm_experts_touched_pct", "lfm_hbm_peak_gib",
        "lfm_kv_pages_peak_pct", "lfm_lanes_live_pct",
        "lfm_prefill_share_pct", "lfm_state_peak_mib", "lfm_step_roofline"]
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(m["moves"] == ("setup_s" if m["name"] == "lfm_compile_s"
                              else "serve_tokens_per_s") for m in mine)
    assert all(os.path.exists(os.path.join(
        spec.HERE, "metrics", m["name"] + ".py")) for m in mine)
    layers = {m["name"]: m["layer"] for m in mine}
    assert layers["lfm_lanes_live_pct"] == "engine step"
    assert layers["lfm_state_peak_mib"] == "router, scheduler and pool"
    for size in ("chip", "rehearsal"):
        limits = spec.load_json(os.path.join(
            spec.HERE, "limits", CELL + ".json"))[size]
        assert set(limits) == {"served_logit_gap_mean",
                               "served_logit_gap_widest"}
    bench = spec.benchmark()
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "64 callers over 32 slots" \
        in entry["why"]
