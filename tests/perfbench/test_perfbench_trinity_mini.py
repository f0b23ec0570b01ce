"""What ``trinity-mini`` brings to the benchmark as new files: its
configuration against the published one (the catalog row's every number but
what ``reduced`` names), its layout against the program's own tree, its
counts at the published widths (a sliding layer reads the window and scores
the band, a full layer the whole context), the readers of the counters the
configuration adds to the program (``tri_window_pages_peak_pct``,
``tri_window_table_held_pct``), and the message a program without the fields
stops with.  The cell's rehearsal runs with every other cell's in
``test_perfbench_run.py``.  Nothing here pins how many cells the benchmark
has, nor another cell's metrics.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import peaks, spec, traffic, weights, worker
from perfbench.metrics import _common

CELL = "serve_trinitymini_longdoc"
CONFIG_FILE = "perfbench/configs/trinity-mini.json"
CONFIG = spec.load_json(os.path.join(spec.ROOT, CONFIG_FILE))
COSTS = spec.named_module(CONFIG, "costs")
TRAFFIC = spec.load_json(os.path.join(spec.HERE, "traffic",
                                      "longdoc_closed32.json"))
SLIDING, FULL = "sliding_attention", "full_attention"


def reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics", name + ".py"))


def test_the_file_holds_the_published_config_but_what_reduced_names():
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "model_type": "afmoe", "moe_intermediate_size": 1024,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
        "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: CONFIG[k] for k in published} == published
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "trinity-mini")
    assert entry["file"] == CONFIG_FILE and entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == [
        "layer_types", "max_position_embeddings", "num_dense_layers",
        "num_hidden_layers", "rms_norm_eps"]
    assert {k: CONFIG[k] for k in entry["reduced"]} == {
        "num_hidden_layers": 5, "layer_types": [SLIDING] * 4 + [FULL],
        "num_dense_layers": 1, "max_position_embeddings": 33280,
        "rms_norm_eps": 1e-06}
    assert CONFIG["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "layer_types": "(sliding_attention, sliding_attention, "
                       "sliding_attention, full_attention) x 8"}
    # no width among the cut keys, and the program's config is the
    # published one, key for key
    m = CONFIG["model"]
    assert (m["hidden_size"], m["num_heads"], m["kv_heads"], m["head_size"],
            m["intermediate_size"], m["vocab_size"], m["num_layers"]) == (
        2048, 32, 4, 128, 6144, 200192, 5)
    assert (m["num_experts"], m["experts_per_token"],
            m["expert_intermediate_size"], m["num_shared_experts"],
            m["routed_scaling_factor"], m["first_dense_layers"]) == (
        128, 8, 1024, 1, 2.826, 1)
    assert m["layer_kinds"] == CONFIG["layer_types"]
    assert (m["sliding_window"], m["rope_base"], m["rope_kinds"]) == (
        2048, 1e4, [SLIDING])
    assert m["qk_head_norm"] and m["attn_output_gate"] \
        and m["scale_embedding"] and m["norm_placement"] == "sandwich"
    assert m["norm_eps"] == CONFIG["rms_norm_eps"]
    assert m["max_position"] == CONFIG["max_position_embeddings"]
    assert CONFIG["init"]["embedding_std"] == pytest.approx(2048 ** -0.5)
    assert "chips that share a layer: 1" in CONFIG["deployment"]
    assert "1, 4, 5, 6, 7" in CONFIG["deployment"]
    assert CONFIG["lower_precision"] == {"quantize": "int8",
                                         "kv_dtype": "float8"}
    said = " ".join(CONFIG["assumed"])
    for what in ("gate", "HEAD", "SLIDING layers only", "four RMSNorms",
                 "sqrt(hidden_size)", "ZERO", "modeling_afmoe.py"):
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
                   if r["name"] == "Trinity-Mini")
    assert row["source_url"] == CONFIG["source"]
    reduced = {"num_hidden_layers", "layer_types", "num_dense_layers",
               "max_position_embeddings", "rms_norm_eps"}
    for key, value in row["config"].items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    assert CONFIG["published"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]
    # the cut is one leading dense layer and one whole published period
    assert CONFIG["layer_types"][1:] == row["config"]["layer_types"][4:8]


def test_the_traffic_fits_the_engine_and_both_pools_hold_every_lane():
    eng = TRAFFIC["engine"]
    cap = eng["page_size"] * eng["max_pages_per_seq"]
    worst = max(TRAFFIC["prompt"]["values"]) + max(
        TRAFFIC["output"]["values"])
    assert worst == cap == TRAFFIC["check_pad"] == 33280
    assert worst <= CONFIG["model"]["max_position"]
    assert eng["num_pages"] == eng["num_slots"] * eng["max_pages_per_seq"]
    assert TRAFFIC["callers"] == 2 * eng["num_slots"] == 32
    assert TRAFFIC["prompt"]["values"] == [4096, 8192, 16384, 32768]
    assert TRAFFIC["output"]["values"] == [128, 256, 384, 512]
    # one prefill program a prompt length, all resident
    assert len(traffic.serve_buckets(TRAFFIC, eng["page_size"])) == 4 \
        <= eng["prefill_cache_cap"]
    # every lane is 2 to 16 windows deep, and a ring is the window and a page
    window = CONFIG["model"]["sliding_window"]
    assert min(TRAFFIC["prompt"]["values"]) == 2 * window
    gcfg = worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    assert gcfg.ring_pages(eng["page_size"]) == 129
    assert gcfg.window_layers == 4
    # bytes: a token 2,048 B a layer; the full pool 1.09 GB, the rings 0.27
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    assert gpt_lib.kv_row_bytes_per_token(gcfg) == 2048
    assert gpt_lib.kv_row_bytes_per_token(gcfg, window=True) == 4 * 2048
    assert eng["num_pages"] * 16 * 2048 == pytest.approx(1.09e9, rel=0.01)
    assert 16 * 129 * 16 * 4 * 2048 == pytest.approx(0.27e9, rel=0.01)
    # every bucket is a multiple of 1,024: the flash kernel's layout holds
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fl
    assert all(fl._layout_ok(p) for p in TRAFFIC["prompt"]["values"])
    # the rehearsal's lanes are past ITS window too
    small = spec.cell(CELL, rehearse=True)
    assert min(small["traffic"]["prompt"]["values"]) \
        >= 2 * small["config"]["model"]["sliding_window"]


def test_the_layout_is_the_programs_tree_at_rehearsal_size():
    cfg = spec.deep_update(CONFIG, CONFIG["rehearsal"])
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = gpt_lib.GptLM(worker.gpt_config(
        {"config": cfg, "config_file": CONFIG_FILE}))
    maker = weights.Maker(cfg)
    assert maker.kinds == ["dense." + SLIDING] + ["sparse." + SLIDING] * 3 \
        + ["sparse." + FULL]
    params = weights.program_tree(7, maker)
    assert worker.check_tree(jax, model, params, cfg) > 0
    sparse = params["layer1"]
    assert sparse["experts_gate"].shape == (128, 64, 16)
    assert sparse["q_proj"]["kernel"].shape == (64, 4, 32)      # not 64 / 4
    assert sparse["gate_proj"]["kernel"].shape == (64, 4, 32)
    assert set(sparse["gate_proj"]) == {"kernel"}
    assert sparse["kv_proj"]["kernel"].shape == (64, 2, 2, 32)
    assert sparse["q_norm"]["scale"].shape == (32,)
    assert float(jnp.min(sparse["k_norm"]["scale"])) == 1.0
    assert float(jnp.max(jnp.abs(sparse["router_bias"]))) == 0.0
    assert set(params["layer0"]) >= {"mlp_in", "mlp_gate", "mlp_out",
                                     "ln_attn_post", "ln_mlp_post"}
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))  # noqa: E731
    assert std(sparse["experts_gate"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(sparse["experts_down"]) == pytest.approx(16 ** -0.5, rel=0.05)
    assert std(params["word_emb"]["embedding"]) == pytest.approx(
        2048 ** -0.5, rel=0.05)
    # a sliding and a full sparse layer have the same leaves, other values
    assert jax.tree.map(jnp.shape, params["layer3"]) == jax.tree.map(
        jnp.shape, params["layer4"])
    assert not jnp.array_equal(params["layer3"]["experts_up"],
                               params["layer4"]["experts_up"])


def test_counts_at_published_widths():
    d = COSTS.dims(CONFIG)
    assert (d["n_dense"], d["n_sparse"], d["window"]) == (1, 4, 2048)
    assert d["attn_params"] == 27_262_976      # q, gate, out 8.39 M; k, v 1.05
    assert d["expert_params"] == 6_291_456
    sparse_layer = (d["attn_params"] + 129 * d["expert_params"]
                    + d["router_params"])
    assert sparse_layer == pytest.approx(839.1e6, rel=1e-4)
    total = (5 * d["attn_params"] + d["dense_mlp_params"]
             + 4 * (129 * d["expert_params"] + d["router_params"])
             + 2 * d["head_params"])
    assert total == pytest.approx(4241.5e6, rel=1e-5)    # 8.48 GB
    assert 2 * total > 0.25 * 16e9 and 2 * total + 1.36e9 > 9e9


@pytest.mark.parametrize("lanes,touched", [(4, 29.1), (8, 51.6),
                                           (16, 82.4)])
def test_a_decode_step_counts_the_window_and_the_experts_expected(lanes,
                                                                  touched):
    d = COSTS.dims(CONFIG)
    assert COSTS.experts_touched(128, 8, lanes) == pytest.approx(touched,
                                                                 abs=0.06)
    ctx = [20000] * lanes
    step = COSTS.decode_step(CONFIG, ctx)
    outside = 2.0 * (5 * d["attn_params"] + d["dense_mlp_params"]
                     + 4 * (d["shared_params"] + d["router_params"])
                     + d["head_params"])
    experts_b = 2.0 * 4 * COSTS.experts_touched(128, 8, lanes) \
        * d["expert_params"]
    # four layers read the window, one the whole context; a row 2,048 B
    rows = lanes * (4 * 2048 + 20000)
    rows_b = 2048.0 * (rows + lanes * 5)
    assert step["bytes"] == pytest.approx(outside + experts_b + rows_b)
    assert experts_b < 2.0 * 4 * min(128, 8 * lanes) * d["expert_params"]
    assert step["flops"] == pytest.approx(
        2.0 * lanes * (COSTS.active_params(d) + d["head_params"])
        + 2.0 * 2.0 * 32 * 128 * rows)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["bound"] == "memory"
    # a lane inside the window reads what it has, in every layer
    assert COSTS.rows_attended(d, 500) == 5 * 500
    assert COSTS.rows_attended(d, 2048) == 5 * 2048
    assert COSTS.rows_attended(d, 2049) == 4 * 2048 + 2049


def test_costs_grow_with_lanes_context_and_prompt():
    one = lambda lanes, ctx: COSTS.decode_step(  # noqa: E731
        CONFIG, [ctx] * lanes)
    for key in ("flops", "bytes"):
        assert one(4, 5000)[key] < one(8, 5000)[key] < one(16, 5000)[key]
        assert one(16, 5000)[key] < one(16, 30000)[key]
        assert COSTS.prefill(CONFIG, 4096)[key] < COSTS.prefill(
            CONFIG, 32768)[key]
    # 16 full lanes at the mix's mean context: 5.1 GB of weights (82.4
    # experts a layer) and 0.78 GB of rows, 7.5 ms at the chip's bandwidth
    step = one(16, 15680)
    assert step["bytes"] == pytest.approx(6.15e9, rel=0.01)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["seconds"] == pytest.approx(
        7.5e-3, rel=0.01)
    # held as FULL layers the same five would read 3.3 times the rows
    full = dict(CONFIG, model=dict(CONFIG["model"],
                                   layer_kinds=[FULL] * 5))
    assert COSTS.decode_step(full, [15680] * 16)["bytes"] - step["bytes"] \
        == pytest.approx(2048.0 * 16 * 4 * (15680 - 2048))
    # a prefill is compute-bound at every prompt length of the cell, and of
    # the last layer (the cut's one FULL layer) only its rows count: the
    # scores are bands, so they grow with p and not with its square
    for p in TRAFFIC["prompt"]["values"]:
        assert _common.costs.least_time(COSTS.prefill(CONFIG, p),
                                        pk)["bound"] == "compute"
    assert COSTS.prefill(CONFIG, 32768)["flops"] == pytest.approx(
        25.2e12, rel=0.01)
    a, b = COSTS.prefill(CONFIG, 16384), COSTS.prefill(CONFIG, 32768)
    band = lambda p: 2.0 * 2.0 * 32 * 128 * 4 * (  # noqa: E731
        p * 2048 - 2048 * 2048 / 2.0)
    assert b["flops"] - 2 * a["flops"] == pytest.approx(
        band(32768) - 2 * band(16384))
    assert COSTS.band_pairs(1000, 2048) == 1000 * 1000 / 2.0
    # a window layer keeps its last 2,048 rows, the full layer all
    rows = lambda p: COSTS.prefill(CONFIG, p)["bytes"]  # noqa: E731
    assert rows(32768) - rows(16384) == 2048.0 * 16384


def test_the_roofline_reads_this_configurations_counts():
    assert _common.costs_of(CONFIG) is not _common.costs
    assert _common.costs_of(CONFIG).__file__.endswith(
        "costs/trinity-mini.py")
    # a step of lanes at the traffic's contexts, through the shared reducer
    ctx = {"kind": "serve", "config": CONFIG,
           "device": {"kind": "TPU v5 lite"},
           "trace": {"busy_s": 0.2, "t0": 0.0, "t1": 10.0},
           "steps": [{"admits": [(1.0, 1.2, 32768)], "context": [4200] * 16,
                      "t_decode": 1.2, "t_end": 1.3}]}
    pk = peaks.peaks_for("TPU v5 lite")
    least = sum(_common.costs.least_time(c, pk)["seconds"] for c in (
        COSTS.prefill(CONFIG, 32768), COSTS.decode_step(CONFIG,
                                                        [4200] * 16)))
    assert _common.step_roofline_pct(ctx) == pytest.approx(
        100.0 * least / 0.2)


def test_a_program_without_the_fields_stops_with_the_config_message(
        monkeypatch):
    """What the parent commit does with the new files laid over it: its
    ``GptConfig`` lacks the fields, and ``worker.gpt_config`` says so (at
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
    assert CONFIG_FILE in message and "sliding_window" in message
    assert "head_size" in message and "attn_output_gate" in message
    assert "which the program's GptConfig does not have" in message


def test_window_counters_are_read_from_the_programs_retire_region(
        tmp_path, monkeypatch):
    """A canned traced run: the program's region with its stats, as
    ``serving/engine.py`` places them, in a profile taken here."""
    from distributed_tensorflow_tpu.utils import profiling
    peak, held = (reader("tri_window_pages_peak_pct"),
                  reader("tri_window_table_held_pct"))
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), "trace", CELL)
    os.makedirs(trace_dir)
    ctx = {"cell": CELL, "trace": {"busy_s": 1.0}}
    for r in (peak, held):
        assert r.read(dict(ctx, trace=None)) is None     # untraced
        assert r.read(ctx) is None                       # no trace file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for rings, in_use, high in ((2064, 2064, 2064), (1935, 1935, 2064),
                                (1806, 2064, 2064)):
        with profiling.annotate("serve.step.retire", pools_in_place=1,
                                table_pages=33280, table_pages_held=15000,
                                window_table_pages=2064,
                                window_table_pages_held=rings,
                                window_pages_in_use=in_use,
                                window_pages_peak=high):
            jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    assert peak.read(ctx) == pytest.approx(100.0)
    assert held.read(ctx) == pytest.approx(
        100.0 * (2064 + 1935 + 1806) / 3 / 2064)
    # a program that places no such stats (the parent; a model without
    # window layers): nothing to read
    other = os.path.join(str(tmp_path), "trace", "other")
    os.makedirs(other)
    jax.profiler.start_trace(other, profiler_options=options)
    with profiling.annotate("serve.step.retire", pools_in_place=1,
                            table_pages=33280, table_pages_held=15000):
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    for r in (peak, held):
        assert r.read(dict(ctx, cell="other")) is None


def test_the_cells_metrics_and_limits():
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "longdoc_closed32"
    assert cell["config_name"] == "trinity-mini"
    assert sorted(cell["metrics"]["end_to_end"]) == ["serve_tokens_per_s",
                                                     "setup_s"]
    mine = [m for m in spec.benchmark()["per_layer"]
            if m["name"].startswith("tri_")]
    assert sorted(cell["metrics"]["per_layer"]) == sorted(
        m["name"] for m in mine) == [
        "tri_compile_s", "tri_compiles_in_window", "tri_decode_attn_ms",
        "tri_decode_matmul_ms", "tri_decode_unnamed_ms",
        "tri_device_idle_pct", "tri_expert_load_peak",
        "tri_experts_touched_pct", "tri_hbm_peak_gib",
        "tri_kv_pages_peak_pct", "tri_prefill_attn_pct",
        "tri_prefill_share_pct", "tri_step_roofline",
        "tri_window_pages_peak_pct", "tri_window_table_held_pct"]
    assert all(m["workloads"] == [CELL] for m in mine)
    assert all(m["moves"] == ("setup_s" if m["name"] == "tri_compile_s"
                              else "serve_tokens_per_s") for m in mine)
    # the region metrics read the vocabulary the program has, the gate's
    # region in it; attention's regions are the benchmark's own list
    from perfbench import regions
    assert "attn.gate" in regions.vocabulary()
    assert "attn.gate" not in regions.ATTENTION
    for size in ("chip", "rehearsal"):
        limits = spec.load_json(os.path.join(
            spec.HERE, "limits", CELL + ".json"))[size]
        assert set(limits) == {"served_logit_gap_mean",
                               "served_logit_gap_widest"}
    bench = spec.benchmark()
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
