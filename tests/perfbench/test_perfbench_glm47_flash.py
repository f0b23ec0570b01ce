"""What ``glm-4.7-flash`` brings to the benchmark as new files: its
configuration against the published one, its layout against the program's
own tree, its counts at the published widths (the routed experts' term above
all), the readers of the counters the configuration adds to the program
(``glm_experts_touched_pct``, ``glm_expert_load_peak``), and the message a
program without the fields stops with.  The cell's rehearsal runs with every
other cell's in ``test_perfbench_run.py``.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import peaks, spec, weights, worker
from perfbench.metrics import _common

CELL = "serve_glm47flash_longctx"
CONFIG_FILE = "perfbench/configs/glm-4.7-flash.json"
CONFIG = spec.load_json(os.path.join(spec.ROOT, CONFIG_FILE))
COSTS = spec.named_module(CONFIG, "costs")
TRAFFIC = spec.load_json(os.path.join(spec.HERE, "traffic",
                                      "longctx_closed32.json"))


def reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics", name + ".py"))


def test_the_file_holds_the_published_config_but_what_reduced_names():
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "model_type": "glm4_moe_lite",
        "moe_intermediate_size": 1536, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
        "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_key_value_heads": 20,
        "partial_rotary_factor": 1, "rope_scaling": None,
        "rope_theta": 1000000, "tie_word_embeddings": False,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    assert {k: CONFIG[k] for k in published} == published
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "glm-4.7-flash")
    assert entry["file"] == CONFIG_FILE and entry["source"] == CONFIG["source"]
    assert sorted(entry["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers",
        "num_nextn_predict_layers", "rms_norm_eps"]
    assert {k: CONFIG[k] for k in entry["reduced"]} == {
        "num_hidden_layers": 8, "max_position_embeddings": 8448,
        "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-06}
    assert CONFIG["published"] == {
        "num_hidden_layers": 47, "max_position_embeddings": 202752,
        "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-05}
    # the program's config is the published one, key for key
    m = CONFIG["model"]
    assert (m["hidden_size"], m["num_heads"], m["intermediate_size"],
            m["vocab_size"], m["num_layers"]) == (2048, 20, 10240, 154880, 8)
    assert (m["latent_q_rank"], m["latent_kv_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["rope_base"]) == (
        768, 512, 192, 64, 256, 1e6)
    assert (m["num_experts"], m["experts_per_token"],
            m["expert_intermediate_size"], m["num_shared_experts"],
            m["routed_scaling_factor"], m["first_dense_layers"]) == (
        64, 4, 1536, 1, 1.8, 1)
    assert m["norm_eps"] == CONFIG["rms_norm_eps"]
    assert "chips that share a layer: 1" in CONFIG["deployment"]


def test_the_traffic_fits_the_engine_and_the_pool_holds_every_lane():
    eng = TRAFFIC["engine"]
    cap = eng["page_size"] * eng["max_pages_per_seq"]
    worst = max(TRAFFIC["prompt"]["values"]) + max(
        TRAFFIC["output"]["values"])
    assert worst == cap == TRAFFIC["check_pad"] == 8448
    assert worst <= CONFIG["model"]["max_position"]
    assert eng["num_pages"] >= eng["num_slots"] * eng["max_pages_per_seq"]
    assert TRAFFIC["callers"] == 2 * eng["num_slots"] == 32
    # every bucket is a multiple of 1,024: the flash kernel's layout holds
    from distributed_tensorflow_tpu.ops.pallas import flash_attention as fl
    assert all(fl._layout_ok(p) for p in TRAFFIC["prompt"]["values"])


def test_the_layout_is_the_programs_tree_at_rehearsal_size():
    cfg = spec.deep_update(CONFIG, CONFIG["rehearsal"])
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = gpt_lib.GptLM(worker.gpt_config(
        {"config": cfg, "config_file": CONFIG_FILE}))
    maker = weights.Maker(cfg)
    assert maker.kinds == ["dense", "sparse", "sparse"]
    params = weights.program_tree(7, maker)
    assert worker.check_tree(jax, model, params, cfg) > 0
    sparse = params["layer1"]
    assert sparse["experts_gate"].shape == (8, 64, 32)
    assert sparse["experts_down"].shape == (8, 32, 64)
    assert float(jnp.max(jnp.abs(sparse["router_bias"]))) == 0.0
    assert float(jnp.min(sparse["kv_a_norm"]["scale"])) == 1.0
    # kernels normal / sqrt(fan_in): the stacked ones by their own fan-in
    std = lambda x: float(jnp.std(x.astype(jnp.float32)))  # noqa: E731
    assert std(sparse["experts_gate"]) == pytest.approx(64 ** -0.5, rel=0.05)
    assert std(sparse["experts_down"]) == pytest.approx(32 ** -0.5, rel=0.05)
    assert std(sparse["router"]["kernel"]) == pytest.approx(64 ** -0.5,
                                                            rel=0.2)
    assert not jnp.array_equal(sparse["experts_up"],
                               params["layer2"]["experts_up"])
    assert not jnp.array_equal(sparse["experts_up"][0],
                               sparse["experts_up"][1])


def test_counts_at_published_widths():
    d = COSTS.dims(CONFIG)
    assert (d["n_dense"], d["n_sparse"]) == (1, 7)
    assert d["attn_params"] == 21_757_952      # + 1,280 of inner norms
    assert d["expert_params"] == 9_437_184
    sparse_layer = (d["attn_params"] + 65 * d["expert_params"]
                    + d["router_params"])
    assert sparse_layer == pytest.approx(635.3e6, rel=1e-3)
    total = (8 * d["attn_params"] + d["dense_mlp_params"]
             + 7 * (65 * d["expert_params"] + d["router_params"])
             + 2 * d["head_params"])
    assert total == pytest.approx(5.166e9, rel=1e-3)    # 10.33 GB
    assert CONFIG["model"]["latent_kv_rank"] + 64 == d["row"] == 576


@pytest.mark.parametrize("lanes,touched", [(4, 14.56), (8, 25.81),
                                           (16, 41.21)])
def test_a_decode_step_counts_the_experts_it_is_expected_to_touch(lanes,
                                                                  touched):
    d = COSTS.dims(CONFIG)
    assert COSTS.experts_touched(64, 4, lanes) == pytest.approx(touched,
                                                                abs=0.01)
    ctx = [4000] * lanes
    step = COSTS.decode_step(CONFIG, ctx)
    outside = 2.0 * (8 * d["attn_params"] + d["dense_mlp_params"]
                     + 7 * (d["shared_params"] + d["router_params"])
                     + d["head_params"])
    experts_b = 2.0 * 7 * COSTS.experts_touched(64, 4, lanes) \
        * d["expert_params"]
    rows_b = 2.0 * 576 * (sum(ctx) + lanes) * 8
    assert step["bytes"] == pytest.approx(outside + experts_b + rows_b)
    # never all 64 experts, never 4 private copies a lane
    assert experts_b < 2.0 * 7 * min(64, 4 * lanes) * d["expert_params"]
    assert step["flops"] == pytest.approx(
        2.0 * lanes * (COSTS.active_params(d) + d["head_params"])
        + 2.0 * 20 * (576 + 512) * sum(ctx) * 8)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["bound"] == "memory"


def test_costs_grow_with_lanes_context_and_prompt():
    one = lambda lanes, ctx: COSTS.decode_step(  # noqa: E731
        CONFIG, [ctx] * lanes)
    for key in ("flops", "bytes"):
        assert one(4, 1000)[key] < one(8, 1000)[key] < one(16, 1000)[key]
        assert one(16, 1000)[key] < one(16, 8000)[key]
        assert COSTS.prefill(CONFIG, 1024)[key] < COSTS.prefill(
            CONFIG, 8192)[key]
    # 16 full lanes at the mix's mean context: 6.7 GB of weights (41.2
    # experts a layer) and 0.59 GB of rows, 8.9 ms at the chip's bandwidth
    step = one(16, 4000)
    assert step["bytes"] == pytest.approx(7.28e9, rel=0.01)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["seconds"] == pytest.approx(
        8.9e-3, rel=0.01)
    # a prefill: compute-bound from 2k on, bound by all experts' read at 1k
    assert _common.costs.least_time(COSTS.prefill(CONFIG, 1024),
                                    pk)["bound"] == "memory"
    assert _common.costs.least_time(COSTS.prefill(CONFIG, 8192),
                                    pk)["bound"] == "compute"
    # the scores' share grows with the square: 7 whole layers of 20 heads
    a, b = COSTS.prefill(CONFIG, 1024), COSTS.prefill(CONFIG, 2048)
    square = lambda p: 2.0 * 512 * 20 * p * p / 2.0 * 7  # noqa: E731
    assert b["flops"] - 2 * a["flops"] == pytest.approx(
        square(2048) - 2 * square(1024))


def test_the_roofline_reads_this_configurations_counts():
    assert _common.costs_of(CONFIG) is not _common.costs
    assert _common.costs_of(CONFIG).__file__.endswith(
        "costs/glm-4.7-flash.py")


def test_a_program_without_the_fields_stops_with_the_config_message(
        monkeypatch):
    """What the parent commit does with the new files laid over it: its
    ``GptConfig`` lacks the fields, and ``worker.gpt_config`` says so."""
    import dataclasses
    from distributed_tensorflow_tpu.models import gpt as gpt_lib

    @dataclasses.dataclass(frozen=True)
    class ParentConfig:
        vocab_size: int = 256
        hidden_size: int = 128

    monkeypatch.setattr(gpt_lib, "GptConfig", ParentConfig)
    with pytest.raises(SystemExit) as err:
        worker.gpt_config({"config": CONFIG, "config_file": CONFIG_FILE})
    message = str(err.value)
    assert CONFIG_FILE in message and "latent_kv_rank" in message
    assert "which the program's GptConfig does not have" in message


def test_routing_counters_are_read_from_the_programs_retire_region(
        tmp_path, monkeypatch):
    """A canned traced run: the program's region with its stats, as
    ``serving/engine.py`` places them, in a profile taken here."""
    from distributed_tensorflow_tpu.utils import profiling
    touched, peak = (reader("glm_experts_touched_pct"),
                     reader("glm_expert_load_peak"))
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), "trace", CELL)
    os.makedirs(trace_dir)
    ctx = {"cell": CELL, "trace": {"busy_s": 1.0}}
    for r in (touched, peak):
        assert r.read(dict(ctx, trace=None)) is None     # untraced
        assert r.read(ctx) is None                       # no trace file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for hit, most, lanes in ((290, 5, 16), (280, 6, 16), (150, 3, 8)):
        with profiling.annotate("serve.step.retire", pools_in_place=1,
                                sampled_lanes=0, experts_touched=hit,
                                expert_slots=448, expert_tokens_max=most,
                                routed_tokens=lanes * 4 * 7):
            jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    assert touched.read(ctx) == pytest.approx(
        100.0 * (290 + 280 + 150) / 3 / 448)
    # 8 lanes: a fair share of half a token, so 3 tokens read 6 like the
    # 6 tokens of 16 lanes
    assert peak.read(ctx) == pytest.approx(6.0)
    # a program that places no such stats (the parent): nothing to read
    other = os.path.join(str(tmp_path), "trace", "other")
    os.makedirs(other)
    jax.profiler.start_trace(other, profiler_options=options)
    with profiling.annotate("serve.step.retire", pools_in_place=1,
                            sampled_lanes=0):
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    for r in (touched, peak):
        assert r.read(dict(ctx, cell="other")) is None


def test_the_cells_metrics_and_limits():
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "longctx_closed32"
    assert sorted(cell["metrics"]["end_to_end"]) == ["serve_tokens_per_s",
                                                     "setup_s"]
    assert sorted(cell["metrics"]["per_layer"]) == [
        "glm_compile_s", "glm_compiles_in_window", "glm_device_idle_pct",
        "glm_expert_load_peak", "glm_experts_touched_pct",
        "glm_hbm_peak_gib", "glm_kv_pages_peak_pct",
        "glm_prefill_share_pct", "glm_step_roofline"]
    for size in ("chip", "rehearsal"):
        limits = spec.load_json(os.path.join(
            spec.HERE, "limits", CELL + ".json"))[size]
        assert set(limits) == {"served_logit_gap_mean",
                               "served_logit_gap_widest"}
    bench = spec.benchmark()
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
