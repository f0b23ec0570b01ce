"""What ``olmo-hybrid-7b`` brings to the benchmark as new files: its layout
against the program's own tree, its counts at the published widths, and the
reader of the one counter the configuration adds to the program
(``ohlp_state_peak_mib``).  The cell's rehearsal runs with every other
cell's in ``test_perfbench_run.py``.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from perfbench import peaks, spec, weights, worker
from perfbench.metrics import _common

CELL = "serve_olmohybrid7b_longprompt"
CONFIG = spec.load_json(os.path.join(spec.HERE, "configs",
                                     "olmo-hybrid-7b.json"))
COSTS = spec.named_module(CONFIG, "costs")
STATE = spec.load_module(os.path.join(spec.HERE, "metrics",
                                      "ohlp_state_peak_mib.py"))


def test_the_file_holds_the_published_config_but_what_reduced_names():
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    assert {k: CONFIG[k] for k in published} == published
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert sorted(entry["reduced"]) == ["layer_types",
                                        "max_position_embeddings",
                                        "num_hidden_layers"]
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert CONFIG["layer_types"] == period * 4
    assert CONFIG["model"]["layer_kinds"] == CONFIG["layer_types"]
    assert CONFIG["num_hidden_layers"] == CONFIG["model"]["num_layers"] == 16
    m = CONFIG["model"]
    assert (m["hidden_size"], m["num_heads"], m["intermediate_size"],
            m["vocab_size"]) == (3840, 30, 11008, 100352)
    assert (m["linear_num_heads"], m["linear_key_head_dim"],
            m["linear_value_head_dim"], m["linear_conv_kernel_dim"],
            m["linear_allow_neg_eigval"]) == (30, 96, 192, 4, True)


def test_the_layout_is_the_programs_tree_at_rehearsal_size():
    cfg = spec.deep_update(CONFIG, CONFIG["rehearsal"])
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = gpt_lib.GptLM(worker.gpt_config(
        {"config": cfg, "config_file": "olmo-hybrid-7b.json"}))
    maker = weights.Maker(cfg)
    assert maker.kinds == ["linear_attention"] * 3 + ["full_attention"]
    params = weights.program_tree(7, maker)
    assert worker.check_tree(jax, model, params, cfg) > 0
    lin = params["layer0"]
    assert float(jnp.min(lin["o_norm"]["scale"])) == 1.0
    assert float(jnp.min(params["layer3"]["q_norm"]["scale"])) == 1.0
    # decays a token: exp(-A softplus(dt_bias)) with A in [0.1, 0.4)
    assert -2.4 < float(jnp.min(lin["A_log"])) and \
        float(jnp.max(lin["A_log"])) < -0.9
    assert float(jnp.std(lin["conv_taps"].astype(jnp.float32))) == \
        pytest.approx(0.5, rel=0.2)
    # one layer's leaves differ from the next one's of the same kind
    assert not jnp.array_equal(lin["A_log"], params["layer1"]["A_log"])


def test_counts_at_published_widths():
    d = COSTS.dims(CONFIG)
    assert (d["n_linear"], d["n_full"]) == (12, 4)
    assert d["linear_params"] == 215_516_160        # "215.5 M"
    assert d["full_params"] == 185_794_560          # "185.8 M"
    total = d["block_params"] + 2 * d["head_params"]
    assert total == pytest.approx(4.10e9, rel=0.002)
    # One lane's state over 12 layers: 12 x 30 x 192 x 96 float32.
    assert 12 * d["state_entries"] * 4 == 26_542_080
    # A decode step's bytes for 8 lanes at the mix's mean context: the
    # weights once (no embedding table), K and V of 4 layers, the state
    # read and written once a lane.
    ctx = [2192] * 8
    step = COSTS.decode_step(CONFIG, ctx)
    weights_b = 2.0 * (d["block_params"] + d["head_params"])
    kv_b = 2.0 * 2 * 4 * sum(ctx) * 30 * 128
    state_b = 2.0 * 8 * 12 * (30 * 192 * 96 * 4 + 2 * 3 * 11520)
    assert step["bytes"] == pytest.approx(weights_b + kv_b + state_b)
    assert weights_b == pytest.approx(7.43e9, rel=0.01)
    assert kv_b == pytest.approx(1.08e9, rel=0.01)     # 61 KB a token
    assert state_b == pytest.approx(0.43e9, rel=0.02)
    pk = peaks.peaks_for("TPU v5 lite")
    assert _common.costs.least_time(step, pk)["bound"] == "memory"
    assert _common.costs.least_time(COSTS.prefill(CONFIG, 1024),
                                    pk)["bound"] == "compute"


def test_prefill_grows_faster_than_linearly_only_through_the_full_layers():
    p1, p2 = 1024, 2048
    a, b = COSTS.prefill(CONFIG, p1), COSTS.prefill(CONFIG, p2)
    square = lambda p: 2.0 * 2.0 * 4 * p * (p / 2.0) * 30 * 128  # noqa: E731
    assert b["flops"] - 2 * a["flops"] == pytest.approx(
        square(p2) - 2 * square(p1))
    only_linear = dict(CONFIG, model=dict(
        CONFIG["model"], layer_kinds=["linear_attention"] * 16))
    assert COSTS.prefill(only_linear, p2)["flops"] == pytest.approx(
        2 * COSTS.prefill(only_linear, p1)["flops"])
    # the rule: 7 operations an entry of the state a token
    d = COSTS.dims(CONFIG)
    assert a["flops"] == pytest.approx(
        2.0 * d["block_params"] * p1 + square(p1)
        + 7.0 * 30 * 192 * 96 * 12 * p1)


def test_the_roofline_reads_this_configurations_counts():
    assert _common.costs_of(CONFIG) is not _common.costs
    assert _common.costs_of(CONFIG).__file__.endswith(
        "costs/olmo-hybrid-7b.py")


def test_state_peak_is_read_from_the_programs_retire_region(tmp_path,
                                                            monkeypatch):
    """A canned traced run: the program's region with its stat, as
    ``serving/engine.py`` places it, in a profile taken here."""
    from distributed_tensorflow_tpu.utils import profiling
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), "trace", CELL)
    os.makedirs(trace_dir)
    ctx = {"cell": CELL, "trace": {"busy_s": 1.0}}
    assert STATE.read(dict(ctx, trace=None)) is None     # untraced
    assert STATE.read(ctx) is None                       # no trace file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for slots in (3, 8, 5):
        with profiling.annotate("serve.step.retire", state_slots=slots,
                                state_bytes=slots * 27_371_520):
            jnp.zeros(8).block_until_ready()
        with profiling.annotate("serve.step.retire"):    # a dense step
            pass
    jax.profiler.stop_trace()
    # 8 slots x 12 layers x (2,211,840 B of state + 69,120 B of tail)
    assert STATE.read(ctx) == pytest.approx(8 * 27_371_520 / 2 ** 20)
    assert STATE.read(ctx) == pytest.approx(208.8, abs=0.1)
    # a program that places no such stat (the parent): nothing to read
    other = os.path.join(str(tmp_path), "trace", "other")
    os.makedirs(other)
    jax.profiler.start_trace(other, profiler_options=options)
    with profiling.annotate("serve.step.retire"):
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    assert STATE.read(dict(ctx, cell="other")) is None
