"""What ``ouro-2.6b`` brings to the benchmark as new files: its configuration
against the published one, its layout against the program's own tree at the
rehearsal and at the published sizes, its counts against a hand count (every
block weight once a LOOP STEP), its plain reference against the program,
the readers of the counters the loop adds to the program
(``ouro_loop_steps_mean``, ``ouro_exit_step_expected``), the message a
program without the fields stops with, and the cell's rehearsal.

The lower-precision control (int8 weights, float8 rows) is NOT told apart at
the rehearsal size (64 wide, 14-18 tokens compared, 9 applications deep: it
reads 1.1e-3 and 1.1e-2 where sound runs read 0 to 1.2e-3); like the hybrid's
and the latent cell's, only the chip separates it (``PERF.md`` section 6 has
the readings, ``perfbench/limits/serve_ouro26b_reasoning.json`` the limits).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import peaks, spec, weights, worker
from perfbench.metrics import _common

CELL = "serve_ouro26b_reasoning"
CONFIG_FILE = "perfbench/configs/ouro-2.6b.json"
CONFIG = spec.load_json(os.path.join(spec.ROOT, CONFIG_FILE))
COSTS = spec.named_module(CONFIG, "costs")
TRAFFIC = spec.load_json(os.path.join(spec.HERE, "traffic",
                                      "reasoning_closed16.json"))
#: One block's kernels: qkv and out (4 h^2) and the gated MLP (3 h inner).
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632


def reader(name):
    return spec.load_module(os.path.join(spec.HERE, "metrics", name + ".py"))


def gpt_config(cfg):
    return worker.gpt_config({"config": cfg, "config_file": CONFIG_FILE})


def test_the_file_holds_the_published_config_but_what_reduced_names():
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    assert {k: CONFIG[k] for k in published} == published
    entry = next(c for c in spec.benchmark()["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["file"] == CONFIG_FILE and entry["source"] == CONFIG["source"]
    assert entry["reduced"] == ["max_position_embeddings"]
    assert CONFIG["max_position_embeddings"] == 768
    assert CONFIG["published"] == {"max_position_embeddings": 65536}
    # the program's config is the published one, key for key: no width,
    # not the depth, not the loop count
    m = CONFIG["model"]
    assert (m["hidden_size"], m["num_heads"], m["intermediate_size"],
            m["vocab_size"], m["num_layers"], m["loop_steps"]) == (
        2048, 16, 5632, 49152, 48, 4)
    assert m["hidden_size"] // m["num_heads"] == CONFIG["head_dim"]
    assert (m["norm_placement"], m["exit_gate"], m["rope_base"],
            m["kv_heads"], m["norm_eps"]) == ("sandwich", True, 1e6, 0, 1e-6)
    assert "chips that share a layer: 1; the whole model on this chip" \
        in CONFIG["deployment"]
    assert CONFIG["lower_precision"] == {"quantize": "int8",
                                         "kv_dtype": "float8"}
    assumed = " ".join(CONFIG["assumed"])
    for said in ("as recalled, not re-read (no network)",
                 "split-half rotary layout", "zero lm_head bias",
                 "exit gate a Dense(1) with bias over the normed stream",
                 "weights random from --seed", "65536 -> 768"):
        assert said in assumed, said


def test_the_traffic_is_the_issues_and_the_pool_holds_every_lane():
    eng = TRAFFIC["engine"]
    assert eng == {"num_slots": 8, "page_size": 16, "num_pages": 384,
                   "max_pages_per_seq": 48, "prefill_chunk": 0,
                   "prefill_cache_cap": 8}
    assert (TRAFFIC["loop"], TRAFFIC["callers"]) == ("closed", 16)
    assert TRAFFIC["prompt"]["values"] == [64, 128, 192, 256]
    assert TRAFFIC["output"]["values"] == [128, 256, 384, 512]
    assert (TRAFFIC["request_timeout_s"], TRAFFIC["check_sample"],
            TRAFFIC["check_pad"]) == (180.0, 8, 768)
    cap = eng["page_size"] * eng["max_pages_per_seq"]
    assert 256 + 512 == cap == TRAFFIC["check_pad"] \
        == CONFIG["model"]["max_position"]
    assert eng["num_pages"] == eng["num_slots"] * eng["max_pages_per_seq"]
    # 9.0 GiB of rows, more than the weights' 4.97 GiB
    row = 4 * 48 * 2 * 2048 * 2
    assert row == 1_572_864
    assert eng["num_pages"] * eng["page_size"] * row == 9 * 2 ** 30
    # the callers start with the window (the file's note says why)
    assert TRAFFIC["lead_s"] == 0.0 and "lead_s 0" in TRAFFIC["note"]


@pytest.mark.parametrize("size", ["rehearsal", "published"])
def test_the_layout_is_the_programs_tree(size):
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    cfg = CONFIG if size == "published" else spec.deep_update(
        CONFIG, CONFIG["rehearsal"])
    model, gcfg = cfg["model"], gpt_config(cfg)
    want = jax.eval_shape(lambda: gpt_lib.GptLM(gcfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    lay = weights.layout(cfg)
    flat = dict(lay.top(model))
    for i, kind in enumerate(lay.kinds(model)):
        flat.update({f"layer{i}/{n}": s
                     for n, s in lay.layer(model, kind).items()})
    laid = {n: tuple(s["shape"] if isinstance(s, dict) else s)
            for n, s in flat.items()}
    tree = {jax.tree_util.keystr(p).replace("']['", "/").strip("[]'"):
            x.shape for p, x in jax.tree_util.tree_flatten_with_path(want)[0]}
    assert laid == tree
    n_params = sum(int(np.prod(s)) for s in laid.values())
    if size == "published":
        assert n_params == 2_668_417_025                 # 5.34 GB
        assert len([n for n in laid if n.startswith("layer")]) == 48 * 11
        assert gpt_lib.kv_row_bytes_per_token(gcfg) == 1_572_864
        assert gpt_lib.kv_row_bytes_per_token(
            gcfg, "float8_e4m3fn") == 786_432
    else:
        # made on the device as the worker makes it: norms 1, biases 0
        params = weights.program_tree(7, weights.Maker(cfg))
        assert worker.check_tree(jax, gpt_lib.GptLM(gcfg), params,
                                 cfg) == n_params
        layer = params["layer1"]
        for ln in ("ln_attn", "ln_attn_post", "ln_mlp", "ln_mlp_post"):
            assert float(jnp.min(layer[ln]["scale"])) == 1.0
        assert float(jnp.max(jnp.abs(params["exit_gate"]["bias"]))) == 0.0
        std = lambda x: float(jnp.std(x.astype(jnp.float32)))  # noqa: E731
        assert std(params["exit_gate"]["kernel"]) == pytest.approx(
            64 ** -0.5, rel=0.3)
        assert not jnp.array_equal(layer["qkv"]["kernel"],
                                   params["layer2"]["qkv"]["kernel"])


def test_counts_at_published_widths_against_a_hand_count():
    d = COSTS.dims(CONFIG)
    assert (d["L"], d["R"], d["heads"], d["D"]) == (48, 4, 16, 128)
    assert d["layer_params"] == LAYER == 51_380_224
    assert 48 * LAYER * 2 == pytest.approx(4.93e9, rel=1e-3)   # bytes
    assert d["head_params"] == 2048 * 49152
    ctx = [350] * 8
    step = COSTS.decode_step(CONFIG, ctx)
    # every block weight once a loop step, the head once; 192 rows of keys
    # and of values a held token, and the lanes' new ones
    weights_b = 2.0 * (4 * 48 * LAYER + 2048 * 49152)
    rows_b = 2.0 * 2 * 192 * 2048 * (sum(ctx) + 8)
    assert step["bytes"] == pytest.approx(weights_b + rows_b)
    assert weights_b == pytest.approx(19.93e9, rel=1e-3)
    assert rows_b == pytest.approx(1_572_864 * (2800 + 8))
    assert step["flops"] == pytest.approx(
        2.0 * 8 * (192 * LAYER + 2048 * 49152)
        + 2.0 * 2 * 192 * sum(ctx) * 2048)
    pk = peaks.peaks_for("TPU v5 lite")
    least = _common.costs.least_time(step, pk)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(29.7e-3, rel=0.01)
    # without the loop the same function counts a quarter of the weights
    once = dict(CONFIG, model=dict(CONFIG["model"], loop_steps=1))
    assert COSTS.decode_step(once, ctx)["bytes"] == pytest.approx(
        2.0 * (48 * LAYER + 2048 * 49152) + rows_b / 4)


def test_a_prefill_counts_every_step_and_no_head():
    for p in (64, 256):
        cost = COSTS.prefill(CONFIG, p)
        params = 191 * LAYER + 2 * 2048 * 2048    # the last: keys, values
        assert cost["flops"] == pytest.approx(
            2.0 * params * p + 2.0 * 2 * 191 * p * (p / 2) * 2048)
        assert cost["bytes"] == pytest.approx(
            2.0 * params + 1_572_864 * p)
    pk = peaks.peaks_for("TPU v5 lite")
    # 19.7 GB of weights read (four times 4.93): 24 ms, which 64 tokens'
    # operations stay under and 256 tokens' (5.0 TFLOP, 25.6 ms) just pass
    assert _common.costs.least_time(COSTS.prefill(CONFIG, 64),
                                    pk)["bound"] == "memory"
    assert _common.costs.least_time(COSTS.prefill(CONFIG, 256),
                                    pk)["bound"] == "compute"
    one = lambda lanes, ctx: COSTS.decode_step(  # noqa: E731
        CONFIG, [ctx] * lanes)
    for key in ("flops", "bytes"):
        assert one(4, 300)[key] < one(8, 300)[key] < one(8, 700)[key]
        assert COSTS.prefill(CONFIG, 64)[key] < COSTS.prefill(
            CONFIG, 256)[key]


def test_the_roofline_reads_this_configurations_counts():
    assert _common.costs_of(CONFIG) is not _common.costs
    assert _common.costs_of(CONFIG).__file__.endswith("costs/ouro-2.6b.py")


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
        gpt_config(CONFIG)
    message = str(err.value)
    assert CONFIG_FILE in message and "loop_steps" in message
    assert "which the program's GptConfig does not have" in message


# ----------------------------------- the reference against the program


@pytest.fixture(scope="module")
def small():
    """The rehearsal size in float32, the program's model and tree."""
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    cfg = spec.deep_update(CONFIG, CONFIG["rehearsal"])
    cfg["model"]["dtype"] = cfg["param_dtype"] = "float32"
    cfg["model"]["attention_backend"] = "xla"
    model = gpt_lib.GptLM(gpt_config(cfg))
    seed = 2 ** 31 + 38
    return cfg, model, weights.program_tree(seed, weights.Maker(cfg)), seed


def test_the_reference_is_the_programs_forward(small):
    """Logits of size about 4 within 5e-5 and exit masses within 5e-6:
    both sides float32, sums in another order (a scan against a Python
    loop, flax's fused projections against einsums); sound readings 5e-6
    and 3e-7; bfloat16 anywhere reads 1e-2."""
    cfg, model, params, seed = small
    ref = spec.named_module(cfg, "reference")
    tokens = np.random.default_rng(38).integers(0, 512, 40)
    logits, aux = model.apply({"params": params}, jnp.asarray(tokens)[None],
                              mutable=["loop"])
    want, masses = ref.forward(cfg, seed, tokens)
    assert masses.shape == (3, 40)
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < 5e-5
    assert float(np.abs(np.asarray(aux["loop"]["exit_mass"][0][:, 0])
                        - masses).max()) < 5e-6
    assert np.allclose(masses.sum(0), 1.0, atol=1e-6)
    # every step's gate matters: no mass is all at one step
    assert 0.05 < masses.mean(1).min() and masses.mean(1).max() < 0.9


def test_served_gaps_are_zero_for_the_engines_tokens_and_see_a_wrong_one(
        small):
    """Prefill and paged decode through ``DecodeEngine`` against the
    reference's full forward: a greedy token is the reference's best unless
    two logits lie within 1e-4; the runner-up in one place is seen."""
    from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                           EngineConfig)
    from distributed_tensorflow_tpu.serving.scheduler import Request
    cfg, model, params, seed = small
    ref = spec.named_module(cfg, "reference")
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=8, num_pages=32, max_pages_per_seq=16))
    prompt = np.random.default_rng(39).integers(0, 512, 21).tolist()
    req = Request(prompt, 9)
    engine.admit(req)
    while engine.active_slots:
        engine.step()
    sample = {"prompt": prompt, "served": req.tokens}
    (gaps,) = ref.served_gaps(cfg, seed, [sample], 64)
    assert gaps.shape == (9,) and float(gaps.max()) < 1e-4
    logits, _ = ref.forward(cfg, seed, np.asarray(prompt + req.tokens))
    wrong = list(req.tokens)
    wrong[4] = int(np.argsort(logits[len(prompt) + 3])[-2])
    (gaps,) = ref.served_gaps(cfg, seed, [dict(sample, served=wrong)], 64)
    assert float(gaps[4]) > 1e-3 and float(gaps[:4].max()) < 1e-4


# ------------------------------------------------ the loop's two readers


def test_loop_counters_are_read_from_the_programs_retire_region(
        tmp_path, monkeypatch):
    """A canned traced run: the program's region with its stats, as
    ``serving/engine.py`` places them, in a profile taken here."""
    from distributed_tensorflow_tpu.utils import profiling
    mean, expected = (reader("ouro_loop_steps_mean"),
                      reader("ouro_exit_step_expected"))
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), "trace", CELL)
    os.makedirs(trace_dir)
    ctx = {"cell": CELL, "trace": {"busy_s": 1.0}}
    for r in (mean, expected):
        assert r.read(dict(ctx, trace=None)) is None     # untraced
        assert r.read(ctx) is None                       # no trace file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for lanes, milli in ((8, 17600), (8, 18400), (5, 9000)):
        with profiling.annotate("serve.step.retire", pools_in_place=1,
                                sampled_lanes=0, loop_steps_run=4 * lanes,
                                loop_tokens=lanes,
                                exit_step_expected_milli=milli):
            jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    assert mean.read(ctx) == pytest.approx(4.0)
    assert expected.read(ctx) == pytest.approx(45.0 / 21)
    # a program that places no such stats (the parent): nothing to read
    other = os.path.join(str(tmp_path), "trace", "other")
    os.makedirs(other)
    jax.profiler.start_trace(other, profiler_options=options)
    with profiling.annotate("serve.step.retire", pools_in_place=1,
                            sampled_lanes=0):
        jnp.zeros(8).block_until_ready()
    jax.profiler.stop_trace()
    for r in (mean, expected):
        assert r.read(dict(ctx, cell="other")) is None


def test_the_cells_metrics_and_limits():
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic_name"] == "reasoning_closed16"
    assert sorted(cell["metrics"]["end_to_end"]) == ["serve_tokens_per_s",
                                                     "setup_s"]
    assert sorted(cell["metrics"]["per_layer"]) == [
        "ouro_compile_s", "ouro_compiles_in_window",
        "ouro_decode_step_p50_ms", "ouro_device_idle_pct",
        "ouro_exit_step_expected", "ouro_hbm_peak_gib",
        "ouro_kv_pages_peak_pct", "ouro_loop_steps_mean",
        "ouro_prefill_share_pct", "ouro_step_roofline"]
    limits = spec.load_json(os.path.join(spec.HERE, "limits", CELL + ".json"))
    for size in ("chip", "rehearsal"):
        assert set(limits[size]) == {"served_logit_gap_mean",
                                     "served_logit_gap_widest"}
        assert all(v > 0 for v in limits[size].values())
    assert "control" in limits["note"]
    bench = spec.benchmark()
    mine = [m for m in bench["per_layer"] if m["name"].startswith("ouro_")]
    assert len(mine) == 10 and all(m["workloads"] == [CELL] for m in mine)
    layers = {m["layer"] for m in bench["per_layer"]
              if not m["name"].startswith("ouro_")}
    assert {m["layer"] for m in mine} <= layers
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_cells_rehearsal_runs_on_the_cpu_and_reads_correct():
    """``--rehearse --trace 1``: the cell's whole flow tiny on the CPU (exit
    4, no metric value), every reader finding something to read but the
    three that need a device's operations."""
    run = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         CELL, "--seed", "3000038077", "--seconds", "5", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=600,
        cwd=spec.ROOT)
    assert run.returncode == spec.REHEARSAL_EXIT, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, run.stderr[-2000:]
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["metrics"]) >= {
        "ouro_compile_s", "ouro_compiles_in_window",
        "ouro_decode_step_p50_ms", "ouro_kv_pages_peak_pct",
        "ouro_loop_steps_mean", "ouro_exit_step_expected",
        "ouro_prefill_share_pct"}
    detail = spec.load_json(os.path.join(
        spec.OUT_DIR, CELL + ".trace1.last.json"))
    assert detail["check"]["where"]["tokens_compared"] >= 8
