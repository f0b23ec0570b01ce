"""Parallelism autotuner tests (ISSUE 14, docs/autotune.md): cost-model
ranking sanity against measured order, pruning that never drops the true
winner on a small exhaustive space, trial crash/timeout containment, the
autotune_trial telemetry contract, and the profile round-trip through
``train.py --profile``."""

import json
import time

import pytest

from distributed_tensorflow_tpu.parallel.mesh import (
    ParallelConfig, load_run_profile)
from distributed_tensorflow_tpu.tools import autotune as at
from distributed_tensorflow_tpu.tools import cost_model
from distributed_tensorflow_tpu.tools import summarize_run


# -------------------------------------------------------- cost model


def test_host_cost_model_ranks_dp1_over_dp8():
    # On the CPU virtual-mesh proxy a single device already uses every
    # core; extra devices only add collective rendezvous — the model
    # must rank the small layouts ahead (matching the measured order the
    # exhaustive fixture below pins).
    wl = at.mlp_workload(batch_size=256, hidden=64)
    costs = {dp: cost_model.estimate_config_cost(
        {"data": dp}, cost_profile="host", **{
            k: wl.dims.get(k, 0)
            for k in ("n_params", "tokens_per_step")})["est_step_ms"]
        for dp in (1, 2, 4, 8)}
    assert costs[1] < costs[2] < costs[4] < costs[8]


def test_tpu_cost_model_rewards_parallelism_on_big_models():
    dims = dict(n_params=10 ** 9, tokens_per_step=8 * 1024,
                num_layers=24, hidden_size=2048, seq_len=1024)
    dp1 = cost_model.estimate_config_cost({"data": 1},
                                          cost_profile="tpu", **dims)
    dp8 = cost_model.estimate_config_cost({"data": 8},
                                          cost_profile="tpu", **dims)
    assert dp8["est_step_ms"] < dp1["est_step_ms"]
    # The pipeline bubble and the comm terms are live.
    pp = cost_model.estimate_config_cost(
        {"data": 1, "pipe": 2, "microbatch": 4}, cost_profile="tpu",
        **dims)
    assert pp["bubble"] == pytest.approx(0.25)
    assert dp8["comm_ms"] > 0


def test_config_mode_scores_profile_without_devices(tmp_path):
    from distributed_tensorflow_tpu.parallel.mesh import save_run_profile
    path = str(tmp_path / "p.json")
    save_run_profile(path, ParallelConfig(data=2),
                     workload={"n_params": 1000, "tokens_per_step": 64})
    cost = cost_model.score_profile(load_run_profile(path),
                                    cost_profile="host")
    assert cost["est_step_ms"] > 0 and cost["degree"] == 2
    rc = cost_model.main(["--config", path, "--cost-profile", "host"])
    assert rc == 0


# ------------------------------------------------------------- space


def test_enumerate_space_default_first_and_feasible():
    wl = at.mlp_workload(batch_size=256)
    space = at.enumerate_space(8, wl, microbatches=(1, 2))
    assert space[0] == at.default_config(8)
    assert len(space) == len({tuple(sorted(c.to_dict().items()))
                              for c in space})
    # MLP supports only the data axis.
    assert all(c.model == c.seq == c.pipe == 1 for c in space)
    # Infeasible arms (batch not divisible) are pre-filtered for free.
    tiny = at.mlp_workload(batch_size=6)
    space6 = at.enumerate_space(8, tiny, microbatches=(1, 4))
    assert all(tiny.invalid_reason(c) is None for c in space6)
    assert all(c.microbatch != 4 or c.data == 1 for c in space6)


def test_gpt_space_covers_tp_sp_pp_and_quant():
    wl = at.gpt_mini_workload(batch_size=8, seq_len=32)
    space = at.enumerate_space(8, wl, microbatches=(2,),
                               quant_arms=("off", "int8"))
    kinds = {(c.model > 1, c.seq > 1, c.pipe > 1, c.quantize)
             for c in space}
    assert (True, False, False, "off") in kinds     # TP arm
    assert (False, True, False, "off") in kinds     # SP arm
    assert (False, False, True, "off") in kinds     # PP arm
    assert any(q == "int8" for _, _, _, q in kinds)
    # Never more than one non-trivial inner axis (nested shard_map).
    assert all([c.model > 1, c.seq > 1, c.pipe > 1].count(True) <= 1
               for c in space)


def test_select_for_measurement_bounds_and_keeps_default():
    wl = at.mlp_workload(batch_size=256)
    space = at.enumerate_space(8, wl, microbatches=(1, 2))
    scores = at.score_space(space, wl, cost_profile="host")
    default = at.default_config(8)
    chosen = at.select_for_measurement(space, scores, 0.4, default)
    assert len(chosen) <= max(1, int(0.4 * len(space)))
    assert default in chosen
    # The cheapest-estimated layout survives pruning.
    cheapest = min(zip(scores, space),
                   key=lambda p: p[0]["est_step_ms"])[1]
    assert cheapest in chosen


# ------------------------------------------------- measured exhaustive
#
# One REAL exhaustive search over a small space, shared by the
# ranking-sanity and pruning-keeps-winner pins below (compiles once).


@pytest.fixture(scope="module")
def exhaustive():
    wl = at.mlp_workload(batch_size=256, hidden=64)
    summary = at.search(wl, steps=8, warmup=2, measure_fraction=1.0,
                        microbatches=(1, 2), trial_timeout_s=120.0)
    space = at.enumerate_space(8, wl, microbatches=(1, 2))
    scores = at.score_space(space, wl, cost_profile="host")
    return wl, summary, space, scores


def test_exhaustive_search_measures_everything(exhaustive):
    _, summary, space, _ = exhaustive
    assert summary["searched"] == len(space)
    assert summary["measured"] == len(space)
    assert summary["winner"] is not None
    assert all(r["verdict"] == "ok" for r in summary["trials"])


def test_cost_model_ranking_matches_measured_order(exhaustive):
    # Ranking sanity: the analytic order agrees with the measured order
    # on the extremes — the winner is estimated cheaper than the default
    # (dp8) layout, and both orders put dp1-class layouts on top.
    _, summary, _, _ = exhaustive
    winner = summary["winner"]
    default = summary["default_trial"]
    assert winner["step_ms"] < default["step_ms"]
    assert winner["est_step_ms"] < default["est_step_ms"]


def test_pruning_never_drops_the_true_winner(exhaustive):
    # The acceptance property: re-running the same search with 40%
    # pruning must still measure (and therefore select) the exhaustive
    # winner.  Short CPU trials measure near-identical layouts within
    # noise (dp1 vs dp2 differ by <1% here, and either's median can
    # spike ~20% under host scheduling), so "the winner" is the set of
    # layouts within 25% of the best measured step time — pruning must
    # keep at least one of them (the pruned-away dp8 default is 60%+
    # slower, so the assertion still has teeth).
    wl, summary, space, scores = exhaustive
    best_ms = summary["winner"]["step_ms"]
    winner_set = {json.dumps(r["config"], sort_keys=True)
                  for r in summary["trials"]
                  if r["verdict"] == "ok"
                  and r["step_ms"] <= 1.25 * best_ms}
    chosen = at.select_for_measurement(space, scores, 0.4,
                                       at.default_config(8))
    assert len(chosen) <= max(1, int(0.4 * len(space)))
    kept = {json.dumps(c.to_dict(), sort_keys=True) for c in chosen}
    assert winner_set & kept, (sorted(winner_set), sorted(kept))


# -------------------------------------------------------- containment


def _boom_workload():
    wl = at.mlp_workload(batch_size=64)

    def boom(workload, cfg):
        raise RuntimeError("injected trial crash")

    wl.make_trial = boom
    return wl


def _hang_workload():
    wl = at.mlp_workload(batch_size=64)

    def hang(workload, cfg):
        time.sleep(60.0)

    wl.make_trial = hang
    return wl


def test_trial_crash_is_contained():
    r = at.run_trial(ParallelConfig(data=1), _boom_workload(),
                     steps=1, warmup=0, timeout_s=30.0)
    assert r["verdict"] == "crash"
    assert "injected trial crash" in r["error"]
    assert r["step_ms"] is None and r["compile_ms"] is None
    # The telemetry-required keys are present even on a crash.
    assert all(k in r for k in ("config", "step_ms", "compile_ms",
                                "mfu", "verdict"))


def test_trial_timeout_is_contained():
    t0 = time.perf_counter()
    r = at.run_trial(ParallelConfig(data=1), _hang_workload(),
                     steps=1, warmup=0, timeout_s=1.0)
    assert r["verdict"] == "timeout"
    assert time.perf_counter() - t0 < 30.0


def test_infeasible_default_is_not_force_measured():
    # batch 100 on 8 devices: the dp8 default fails the feasibility
    # filter — pruning must not burn a measured slot on the doomed
    # baseline, and the search reports a null ratio instead.
    wl = at.mlp_workload(batch_size=100)
    space = at.enumerate_space(8, wl, microbatches=(1,))
    default = at.default_config(8)
    assert default not in space
    scores = at.score_space(space, wl, cost_profile="host")
    chosen = at.select_for_measurement(space, scores, 0.5, default)
    assert default not in chosen
    summary = at.search(wl, measure_fraction=0.5, microbatches=(1,),
                        measure_fn=_fake_measure)
    assert summary["default_trial"] is None
    assert summary["best_vs_default"] is None
    assert summary["winner"] is not None


def test_autotune_summary_never_mixes_phases():
    # A reused metrics file can carry both tuners' streams; the report's
    # best/default figures must compare within the train phase only
    # (serving step_ms is a mean engine step, not an optimizer step).
    records = [
        {"kind": "autotune_trial", "phase": "train", "verdict": "ok",
         "layout": "dp2-mb1", "step_ms": 10.0, "default": False},
        {"kind": "autotune_trial", "phase": "train", "verdict": "ok",
         "layout": "dp8-mb1", "step_ms": 20.0, "default": True},
        {"kind": "autotune_trial", "phase": "serving", "verdict": "ok",
         "layout": "slots2-page16-spec0-chunk0", "step_ms": 1.0,
         "slo_violations": 1},
    ]
    section = summarize_run.autotune_summary(records)
    assert section["best"]["layout"] == "dp2-mb1"
    assert section["best_vs_default"] == pytest.approx(2.0)
    assert section["slo_violating_trials"] == 1


def test_search_survives_crashing_trials():
    # A crashing arm is a verdict, not a dead tuner: the search completes
    # and crowns a surviving layout.
    wl = at.mlp_workload(batch_size=64)
    calls = {"n": 0}

    def measure(cfg, workload, **kw):
        calls["n"] += 1
        if cfg.data == 1:
            return {"config": cfg.to_dict(), "describe": cfg.describe(),
                    "verdict": "crash", "compile_ms": None,
                    "step_ms": None, "mfu": None, "error": "boom"}
        return {"config": cfg.to_dict(), "describe": cfg.describe(),
                "verdict": "ok", "compile_ms": 10.0,
                "step_ms": 5.0 * cfg.data, "mfu": None, "error": None}

    summary = at.search(wl, measure_fraction=1.0, microbatches=(1,),
                        measure_fn=measure)
    assert calls["n"] == summary["measured"]
    assert summary["winner"] is not None
    assert summary["winner"]["config"]["data"] > 1
    assert any(r["verdict"] == "crash" for r in summary["trials"])


# ----------------------------------------------------------- telemetry


def _fake_measure(cfg, workload, **kw):
    return {"config": cfg.to_dict(), "describe": cfg.describe(),
            "verdict": "ok", "compile_ms": 50.0,
            "step_ms": float(cfg.data), "mfu": None, "error": None}


def test_trial_stream_satisfies_check_contract(tmp_path):
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
    from distributed_tensorflow_tpu.utils.telemetry import Telemetry
    path = str(tmp_path / "trials.jsonl")
    logger = MetricsLogger(path)
    at.search(at.mlp_workload(batch_size=64), measure_fraction=1.0,
              microbatches=(1,), telemetry=Telemetry(logger),
              measure_fn=_fake_measure)
    logger.close()
    records, errors = summarize_run.load_records(path)
    assert records and not errors
    assert all(r["kind"] == "autotune_trial" for r in records)
    missing = [f for f in summarize_run.REQUIRED_AUTOTUNE_FIELDS
               if f not in records[0]]
    assert not missing
    # A tuner-only stream is a first-class --check citizen...
    assert summarize_run.check_records(records, []) == []
    # ...and the report grows a tuner section with the speedup.
    section = summarize_run.autotune_summary(records)
    assert section["trials"] == len(records)
    assert section["ok"] == len(records)
    assert section["best"]["layout"] == "dp1-mb1"
    assert section["best_vs_default"] == pytest.approx(8.0)
    # A record missing a required field fails --check.
    broken = [dict(r) for r in records]
    del broken[0]["verdict"]
    assert summarize_run.check_records(broken, [])


def test_serving_scoring_against_slos():
    from distributed_tensorflow_tpu.serving.slo import parse_slos
    objectives = parse_slos("ads:ttft_p95_ms<=10,search:ttft_p95_ms<=10,"
                            "*:tpot_p99_ms<=10000,*:e2e_p95_ms<=1,"
                            "*:error_rate<=0.5")
    # Tenant-scoped objectives evaluate over THEIR tenant's stream: ads
    # is fast (meets 10ms), search is slow (violates) — the merged
    # stream would mis-score both.  The wildcard e2e bar is impossible.
    trial = {"ttft_ms": [5.0, 50.0, 6.0, 60.0],
             "ttft_ms_by_tenant": {"ads": [5.0, 6.0],
                                   "search": [50.0, 60.0]},
             "tpot_ms": [2.0, 3.0], "tpot_ms_by_tenant": {},
             "e2e_ms": [100.0, 200.0], "e2e_ms_by_tenant": {}}
    n, labels = at.score_against_slos(trial, objectives)
    assert n == 2
    assert any(v.startswith("search:ttft") for v in labels)
    assert any("e2e" in v for v in labels)
    assert not any(v.startswith("ads:") for v in labels)
    arms = at.serving_space(slots=(4, 64), num_pages=128,
                            max_pages_per_seq=4)
    # Geometry the pool can't host is filtered (64 * 4 > 128 pages).
    assert all(a["num_slots"] * a["max_pages_per_seq"] <= 128
               for a in arms)
    assert {a["num_slots"] for a in arms} == {4}


@pytest.mark.slow
def test_serving_search_real_drive(tmp_path):
    # One real serving-knob trial through the in-process engine drive:
    # the arm measures, scores against a generous SLO (0 violations),
    # and lands as a --check-green autotune_trial record.
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
    from distributed_tensorflow_tpu.utils.telemetry import Telemetry
    path = str(tmp_path / "serve_trials.jsonl")
    logger = MetricsLogger(path)
    summary = at.serving_search(
        slo_spec="*:tpot_p99_ms<=60000", slots=(2,), page_sizes=(16,),
        spec_ks=(0,), prefill_chunks=(0,), n_requests=4, gen_tokens=6,
        telemetry=Telemetry(logger))
    logger.close()
    winner = summary["winner"]
    assert winner is not None and winner["verdict"] == "ok"
    assert winner["tokens_per_sec"] > 0
    assert winner["slo_violations"] == 0
    records, errors = summarize_run.load_records(path)
    assert records and not errors
    assert summarize_run.check_records(records, []) == []
    assert records[0]["phase"] == "serving"


# ------------------------------------------------------ profile e2e


def test_emit_profile_and_train_consumes_it(tmp_path, monkeypatch):
    # The round trip the whole tool exists for: a search winner written
    # as a run profile, train.py --profile reproducing the tuned layout
    # (mesh size, grad accumulation) end to end through the real CLI
    # main().
    from helpers import patch_standalone_server
    patch_standalone_server(monkeypatch)
    from distributed_tensorflow_tpu.train import (FLAGS, apply_run_profile,
                                                  main)

    wl = at.mlp_workload(batch_size=32)

    def measure(cfg, workload, **kw):
        # Crown dp2-mb2 deliberately: both a mesh override AND a
        # microbatch override must survive the round trip.
        ms = 1.0 if (cfg.data, cfg.microbatch) == (2, 2) else 9.0
        return {"config": cfg.to_dict(), "describe": cfg.describe(),
                "verdict": "ok", "compile_ms": 5.0, "step_ms": ms,
                "mfu": None, "error": None}

    summary = at.search(wl, measure_fraction=1.0, microbatches=(1, 2),
                        measure_fn=measure)
    assert summary["winner"]["describe"] == "dp2-mb2"
    profile_path = str(tmp_path / "profile.json")
    payload = at.emit_profile(profile_path, summary, wl)
    assert payload["parallel"]["data"] == 2
    # The trial split the 32-row global batch across 2 microsteps;
    # train.py feeds batch_size PER microstep, so the profile records 16
    # and the replayed run is exactly the measured workload.
    assert payload["workload"]["batch_size"] == 16
    assert payload["tuning"]["best_vs_default"] > 1.0

    argv = ["--job_name=worker", "--task_index=0",
            "--data_dir=/nonexistent", "--sync_replicas=true",
            "--worker_hosts=localhost:0", "--ps_hosts=localhost:0",
            "--learning_rate=0.05", "--log_every=1",
            "--validation_every=0", "--train_steps=2",
            "--save_interval_steps=1000000",
            f"--logdir={tmp_path}/logdir",
            f"--profile={profile_path}"]
    FLAGS.parse(argv)
    applied, pcfg = apply_run_profile(FLAGS)
    assert pcfg == ParallelConfig.from_dict(payload["parallel"])
    assert applied["grad_accum_steps"] == 2
    assert applied["batch_size"] == 16
    assert pcfg.build_mesh().devices.size == 2     # dp2 submesh pinned
    # And the real training run completes under the profile.
    FLAGS.parse(argv)
    result = main([])
    assert result.final_global_step >= 2
    assert FLAGS.grad_accum_steps == 2


def test_profile_overrides_are_authoritative_both_ways(tmp_path):
    # Review fixes (PR 14): the profile is the layout of record —
    # a stale command line cannot survive it.
    from distributed_tensorflow_tpu.parallel.mesh import save_run_profile
    from distributed_tensorflow_tpu.train import FLAGS, apply_run_profile

    base = ["--job_name=worker", "--task_index=0",
            "--data_dir=/nonexistent",
            "--worker_hosts=localhost:0", "--ps_hosts=localhost:0"]

    # (1) A pipeline winner maps microbatch to --pipeline_microbatches
    # (NOT grad accumulation, which train.py rejects alongside pipe>1),
    # and clears a stale --grad_accum_steps.
    pp_path = str(tmp_path / "pp.json")
    save_run_profile(pp_path, ParallelConfig(data=1, pipe=2, microbatch=8),
                     workload={"model": "gpt_mini", "seq_len": 32,
                               "pipeline_schedule": "gpipe"})
    FLAGS.parse(base + ["--grad_accum_steps=2",
                        "--pipeline_schedule=interleaved",
                        f"--profile={pp_path}"])
    applied, pcfg = apply_run_profile(FLAGS)
    assert applied["pipeline_microbatches"] == 8
    assert FLAGS.pipeline_microbatches == 8
    assert FLAGS.grad_accum_steps == 1          # stale knob reset
    assert FLAGS.pipeline_parallel == 2
    # Trial-pinned knobs recorded in the profile override stale flags:
    # the tuner measured the gpipe schedule, not interleaved.
    assert FLAGS.pipeline_schedule == "gpipe" 

    # (2) quantize='off' clears a stale --gpt_matmul_int8=true, and a
    # dp-only profile clears a stale --attention_backend=ring; the
    # model-shape knob (hidden_units) the tune recorded is applied too.
    off_path = str(tmp_path / "off.json")
    save_run_profile(off_path, ParallelConfig(data=2),
                     workload={"model": "mnist_mlp", "hidden_units": 128})
    FLAGS.parse(base + ["--gpt_matmul_int8=true",
                        "--attention_backend=ring",
                        f"--profile={off_path}"])
    applied, _ = apply_run_profile(FLAGS)
    assert FLAGS.gpt_matmul_int8 is False
    assert applied["gpt_matmul_int8"] is False
    assert FLAGS.attention_backend == "xla"
    assert FLAGS.hidden_units == 128


def test_unknown_quant_arm_rejected():
    # Strict like ParallelConfig.from_dict: a typo'd or unsupported arm
    # must error, never silently search "off" only.
    with pytest.raises(ValueError, match="not supported"):
        at.enumerate_space(8, at.mlp_workload(batch_size=64),
                           quant_arms=("int8",))
    with pytest.raises(ValueError, match="not supported"):
        at.enumerate_space(8, at.gpt_mini_workload(),
                           quant_arms=("in8",))


def test_pipeline_space_never_carries_quant_arms():
    # The int8 arm is not plumbed through the pipeline bundles; an
    # enumerated pp-int8 arm would time the unquantized step under an
    # int8 label and emit a profile train.py rejects.
    wl = at.gpt_mini_workload(batch_size=8, seq_len=32)
    space = at.enumerate_space(8, wl, microbatches=(2,),
                               quant_arms=("off", "int8"))
    assert all(c.quantize == "off" for c in space if c.pipe > 1)
    assert any(c.quantize == "int8" for c in space)   # non-pp arms keep it


def test_autotune_cli_headline_contract(tmp_path):
    # The CLI's one-line machine contract (the CI gate parses it):
    # run a real 2-arm tune end to end through main().
    out = str(tmp_path / "profile.json")
    trials = str(tmp_path / "trials.jsonl")
    lines = []
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = at.main(["--workload", "mlp", "--batch_size", "64",
                      "--steps", "2", "--warmup", "1",
                      "--microbatches", "1", "--device_counts", "1,2",
                      "--measure_fraction", "1.0", "--out", out,
                      "--metrics_file", trials])
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    assert rc == 0
    headline = json.loads(lines[-1])
    assert headline["ok"] is True
    assert headline["searched"] >= 3
    assert headline["winner"]
    assert headline["profile"] == out
    profile = load_run_profile(out)
    assert "parallel" in profile and "tuning" in profile
    records, errors = summarize_run.load_records(trials)
    assert not errors
    assert summarize_run.check_records(records, []) == []
