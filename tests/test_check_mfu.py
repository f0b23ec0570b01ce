"""MFU regression guard (VERDICT r4 #9): the committed bench artifact's
flagship MFU figures are a pinned contract — the guard must fire on an
injected regression and stay quiet on noise within the threshold."""

import json

from distributed_tensorflow_tpu.tools import check_mfu


def artifact(flagship=63.4, s8192=58.9):
    return {
        "metric": "mnist_mlp_steps_per_sec_per_chip",
        "value": 1447.0,
        "extra": {
            "gpt_mfu_pct": flagship,
            "gpt_dense_mfu_pct": 49.6,
            "mfu_by_seq": {
                "mfu_s4096": {"mfu_pct": 63.4, "step_ms": 211.5},
                "mfu_s8192": {"mfu_pct": s8192, "step_ms": 142.4},
            },
        },
    }


def test_fires_on_injected_regression():
    logs = []
    regs = check_mfu.compare(artifact(flagship=60.0), artifact(),
                             threshold=2.0, print_fn=logs.append)
    assert len(regs) == 1
    assert "gpt_mfu_pct: 63.40 -> 60.00" in regs[0]
    assert any("REGRESSION" in line for line in logs)


def test_fires_on_ladder_rung_regression():
    regs = check_mfu.compare(artifact(s8192=55.0), artifact(),
                             threshold=2.0, print_fn=lambda *_: None)
    assert regs and "mfu_by_seq.mfu_s8192" in regs[0]


def test_quiet_within_threshold_and_on_improvement():
    assert check_mfu.compare(artifact(flagship=62.0), artifact(),
                             threshold=2.0, print_fn=lambda *_: None) == []
    assert check_mfu.compare(artifact(flagship=70.0), artifact(),
                             threshold=2.0, print_fn=lambda *_: None) == []


def test_partial_fresh_artifact_skips_not_fails():
    """A partial bench run (mode subset) lacks ladder keys — report the
    skip, don't fail the guard."""
    fresh = {"extra": {"gpt_mfu_pct": 63.4}}
    logs = []
    regs = check_mfu.compare(fresh, artifact(), threshold=2.0,
                             print_fn=logs.append)
    assert regs == []
    assert any("SKIP" in line and "mfu_by_seq" in line for line in logs)


def test_cli_exit_codes(tmp_path):
    good = tmp_path / "good.json"
    bad = tmp_path / "bad.json"
    base = tmp_path / "base.json"
    base.write_text(json.dumps(artifact()))
    good.write_text(json.dumps(artifact()))
    bad.write_text(json.dumps(artifact(flagship=58.0)))
    assert check_mfu.main(["--fresh", str(good),
                           "--committed", str(base)]) == 0
    assert check_mfu.main(["--fresh", str(bad),
                           "--committed", str(base)]) == 1


def test_cli_against_committed_head(tmp_path, monkeypatch, capsys):
    """The default mode (working-tree BENCH_DETAILS.json vs the one
    committed at HEAD) end to end, in a throwaway repository: the repo
    itself commits no bench artifact."""
    import subprocess
    monkeypatch.chdir(tmp_path)
    git = ["git", "-c", "user.name=t", "-c", "user.email=t@example.com"]
    subprocess.run([*git, "init", "-q"], check=True)
    (tmp_path / "BENCH_DETAILS.json").write_text(json.dumps(artifact()))
    subprocess.run([*git, "add", "BENCH_DETAILS.json"], check=True)
    subprocess.run([*git, "commit", "-q", "-m", "artifact"], check=True)
    assert check_mfu.main([]) == 0
    assert "[check_mfu] PASS" in capsys.readouterr().out
    (tmp_path / "BENCH_DETAILS.json").write_text(
        json.dumps(artifact(flagship=58.0)))
    assert check_mfu.main([]) == 1
    assert "[check_mfu] FAIL" in capsys.readouterr().out


def test_train_step_flops_param_convention():
    """3x forward, forward = 2*params*tokens (the PaLM MFU convention)."""
    assert check_mfu.train_step_flops(1000, 32) == 3 * 2 * 1000 * 32


def test_train_step_flops_attention_credit_and_window():
    base = check_mfu.train_step_flops(10_000, 64)
    full = check_mfu.train_step_flops(10_000, 64, num_layers=2,
                                      hidden_size=128, seq_len=256)
    # Attention adds 4*L*tokens*kv*H per forward, 3x for the step.
    assert full - base == 3 * 4 * 2 * 64 * 256 * 128
    windowed = check_mfu.train_step_flops(10_000, 64, num_layers=2,
                                          hidden_size=128, seq_len=256,
                                          window=31)
    assert full - windowed == 3 * 4 * 2 * 64 * (256 - 32) * 128


def test_device_peak_flops_unknown_kind_is_none():
    # CPU test rigs have no entry in the public-spec table: MFU must be
    # null-able rather than fabricated.
    assert check_mfu.device_peak_flops() is None
