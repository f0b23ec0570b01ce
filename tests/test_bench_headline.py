"""bench.py crash-proof headline (ISSUE 3 satellite): a leg crash or hang
must still end in ONE parseable final headline JSON line with ``ok:
false`` and the failed legs listed — five rounds of BENCH_r*.json had no
parseable headline because a crash exited before the final print.

The bench subprocess is pointed at a COPY of bench.py in a temp dir so
the artifact merge writes a throwaway BENCH_DETAILS.json, never the
committed one."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(tmp_path, env_extra, args=("--mode", "mnist"), timeout=180):
    bench_copy = tmp_path / "bench.py"
    shutil.copyfile(os.path.join(REPO, "bench.py"), bench_copy)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, str(bench_copy), *args], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=timeout)


def _last_json_line(out: str) -> dict:
    lines = [l for l in out.strip().splitlines() if l.strip()]
    assert lines, out
    return json.loads(lines[-1])


@pytest.mark.slow
@pytest.mark.smoke
def test_injected_leg_crash_still_emits_parseable_headline(tmp_path):
    proc = _run_bench(tmp_path, {"BENCH_INJECT_FAULT": "crash:mnist"})
    headline = _last_json_line(proc.stdout)
    assert headline["ok"] is False
    assert headline["failed_legs"] == ["mnist"]
    assert headline["metric"] == "mnist_mlp_steps_per_sec_per_chip"
    assert proc.returncode == 1  # failure is signalled, not swallowed
    # The error survives into the (throwaway) artifact for the postmortem.
    details = json.loads((tmp_path / "BENCH_DETAILS.json").read_text())
    assert "injected crash" in details["extra"]["mnist_error"]


@pytest.mark.slow
def test_hung_leg_hits_per_leg_timeout_and_headline_survives(tmp_path):
    proc = _run_bench(tmp_path, {"BENCH_INJECT_FAULT": "hang:mnist",
                                 "BENCH_LEG_TIMEOUT_S": "3"})
    headline = _last_json_line(proc.stdout)
    assert headline["ok"] is False
    assert headline["failed_legs"] == ["mnist"]
    details = json.loads((tmp_path / "BENCH_DETAILS.json").read_text())
    assert "limit" in details["extra"]["mnist_error"]


@pytest.mark.slow
def test_unavailable_backend_fails_the_run_and_writes_no_artifact(tmp_path):
    """An unavailable accelerator backend must end the run non-zero before
    any leg runs: no fallback to another backend, no headline, and no
    artifact that a later reader could take for a device measurement."""
    proc = _run_bench(
        tmp_path, {"JAX_PLATFORMS": "nosuch",
                   "BENCH_INJECT_FAULT": "crash:mnist"})
    assert proc.returncode != 0
    assert "nosuch" in proc.stdout          # the root cause is reported
    assert '"metric"' not in proc.stdout    # no headline line
    assert not (tmp_path / "BENCH_DETAILS.json").exists()
