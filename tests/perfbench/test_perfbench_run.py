"""The harness end to end at tiny sizes on the CPU (``--rehearse``): the same
control flow as a chip run in both ``--trace`` values, the control (the
configuration's lower precision) read apart from the sound program, and
``correct`` coming out false when the timed path is broken underneath.

A rehearsal skips only the harness's look for a chip; it prints no metric
and exits 4.  Its numbers are held to the ``rehearsal`` limits of ``limits/<cell>.json``, read on
this CPU at these sizes, never to the chip's.
"""

import json
import os
import subprocess
import sys

import pytest

from perfbench import spec

RUN = [sys.executable, os.path.join(spec.HERE, "run.py")]


def rehearse(cell, *extra, seed=2 ** 31 + 77, trace=0, seconds=2):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        RUN + ["--workload", cell, "--seed", str(seed), "--seconds",
               str(seconds), "--trace", str(trace), "--rehearse", *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=spec.ROOT)
    assert proc.returncode == spec.REHEARSAL_EXIT, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    # Never a device metric from the CPU: names only.
    assert all(v == "read" for v in line["metrics"].values())
    assert "memory_peak_bytes" not in line["device"]
    assert list(line)[-2:] == ["check", "rehearsal"]
    assert "check correct" in proc.stderr
    return line


CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_names_its_end_to_end_metrics(cell):
    line = rehearse(cell)
    assert line["correct"] is True, line["check"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == set(spec.cell(cell)["metrics"]["end_to_end"])
    assert line["device"]["count"] == spec.cell(cell)["chips"]


@pytest.mark.parametrize("cell", ["train_gpt2m_1chip", "serve_mistral7b_chat"])
def test_traced_rehearsal_reads_the_per_layer_metrics_the_cpu_can(cell):
    line = rehearse(cell, trace=1)
    assert line["correct"] is True, line["check"]
    assert "compile_s" in line["metrics"]
    # No device plane on the CPU: the trace readers find nothing and their
    # metrics are left out, not reported as 0.
    assert not any(k.endswith(("_roofline", "_device_idle_pct"))
                   for k in line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line


@pytest.mark.parametrize("cell,fault,number", [
    ("train_gpt2m_1chip", "frozen_step", "param_change_gap"),
    ("train_gpt2m_1chip", "dropped_rows", "loss_gap_step1"),
    ("serve_mistral7b_chat", "altered_token", "served_logit_gap_mean"),
    ("serve_mistral7b_chat", "runner_up_token", "served_logit_gap_widest")])
def test_a_broken_timed_path_is_not_correct(cell, fault, number):
    line = rehearse(cell, "--break-path", fault)
    assert line["correct"] is False
    value, limit = line["check"][number]
    assert value > limit


@pytest.mark.parametrize("cell", ["train_gpt2m_1chip", "serve_mistral7b_chat"])
def test_the_lower_precision_control_is_not_correct(cell):
    line = rehearse(cell, "--control")
    assert line["correct"] is False, line["check"]
    over = [k for k, (v, lim) in line["check"].items() if v > lim]
    assert over and "failed" not in over
