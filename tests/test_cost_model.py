"""The program's cost model (``tools/cost_model.py``): the FLOP and byte
counts live MFU and the autotuner price a step with, and the rule that
finds a chip's peak from its ``device_kind``."""

import types

import jax
import pytest

from distributed_tensorflow_tpu.tools import cost_model
from perfbench import peaks


def test_train_step_flops_param_convention():
    """3x forward, forward = 2*params*tokens (the PaLM MFU convention)."""
    assert cost_model.train_step_flops(1000, 32) == 3 * 2 * 1000 * 32


def test_train_step_flops_attention_credit_and_window():
    base = cost_model.train_step_flops(10_000, 64)
    full = cost_model.train_step_flops(10_000, 64, num_layers=2,
                                       hidden_size=128, seq_len=256)
    # Attention adds 4*L*tokens*kv*H per forward, 3x for the step.
    assert full - base == 3 * 4 * 2 * 64 * 256 * 128
    windowed = cost_model.train_step_flops(10_000, 64, num_layers=2,
                                           hidden_size=128, seq_len=256,
                                           window=31)
    assert full - windowed == 3 * 4 * 2 * 64 * (256 - 32) * 128


def test_train_step_bytes_param_convention():
    """Six parameter-sized transfers: read forward and backward, written by
    the update, two read+write pairs of Adam's slots; f32 by default."""
    assert cost_model.train_step_bytes(1000, 32) == 6 * 1000 * 4
    assert cost_model.train_step_bytes(1000, 32, param_bytes=2) == 6 * 1000 * 2


def test_train_step_bytes_transformer_credit():
    base = cost_model.train_step_bytes(10_000, 64)
    full = cost_model.train_step_bytes(10_000, 64, num_layers=2,
                                       hidden_size=128)
    # The residual stream, ~6 passes a layer over forward + backward, each
    # written and read, bf16 by default.
    assert full - base == 6 * 2 * 64 * 128 * 2 * 2
    f32 = cost_model.train_step_bytes(10_000, 64, num_layers=2,
                                      hidden_size=128, act_bytes=4)
    assert f32 - base == 2 * (full - base)
    # Without both dimensions there is nothing to credit.
    assert cost_model.train_step_bytes(10_000, 64, num_layers=2) == base


def test_device_peak_flops_unknown_kind_is_none():
    # CPU test rigs have no entry in the public-spec table: MFU must be
    # null-able rather than fabricated.
    assert cost_model.device_peak_flops() is None


def _chip(kind):
    return types.SimpleNamespace(device_kind=kind)


@pytest.mark.parametrize("kind,tflops", [
    ("TPU v4", 275.0), ("TPU v5 lite", 197.0), ("TPU v5e", 197.0),
    ("TPU v5p", 459.0), ("TPU v6 lite", 918.0), ("TPU v6e", 918.0)])
def test_peak_flops_per_chip_by_reported_device_kind(monkeypatch, kind,
                                                     tflops):
    """The ``device_kind`` strings chips report, each to its own row of the
    table; the aggregate is that times the run's devices."""
    monkeypatch.setattr(jax, "devices", lambda: [_chip(kind)] * 4)
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert cost_model.peak_flops_per_chip() == tflops * 1e12
    assert cost_model.device_peak_flops() == 4 * tflops * 1e12


def test_v5e_peak_equals_the_benchmarks(monkeypatch):
    """Two tables hold the chip's bf16 peak: the program's (live MFU, the
    autotuner) and the benchmark's (``perfbench/peaks.py``, every roofline
    share).  They must say the same of the chip the benchmark runs on."""
    for kind, row in peaks.PEAKS.items():
        monkeypatch.setattr(jax, "devices", lambda kind=kind: [_chip(kind)])
        assert cost_model.peak_flops_per_chip() == row["bf16_flops"], kind
