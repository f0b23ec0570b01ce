"""Toy counts for the tests, with ``perfbench/costs.py``'s signatures: every
piece of work is one second of the chip's matmul peak, whatever its size."""

from perfbench import peaks

FLOPS = peaks.PEAKS["TPU v5 lite"]["bf16_flops"]


def train_step(cfg, rows, seq, n_params):
    return {"flops": FLOPS, "bytes": 0.0}


def prefill(cfg, prompt_len, weight_bytes=2.0, kv_bytes=2.0):
    return {"flops": FLOPS, "bytes": 0.0}


def decode_step(cfg, context_lens, weight_bytes=2.0, kv_bytes=2.0):
    return {"flops": FLOPS, "bytes": 0.0}
