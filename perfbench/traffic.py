"""One general traffic generator, driven by the data files under
``perfbench/traffic/``.  No JAX.

A traffic file's ``kind`` is ``train_lm`` (packed sequences for a training
step) or ``serve`` (requests, with ``loop`` ``open_paced`` or ``closed``).

What makes two runs of one cell agree: the multiset of lengths and the
due-times of a cell are THE SAME FOR EVERY SEED.  Lengths are the medians of
equal-probability strata of a distribution, not draws from it; arrivals are
evenly spaced.  In the open loop the seed sets the phase of the arrivals,
rotates and block-shuffles the order, and fills in the token ids; in the
closed loop it fills in the token ids only.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

import numpy as np

#: Requests are shuffled by the seed only inside blocks of this many
#: neighbours, so no seed can put all the long requests side by side.
SHUFFLE_BLOCK = 8


def strata(spec: dict) -> list[int]:
    """Lengths a spec stands for.  ``{"values": [...]}`` is a plain list;
    ``{"dist": "lognormal", median, sigma, min, max, strata}`` gives the
    median of each of ``strata`` equal-probability slices, clipped."""
    if "values" in spec:
        return [int(v) for v in spec["values"]]
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown length spec {spec!r}")
    n = int(spec["strata"])
    norm = NormalDist()
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(
            spec["sigma"] * norm.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def _combos(traffic: dict) -> list[tuple[int, int]]:
    """Every (prompt, output) pair once, in a fixed order in which each run
    of ``len(prompts)`` neighbours holds every prompt length once and the
    outputs advance like a Latin square."""
    prompts, outputs = strata(traffic["prompt"]), strata(traffic["output"])
    np_, no = len(prompts), len(outputs)
    out = []
    for k in range(np_ * no):
        i = k % np_
        j = (k // np_ + (i * no) // np_) % no
        out.append((prompts[i], outputs[j]))
    return out


def request_lengths(traffic: dict, n: int, seed: int) -> list[tuple[int, int]]:
    """``n`` (prompt, output) pairs: the first ``n`` of the fixed cycle of
    combos, rotated by whole blocks and shuffled inside blocks by the seed.
    ``sorted(request_lengths(t, n, s))`` does not depend on ``s``."""
    cycle = _combos(traffic)
    base = [cycle[i % len(cycle)] for i in range(n)]
    rng = random.Random(seed)
    nblocks = max(1, n // SHUFFLE_BLOCK)
    rot = rng.randrange(nblocks) * SHUFFLE_BLOCK
    base = base[rot:] + base[:rot]
    out = []
    for b in range(0, n, SHUFFLE_BLOCK):
        block = base[b:b + SHUFFLE_BLOCK]
        rng.shuffle(block)
        out.extend(block)
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list[int]:
    rng = np.random.default_rng([seed, index, 0x70])
    return rng.integers(0, vocab, length, dtype=np.int64).tolist()


def open_schedule(traffic: dict, seconds: float, seed: int,
                  extra_s: float = 0.0) -> list[dict]:
    """Paced open loop.  Requests due in ``[0, seconds)`` are the measured
    ones (``phase`` "window"); the same rate runs ``lead_s`` before
    (``lead``) and ``extra_s`` after (``tail``) so that the window sees a
    system already and still under load.  Due-times are evenly spaced; the
    seed sets only the phase of the comb."""
    rate = float(traffic["rate_per_s"])
    n = int(round(rate * seconds))
    phase = random.Random(seed ^ 0x5EED).random()
    lengths = request_lengths(traffic, n, seed)
    n_lead = int(math.ceil(float(traffic.get("lead_s", 0.0)) * rate))
    n_tail = int(math.ceil(extra_s * rate))
    items = []
    for i in range(-n_lead, n + n_tail):
        p, o = lengths[i % n]
        items.append({"index": i + n_lead, "due": (i + phase) / rate,
                      "prompt_len": p, "num_tokens": o,
                      "phase": ("lead" if i < 0 else
                                "window" if i < n else "tail")})
    return items


def closed_sequence(traffic: dict, seed: int):
    """Closed loop: an endless fixed cycle of (prompt, output) pairs that
    every caller draws its next request from, THE SAME ORDER FOR EVERY SEED
    (the seed fills in the token ids and nothing else).  A window holds a
    few tens of these requests and each prefill is a lump of thousands of
    tokens, so letting the seed choose where in the cycle a run starts moved
    the rate by a request's worth, about 2% (PERF.md)."""
    del seed
    cycle = _combos(traffic)
    i = 0
    while True:
        p, o = cycle[i % len(cycle)]
        yield {"index": i, "prompt_len": p, "num_tokens": o}
        i += 1


def serve_buckets(traffic: dict, page_size: int) -> list[int]:
    """Distinct prompt lengths, one per prefill bucket (a bucket is a
    prompt's page count): what set-up has to warm."""
    seen = {}
    for p in strata(traffic["prompt"]):
        seen.setdefault(-(-p // page_size), p)
    return sorted(seen.values())


class PackedLmStream:
    """Training feed: documents of lognormal length (stratified the same
    way), each closed by ``eos_id``, packed end to end into rows of
    ``seq_len``.  Batch ``k`` depends on ``(seed, k)`` alone, so the feed
    can be rewound and the reference can make the same rows."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.seq_len = int(traffic["seq_len"])
        self.vocab = int(vocab)
        self.eos = self.vocab - 1
        self.doc_lens = strata(traffic["doc_len"])
        self.seed = int(seed)
        self.cursor = 0

    def batch(self, k: int, rows: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, k, 0x7A])
        need = rows * self.seq_len
        toks = rng.integers(0, self.vocab - 1, need, dtype=np.int32)
        pos = int(rng.integers(0, self.doc_lens[0]))
        order = rng.permutation(len(self.doc_lens))
        i = 0
        while pos < need:
            toks[pos] = self.eos
            pos += self.doc_lens[order[i % len(order)]] + 1
            i += 1
        return toks.reshape(rows, self.seq_len)

    def next_batch(self, rows: int) -> np.ndarray:
        out = self.batch(self.cursor, rows)
        self.cursor += 1
        return out

    def seek(self, k: int) -> None:
        self.cursor = int(k)
