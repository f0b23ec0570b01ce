"""Plain reference for ``ouro-2.6b`` (``model_type`` ``ouro``): a decoder
whose stack of layers is applied ``R = loop_steps`` times over the SAME
weights.  Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: a Python loop over the steps and the layers, no cache, no
kernel, no scan, no batching, nothing of the program.  Weights come from
``perfbench.weights`` by leaf name, laid out by the configuration's
``layout``, one layer at a time and made anew at every application (the
same values each time: a leaf depends on the seed, the layer and its name).

``h <- E[token]``; for ``t = 1..R``, for ``l = 1..L``::

    a = Attn_l(RMSNorm_{l,1}(h))            H heads of D, no grouping;
        q, k, v = x W_qkv (+ a zero bias); q, k rotated; causal softmax of
        q . k / sqrt(D); [o_1 .. o_H] W_o (+ a zero bias).  The keys and
        values are those of THIS step's stream: step t never sees another
        step's (which is why a cache holds a row a (step, layer))
    h <- h + RMSNorm_{l,2}(a)
    m = W_down(silu(W_gate x) * W_up x),  x = RMSNorm_{l,3}(h)
    h <- h + RMSNorm_{l,4}(m)

then, after layer L of each step, ``h <- RMSNorm_final(h)`` (the normed
``h`` is what step ``t + 1`` starts from) and the exit gate ``lambda_t =
sigmoid(w . h + b)``.  Exit mass ``p_t = lambda_t prod_{j<t} (1 -
lambda_j)`` for ``t < R``, ``p_R`` the rest.  The model leaves at the first
step whose cumulative mass reaches ``early_exit_threshold``; at the
published threshold 1 that is always step R, so ``logits = W_head h`` after
step R (no further norm: ``h`` is normed already).

RoPE rotates the pairs (i, i + D/2) of a head's D entries by ``position *
base^(-2i/D)``, base ``rope_theta``, no scaling.  RMSNorm with
``model.norm_eps``.  Embedding and head untied.

**Assumed** (the configuration's file lists each): the four-norm placement
and the final norm inside the loop, as recalled from the public modelling
code; the split-half rotary layout; zero biases where the program's tree
has one; the gate a Dense(1) over the normed stream, applied in float32;
how the leaves are drawn.

Entry points: ``served_gaps`` (the worker's call, ``perfbench/refs/
dense_decoder.py``'s signature), ``forward`` (every position's logits and
the four exit masses, for the program's tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

F32 = jnp.float32


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, base):
    """``x`` [T, heads, D] at positions 0..T-1: pairs (i, i + D/2)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=F32) / half)
    ang = (jnp.arange(T, dtype=F32)[:, None] * inv)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def attention(model, p, h):
    """``h`` [T, hidden], one sequence -> [T, hidden], causal."""
    T = h.shape[0]
    qkv = jnp.einsum("th,hcnd->tcnd", h, p["qkv/kernel"]) + p["qkv/bias"]
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    q, k = _rope(q, model["rope_base"]), _rope(k, model["rope_base"])
    scores = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    ctx = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("qnd,ndh->qh", ctx, p["out/kernel"]) + p["out/bias"]


def block(model, p, x):
    """One decoder block over ``x`` [T, hidden]: a norm on each sublayer's
    input and one on its output."""
    if (model["norm"], model["activation"], model["norm_placement"],
            model["pos_encoding"], model.get("kv_heads") or 0) != (
                "rmsnorm", "swiglu", "sandwich", "rope", 0):
        raise ValueError("this reference has four RMSNorms a block, gated "
                         "SiLU MLPs, rotary positions and no grouped heads")
    eps = model.get("norm_eps", 1e-6)
    a = attention(model, p, _rms(x, p["ln_attn/scale"], eps))
    x = x + _rms(a, p["ln_attn_post/scale"], eps)
    h = _rms(x, p["ln_mlp/scale"], eps)
    m = (jax.nn.silu(h @ p["mlp_gate/kernel"]) * (h @ p["mlp_in/kernel"])) \
        @ p["mlp_out/kernel"]
    return x + _rms(m, p["ln_mlp_post/scale"], eps)


def exit_masses(gates):
    """``gates`` [R, T], the gate's logits after each step -> the mass that
    leaves at each step [R, T]: ``lambda_t`` of what has not left before,
    and at the last step all that is left."""
    lam = jax.nn.sigmoid(gates)
    left, out = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(out + [left])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


class Loop:
    """The model a layer application at a time, the layer's weights made on
    the spot from the seed by the rule that filled the program's tree: one
    jitted function for every layer, its index an argument."""

    def __init__(self, cfg: dict, seed: int):
        model, init = cfg["model"], cfg["init"]
        dtype = jnp.dtype(cfg["param_dtype"])
        lay = weights.layout(cfg)
        self.model = model
        self.halves = weights.seed_halves(seed)
        top_leaves = lay.top(model)
        (kind,) = set(lay.kinds(model))
        leaves = lay.layer(model, kind)
        eps = model.get("norm_eps", 1e-6)

        def top(halves):
            return _f32(weights.top_leaves(
                weights.base_key_from(halves), model, init, dtype,
                top_leaves))

        self.embed = jax.jit(lambda halves, tokens: top(halves)[
            "word_emb/embedding"][tokens])
        self.layer = jax.jit(lambda halves, i, x: block(
            model, _f32(weights.layer_leaves(
                weights.base_key_from(halves), i, model, init, dtype,
                leaves)), x))

        def close(halves, x):
            """After a step's last layer: the normed stream and the gate."""
            t = top(halves)
            x = _rms(x, t["ln_final/scale"], eps)
            return x, (x @ t["exit_gate/kernel"])[:, 0] + t["exit_gate/bias"]

        self.close = jax.jit(close)
        self.head = jax.jit(lambda halves, x: x @ top(halves)[
            "lm_head/kernel"] + top(halves)["lm_head/bias"])

    def hidden(self, tokens):
        """``tokens`` [T] -> (the head's input [T, hidden], the gate's
        logits [R, T])."""
        x = self.embed(self.halves, tokens)
        gates = []
        for _ in range(self.model["loop_steps"]):
            for i in range(self.model["num_layers"]):
                x = self.layer(self.halves, jnp.int32(i), x)
            x, gate = self.close(self.halves, x)
            gates.append(gate)
        return x, jnp.stack(gates)


def forward(cfg: dict, seed: int, tokens) -> tuple[np.ndarray, np.ndarray]:
    """For one sequence ``tokens`` [T]: every position's logits [T, V] and
    the mass leaving at each loop step [R, T]."""
    with jax.default_matmul_precision("highest"):
        loop = Loop(cfg, seed)
        x, gates = loop.hidden(jnp.asarray(tokens, jnp.int32))
        return (np.asarray(loop.head(loop.halves, x)),
                np.asarray(exit_masses(gates)))


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                pad_to: int) -> list[np.ndarray]:
    """For each sample ``{"prompt": [...], "served": [...]}``: the gap, per
    served token, between the reference's best logit at that position and
    the served token's logit there (0 where the served token IS the
    reference's choice).  One sequence at a time, padded to ``pad_to`` (a
    causal model's earlier positions do not see the padding), one layer
    application at a time; the head runs over the positions that were
    served only, in one shape for all samples."""
    most = max((len(s["served"]) for s in samples), default=0)

    @jax.jit
    def gaps_at(logits, nxt):
        chosen = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
        return jnp.max(logits, -1) - chosen

    out = []
    with jax.default_matmul_precision("highest"):
        loop = Loop(cfg, seed)
        for s in samples:
            seq = list(s["prompt"]) + list(s["served"])
            P, n = len(s["prompt"]), len(s["served"])
            if len(seq) > pad_to:
                raise ValueError(f"sample of {len(seq)} tokens, pad {pad_to}")
            toks = np.zeros((pad_to,), np.int32)
            toks[:len(seq)] = seq
            x, _ = loop.hidden(jnp.asarray(toks))
            # position P-1+j predicts served token j
            at = np.minimum(P - 1 + np.arange(most), pad_to - 1)
            nxt = np.zeros((most,), np.int32)
            nxt[:n] = s["served"]
            gaps = gaps_at(loop.head(loop.halves, x[jnp.asarray(at)]),
                           jnp.asarray(nxt))
            out.append(np.asarray(gaps)[:n])
    return out
