"""Plain reference for ``glm-4.7-flash`` (``model_type`` ``glm4_moe_lite``):
latent attention (MLA) in its EXPANDED form only, and routed experts as a
loop over the experts with a mask.  Straightforward ``jax.numpy`` in float32
at ``highest`` matmul precision: no absorbed form, no cache, no sort, no
grouped product, no batching, nothing of the program.  Weights come from
``perfbench.weights`` by leaf name, laid out by the configuration's
``layout``, one layer at a time (a sparse layer is 2.5 GB in float32).

Pre-norm residual block, ``x <- x + mixer(RMSNorm(x))``, ``x <- x +
mlp(RMSNorm(x))``, RMSNorm with ``model.norm_eps``, no biases, SiLU.

**Latent attention**, x in R^hidden, H heads (published: 2048, 20)::

    c_q = RMSNorm(x W_qa)                                   (rank 768)
    [q_nope_h (192) ; q_rot_h (64)] = c_q W_qb              for each head h
    [c_kv (512) ; k_rot (64)] = x W_kva ;  c = RMSNorm(c_kv)
        k_rot is ONE vector a token, shared by all heads
    [k_nope_h (192) ; v_h (256)] = c W_kvb                  for each head h
    q_h = [q_nope_h ; RoPE(q_rot_h)],  k_h = [k_nope_h ; RoPE(k_rot)]
    scores q_h . k_h / sqrt(192 + 64), causal softmax, o_h = sum p v_h
    output [o_1 .. o_H] W_o

RoPE rotates the pairs (i, i + 32) of the 64 rotary entries by
``position * base^(-i/32)``, base ``rope_theta`` = 1e6, no further scaling
(``rope_scaling`` null, ``partial_rotary_factor`` 1 of ``qk_rope_head_dim``).
The program caches ``[c ; RoPE(k_rot)]`` a token a layer and decodes in the
absorbed form; this file never does, which is what the comparison is for.

**Sparse MLP** (layers ``first_dense_layers`` on), E = 64 experts, k = 4::

    s = sigmoid(x_f32 . W_r)                     (E scores, float32)
    chosen = the k largest of s + b              (b: the noaux_tc selection
                                                  bias; n_group = topk_group
                                                  = 1, so no group step)
    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor (1.8)
    y = sum_chosen w_e E_e(x) + E_shared(x),  E(x) = (SiLU(x Wg) * x Wu) Wd

No token is dropped.  The first ``first_dense_layers`` layers have the same
gated MLP at ``intermediate_size``.  Embedding and head untied.  Multi-token
prediction (``num_nextn_predict_layers``) is not modelled: next-token logits
do not depend on it.

**Assumed** (the configuration's file lists each): b = 0; the router's
kernel stored in bfloat16 and applied in float32; the split-half rotary
layout (against an interleaved one a permutation of random columns); a zero
``lm_head`` bias, which the program's head carries; how the leaves are drawn.

Entry points: ``served_gaps`` (the worker's call), ``logits`` (every
position's, for the program's tests), ``experts`` (the sparse MLP alone).
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
    """``x`` [T, ..., D] at positions 0..T-1: pairs (i, i + D/2)."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv              # [T, half]
    ang = ang.reshape(T, *([1] * (x.ndim - 2)), half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _block_rows(T: int, cap: int = 1024) -> int:
    """Query rows scored at once: the largest divisor of T up to ``cap``
    (20 heads x 8,448 x 8,448 float32 scores would be 5.7 GB)."""
    return max(b for b in range(1, min(T, cap) + 1) if T % b == 0)


def latent_attention(model, p, h):
    """``h`` [T, hidden], one sequence -> [T, hidden], causal."""
    eps = model.get("norm_eps", 1e-6)
    nope, kv_rank = model["qk_nope_head_dim"], model["latent_kv_rank"]
    base = model["rope_base"]
    T = h.shape[0]
    c_q = _rms(h @ p["q_a/kernel"], p["q_a_norm/scale"], eps)
    q = jnp.einsum("tr,rnd->tnd", c_q, p["q_b/kernel"])
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], base)], -1)
    kv = h @ p["kv_a/kernel"]
    c = _rms(kv[:, :kv_rank], p["kv_a_norm/scale"], eps)
    k_rot = _rope(kv[:, kv_rank:], base)                       # [T, rope]
    kvb = jnp.einsum("tc,cnd->tnd", c, p["kv_b/kernel"])
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_rot[:, None, :], (T, kvb.shape[1], k_rot.shape[-1]))], -1)
    v = kvb[..., nope:]
    rows = _block_rows(T)

    def scored(q_blk, first):
        scores = jnp.einsum("qnd,knd->nqk", q_blk, k) \
            / jnp.sqrt(F32(q.shape[-1]))
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(lambda a: scored(*a), (
        q.reshape(T // rows, rows, *q.shape[1:]),
        jnp.arange(0, T, rows))).reshape(T, *v.shape[1:])
    return jnp.einsum("qnd,ndh->qh", ctx, p["out/kernel"])


def gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(model, p, h):
    """The sparse MLP over ``h`` [T, hidden]: every expert in turn over
    every token, masked to the tokens that chose it.  Returns (y, the
    chosen experts [T, k])."""
    k = model["experts_per_token"]
    s = jax.nn.sigmoid(h @ p["router/kernel"])
    _, chosen = jax.lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * model["routed_scaling_factor"]

    def one(e, y):
        share = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)     # [T]
        return y + share[:, None] * gated(
            h, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])

    y = jax.lax.fori_loop(0, model["num_experts"], one, jnp.zeros_like(h))
    if model["num_shared_experts"]:
        y = y + gated(h, p["shared_gate/kernel"], p["shared_in/kernel"],
                      p["shared_out/kernel"])
    return y, chosen


def block(model, sparse, p, x):
    """One decoder block over ``x`` [T, hidden]."""
    if model["norm"] != "rmsnorm" or model["activation"] != "swiglu":
        raise ValueError("this reference has RMSNorm and gated SiLU MLPs")
    eps = model.get("norm_eps", 1e-6)
    x = x + latent_attention(model, p, _rms(x, p["ln_attn/scale"], eps))
    h = _rms(x, p["ln_mlp/scale"], eps)
    if sparse:
        return x + experts(model, p, h)[0]
    return x + gated(h, p["mlp_gate/kernel"], p["mlp_in/kernel"],
                     p["mlp_out/kernel"])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


class Layers:
    """The model a layer at a time, each layer's weights made on the spot
    from the seed by the rule that filled the program's tree: one jitted
    function a KIND of layer, the layer's index an argument."""

    def __init__(self, cfg: dict, seed: int):
        model, init = cfg["model"], cfg["init"]
        dtype = jnp.dtype(cfg["param_dtype"])
        lay = weights.layout(cfg)
        self.kinds = list(lay.kinds(model))
        self.halves = weights.seed_halves(seed)
        top_leaves = lay.top(model)

        def top(halves):
            return _f32(weights.top_leaves(
                weights.base_key_from(halves), model, init, dtype,
                top_leaves))

        def layer_fn(kind):
            leaves = lay.layer(model, kind)
            return jax.jit(lambda halves, i, x: block(
                model, kind == "sparse", _f32(weights.layer_leaves(
                    weights.base_key_from(halves), i, model, init, dtype,
                    leaves)), x))

        self._layer = {kind: layer_fn(kind)
                       for kind in dict.fromkeys(self.kinds)}
        self.embed = jax.jit(lambda halves, tokens: top(halves)[
            "word_emb/embedding"][tokens])

        def head(halves, x):
            t = top(halves)
            return _rms(x, t["ln_final/scale"], model.get("norm_eps", 1e-6)) \
                @ t["lm_head/kernel"] + t["lm_head/bias"]

        self.head = jax.jit(head)

    def hidden(self, tokens):
        """``tokens`` [T] -> the stream before the final norm [T, hidden]."""
        x = self.embed(self.halves, tokens)
        for i, kind in enumerate(self.kinds):
            x = self._layer[kind](self.halves, jnp.int32(i), x)
        return x


def logits(cfg: dict, seed: int, tokens) -> np.ndarray:
    """Every position's logits [T, V] for one sequence ``tokens`` [T]."""
    with jax.default_matmul_precision("highest"):
        layers = Layers(cfg, seed)
        x = layers.hidden(jnp.asarray(tokens, jnp.int32))
        return np.asarray(layers.head(layers.halves, x))


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                pad_to: int) -> list[np.ndarray]:
    """For each sample ``{"prompt": [...], "served": [...]}``: the gap, per
    served token, between the reference's best logit at that position and
    the served token's logit there (0 where the served token IS the
    reference's choice).  One sequence at a time, padded to ``pad_to`` (no
    earlier position sees the padding, and a padded token's experts add
    nothing to another token), one layer at a time; the head runs over the
    positions that were served only (8,448 x 154,880 logits would be 5 GB),
    in one shape for all samples."""
    most = max((len(s["served"]) for s in samples), default=0)

    @jax.jit
    def gaps_at(logits, nxt):
        chosen = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
        return jnp.max(logits, -1) - chosen

    out = []
    with jax.default_matmul_precision("highest"):
        layers = Layers(cfg, seed)
        for s in samples:
            seq = list(s["prompt"]) + list(s["served"])
            P, n = len(s["prompt"]), len(s["served"])
            if len(seq) > pad_to:
                raise ValueError(f"sample of {len(seq)} tokens, pad {pad_to}")
            toks = np.zeros((pad_to,), np.int32)
            toks[:len(seq)] = seq
            x = layers.hidden(jnp.asarray(toks))
            # position P-1+j predicts served token j
            at = np.minimum(P - 1 + np.arange(most), pad_to - 1)
            nxt = np.zeros((most,), np.int32)
            nxt[:n] = s["served"]
            gaps = gaps_at(layers.head(layers.halves, x[jnp.asarray(at)]),
                           jnp.asarray(nxt))
            out.append(np.asarray(gaps)[:n])
    return out
