"""Plain reference for ``smallthinker-21b-a3b`` (``model_name``
``smallthinker_21b_instruct``): full layers without any position encoding
beside sliding-window layers with rotation, grouped-query heads in groups of
seven, and in every block routed experts whose ROUTER reads the block's
normed input, before attention, while the experts read the stream after it.
Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: a
loop over ALL the experts with the weights zero off the chosen ones, the
window a mask by position over the full score matrix; no ring, no cache, no
page, no sort, no grouped product, no kernel, no batching, nothing of the
program.  Weights come from ``perfbench.weights`` by leaf name, laid out by
the configuration's ``layout``, one layer at a time (a layer is 1.6 GB in
float32).

``x <- E[token]``; then every block, for a stream ``x`` in R^hidden, H query
heads and G key/value heads of D entries (published: 2560; 28, 4, 128, so q
is 3,584 wide and a kv head serves SEVEN query heads)::

    a = RMSNorm_in(x)
    q_h = a W_q,  k_g = a W_k,  v_g = a W_v            no bias, no q/k norm
    SLIDING layer (rope_layout 1): q_h, k_g <- RoPE(.)
    FULL layer (rope_layout 0): NOT rotated (no position encoding there:
        causality alone orders the tokens)
    scores q_h . k_{h // (H/G)} / sqrt(D), causal; on a SLIDING layer only
        keys with 0 <= pos_q - pos_k < sliding_window
    r = a_f32 . W_r                 E logits from the SAME a: the block's
                                    normed INPUT, not the attention's output
    chosen = the k largest of r;   w_j = exp(r_j) / sum_{i in chosen} exp(r_i)
    x <- x + [o_1 .. o_H] W_o
    m = RMSNorm_post(x)             the stream AFTER attention
    y = sum_{j in chosen} w_j (relu(m Wg_j) * (m Wu_j)) Wd_j
    x <- x + y

then a final RMSNorm and an untied head.  RoPE rotates the pairs (i, i +
D/2) of a head's D entries by ``position * base^(-2i/D)``, base
``rope_theta`` = 1.5e6, no scaling.  RMSNorm with ``model.norm_eps`` (the
published 1e-6).  No shared expert, no scale, no selection bias, no dropped
token.

**Departures from the published model, each at its line below**: the
program's q, k/v and out projections and its head carry a bias the published
model lacks (zero here, the same mathematics).  **Assumed** (the
configuration's file lists each, as recalled from the public modelling code
and not re-read): that the router reads ``input_layernorm``'s output; the 6
largest LOGITS and a softmax over those six (with ``norm_topk_prob`` the
same as a softmax over all 64 renormalised over the chosen); ReLU on the
gate branch only; the two-norm placement; the split-half rotary layout;
``rope_layout`` 0 = no rotation; the window counts the query's own position.

Entry points: ``served_gaps`` (the worker's call), ``logits`` (every
position's, for the program's tests); ``route``, ``gated`` and ``experts``
are the pieces the program's tests swap for deliberately wrong ones (each
is looked up by name where it is called).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

F32 = jnp.float32
#: A sequence is padded to a multiple of this many tokens (and never past
#: the cell's ``check_pad``): a sample of 640 tokens is not worth the
#: 13,312-token forward of the longest, and a few shapes compile.
PAD_UNIT = 1024


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


def _block_rows(T: int, cap: int = 256) -> int:
    """Query rows scored at once: the largest divisor of T up to ``cap``
    (28 heads x 13,312 x 13,312 float32 scores would be 20 GB; 256 rows of
    them are 0.4 GB)."""
    return max(b for b in range(1, min(T, cap) + 1) if T % b == 0)


def attention(model, sliding, p, a):
    """``a`` [T, hidden], the normed stream of one sequence -> the heads'
    contexts through the out projection [T, hidden]."""
    T = a.shape[0]
    # departure: the program's projections carry biases; zero here
    q = jnp.einsum("th,hnd->tnd", a, p["q_proj/kernel"]) + p["q_proj/bias"]
    kv = jnp.einsum("th,hcgd->tcgd", a, p["kv_proj/kernel"]) \
        + p["kv_proj/bias"]
    k, v = kv[:, 0], kv[:, 1]
    if sliding:
        q, k = _rope(q, model["rope_base"]), _rope(k, model["rope_base"])
    H, G, D = q.shape[1], k.shape[1], q.shape[2]
    rows = _block_rows(T)
    window = model["sliding_window"] if sliding else T

    def scored(q_blk, first):
        # [G, H/G] query heads against their own key/value head
        s = jnp.einsum("qgrd,kgd->grqk", q_blk.reshape(rows, G, H // G, D),
                       k) / jnp.sqrt(F32(D))
        behind = (first + jnp.arange(rows))[:, None] - jnp.arange(T)[None, :]
        seen = (behind >= 0) & (behind < window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1),
                          v).reshape(rows, H, D)

    ctx = jax.lax.map(lambda blk: scored(*blk), (
        q.reshape(T // rows, rows, H, D),
        jnp.arange(0, T, rows))).reshape(T, H, D)
    return jnp.einsum("qnd,ndh->qh", ctx, p["out/kernel"]) + p["out/bias"]


def route(model, p, a):
    """``a`` [T, hidden], what the router reads -> (the chosen experts
    [T, k], their weights [T, k]): the k largest logits, a softmax over
    those k alone."""
    r = a @ p["router/kernel"]
    top, chosen = jax.lax.top_k(r, model["experts_per_token"])
    return chosen, jax.nn.softmax(top, -1)


def gated(h, w_gate, w_up, w_down):
    return (jax.nn.relu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(model, p, a, m):
    """The sparse MLP: the route read off ``a`` [T, hidden] (the block's
    normed input), every expert in turn over every token of ``m`` (the
    normed stream after attention), weighted zero where the token did not
    choose it.  Returns (y, the chosen experts [T, k])."""
    chosen, w = route(model, p, a)

    def one(e, y):
        share = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)     # [T]
        return y + share[:, None] * gated(
            m, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])

    y = jax.lax.fori_loop(0, model["num_experts"], one, jnp.zeros_like(m))
    return y, chosen


def block(model, kind, p, x):
    """One decoder block of ``kind`` (``"sparse.<mixer>"``, the layout's)
    over ``x`` [T, hidden]."""
    if model["norm"] != "rmsnorm" or model["norm_placement"] != "pre" \
            or model["num_shared_experts"] or model["first_dense_layers"]:
        raise ValueError("this reference has two RMSNorms a block around "
                         "attention and routed experts, nothing shared and "
                         "no dense layer")
    eps = model.get("norm_eps", 1e-6)
    a = _rms(x, p["ln_attn/scale"], eps)
    x = x + attention(model, kind.split(".")[1] == "sliding_attention", p, a)
    m = _rms(x, p["ln_mlp/scale"], eps)
    return x + experts(model, p, a, m)[0]


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
                model, kind, _f32(weights.layer_leaves(
                    weights.base_key_from(halves), i, model, init, dtype,
                    leaves)), x))

        self._layer = {kind: layer_fn(kind)
                       for kind in dict.fromkeys(self.kinds)}
        self.embed = jax.jit(lambda halves, tokens: top(halves)[
            "word_emb/embedding"][tokens])

        def head(halves, x):
            t = top(halves)
            # departure: the program's head carries a bias; zero here
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
    reference's choice).  One sequence at a time, padded to a multiple of
    ``PAD_UNIT`` and at most to ``pad_to`` (no earlier position sees the
    padding, and a padded token's experts add nothing to another token),
    one layer at a time; the head runs over the positions that were served
    only (13,312 x 151,936 logits would be 8 GB), in one shape for all
    samples."""
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
            padded = min(pad_to, -(-len(seq) // PAD_UNIT) * PAD_UNIT)
            toks = np.zeros((padded,), np.int32)
            toks[:len(seq)] = seq
            x = layers.hidden(jnp.asarray(toks))
            # position P-1+j predicts served token j
            at = np.minimum(P - 1 + np.arange(most), padded - 1)
            nxt = np.zeros((most,), np.int32)
            nxt[:n] = s["served"]
            gaps = gaps_at(layers.head(layers.halves, x[jnp.asarray(at)]),
                           jnp.asarray(nxt))
            out.append(np.asarray(gaps)[:n])
    return out
