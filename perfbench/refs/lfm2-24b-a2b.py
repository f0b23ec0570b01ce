"""Plain reference for ``lfm2-24b-a2b`` (``model_type`` ``lfm2_moe``): gated
short-convolution layers beside grouped-query attention layers with a norm a
head, two norms a block, and routed experts (none shared) as a loop over ALL
the experts with a mask.  Straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision: no tail, no cache, no page, no sort, no grouped
product, no kernel, no batching, nothing of the program.  The convolution is
a sum of three shifted products over the whole sequence.  Weights come from
``perfbench.weights`` by leaf name, laid out by the configuration's
``layout``, one layer at a time (a sparse layer is 2.5 GB in float32).

``x <- E[token]`` (not scaled); then every block, for a stream ``x`` in
R^hidden (published: 2048)::

    a = RMSNorm_operator(x)
    CONVOLUTION layer (K taps, published 3):
        [B | C | X] = a W_in            three thirds of 3 x hidden, in
                                        that order, no bias
        u_t = B_t * X_t                 entry by entry
        c_t = sum_{j=0..K-1} w[j] * u_{t-(K-1)+j}      depthwise, causal,
                                        zeros before position 0, no bias
        x <- x + (C * c) W_out
    ATTENTION layer (H query heads, G key/value heads of D; 32, 8, 64):
        q_h = a W_q,  k_g = a W_k,  v_g = a W_v
        q_h <- RMSNorm_q(q_h),  k_g <- RMSNorm_k(k_g)    over a head's D
            entries, ONE scale vector of D for all q heads, one for all k
        q_h, k_g <- RoPE(.)             base rope_theta = 1e6 over D
        scores q_h . k_{h // (H/G)} / sqrt(D), causal
        x <- x + [softmax(scores) v_{h // (H/G)}]_h W_o
    m = RMSNorm_ffn(x)
    first ``first_dense_layers`` layers:  y = (SiLU(m W_gate) * m W_up) W_down
    the others:  s = sigmoid(m_f32 . W_r)            (E scores, float32)
        chosen = the k largest of s + b              (b: the selection bias)
        w = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
        y = sum_chosen w_e E_e(m)
    x <- x + y

then a final RMSNorm and a head.  RoPE rotates the pairs (i, i + D/2) of a
head's D entries by ``position * base^(-2i/D)``, no scaling.  RMSNorm with
``model.norm_eps``.  No token is dropped by the experts.

**Departures from the published model, each at its line below**: the
program's q, k/v and attention-out projections and its head carry a bias the
published model lacks (zero here, the same mathematics); ``norm_eps`` is the
program's 1e-6 for the published 1e-5 (``reduced``); b = 0; the head is an
untied ``lm_head`` of its own draw where the published model is recalled to
tie it to the embedding (other numbers, not other mathematics).  The
reference keeps the published ``+ 1e-6`` under the routing weights' sum
where the program's ``ops/routed_experts.route`` has ``+ 1e-20``: 5e-7 of a
weight (the chosen scores sum to 2 or so).  The program rounds ``u`` to
bfloat16 before the taps read it (what its tail holds); this reference
rounds nothing.  **Assumed** (the configuration's file lists each, as
recalled from the public ``modeling_lfm2_moe.py`` and not re-read): the
order of the three thirds, the taps over ``B * X`` with ``C`` after, the norm
a head before the rotation, the split-half rotary layout, the two-norm
placement.

Entry points: ``served_gaps`` (the worker's call), ``logits`` (every
position's, for the program's tests), ``experts`` and ``short_conv`` (one
mixer alone).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

F32 = jnp.float32
#: A sequence is padded to a multiple of this many tokens (and never past
#: the cell's ``check_pad``): a few shapes compile.
PAD_UNIT = 512


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
    (32 heads x 3,072 x 3,072 float32 scores would be 1.2 GB; 256 rows of
    them are 0.1 GB)."""
    return max(b for b in range(1, min(T, cap) + 1) if T % b == 0)


def short_conv(p, a):
    """``a`` [T, hidden], the normed stream of one sequence -> the gated
    convolution through the out projection [T, hidden]."""
    T = a.shape[0]
    gate_in, gate_out, value = jnp.split(a @ p["in_proj/kernel"], 3, -1)
    u = gate_in * value
    taps = p["conv_taps"]                       # [K, hidden], oldest first
    K = taps.shape[0]
    # c_t = sum_j taps[j] * u_{t-(K-1)+j}: K shifted copies of the sequence
    # with zeros standing before position 0.
    c = sum(taps[j] * jnp.pad(u, ((K - 1 - j, 0), (0, 0)))[:T]
            for j in range(K))
    return (gate_out * c) @ p["out/kernel"]


def attention(model, p, a):
    """``a`` [T, hidden] -> the heads' contexts through the out projection
    [T, hidden]."""
    eps = model.get("norm_eps", 1e-6)
    T = a.shape[0]
    # departure: the program's projections carry biases; zero here
    q = jnp.einsum("th,hnd->tnd", a, p["q_proj/kernel"]) + p["q_proj/bias"]
    kv = jnp.einsum("th,hcgd->tcgd", a, p["kv_proj/kernel"]) \
        + p["kv_proj/bias"]
    q = _rope(_rms(q, p["q_norm/scale"], eps), model["rope_base"])
    k = _rope(_rms(kv[:, 0], p["k_norm/scale"], eps), model["rope_base"])
    v = kv[:, 1]
    H, G, D = q.shape[1], k.shape[1], q.shape[2]
    rows = _block_rows(T)

    def scored(q_blk, first):
        # [G, H/G] query heads against their own key/value head
        s = jnp.einsum("qgrd,kgd->grqk", q_blk.reshape(rows, G, H // G, D),
                       k) / jnp.sqrt(F32(D))
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, -1),
                          v).reshape(rows, H, D)

    ctx = jax.lax.map(lambda blk: scored(*blk), (
        q.reshape(T // rows, rows, H, D),
        jnp.arange(0, T, rows))).reshape(T, H, D)
    return jnp.einsum("qnd,ndh->qh", ctx, p["out/kernel"]) + p["out/bias"]


def gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def experts(model, p, h):
    """The sparse MLP over ``h`` [T, hidden]: every expert in turn over
    every token, masked to the tokens that chose it.  Returns (y, the
    chosen experts [T, k])."""
    k = model["experts_per_token"]
    s = jax.nn.sigmoid(h @ p["router/kernel"])
    # departure: b is a trained quantity; zero here (the layout's constant)
    _, chosen = jax.lax.top_k(s + p["router_bias"], k)
    w = jnp.take_along_axis(s, chosen, -1)
    # the published + 1e-6 (the program's route() has + 1e-20)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) \
        * model["routed_scaling_factor"]

    def one(e, y):
        share = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)     # [T]
        return y + share[:, None] * gated(
            h, p["experts_gate"][e], p["experts_up"][e],
            p["experts_down"][e])

    y = jax.lax.fori_loop(0, model["num_experts"], one, jnp.zeros_like(h))
    return y, chosen


def block(model, kind, p, x):
    """One decoder block of ``kind`` (``"<mlp>.<mixer>"``, the layout's)
    over ``x`` [T, hidden]."""
    if model["norm"] != "rmsnorm" or model["activation"] != "swiglu" \
            or model["norm_placement"] != "pre" \
            or model["num_shared_experts"]:
        raise ValueError("this reference has two RMSNorms a block, gated "
                         "SiLU MLPs and no shared expert")
    mlp, mixer = kind.split(".")
    # departure: the program's one epsilon, 1e-6 (published 1e-5)
    eps = model.get("norm_eps", 1e-6)
    a = _rms(x, p["ln_attn/scale"], eps)
    x = x + (short_conv(p, a) if mixer == "short_conv"
             else attention(model, p, a))
    m = _rms(x, p["ln_mlp/scale"], eps)
    if mlp == "sparse":
        return x + experts(model, p, m)[0]
    return x + gated(m, p["mlp_gate/kernel"], p["mlp_in/kernel"],
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
                model, kind, _f32(weights.layer_leaves(
                    weights.base_key_from(halves), i, model, init, dtype,
                    leaves)), x))

        self._layer = {kind: layer_fn(kind)
                       for kind in dict.fromkeys(self.kinds)}
        self.embed = jax.jit(lambda halves, tokens: top(halves)[
            "word_emb/embedding"][tokens])

        def head(halves, x):
            t = top(halves)
            # departures: an untied head of its own draw; its bias zero
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
    padding: the convolution and the attention are causal, and a padded
    token's experts add nothing to another token), one layer at a time; the
    head runs over the positions that were served only, in one shape for
    all samples."""
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
