"""Plain reference for a decoder whose layers are of two kinds
(``model.layer_kinds``): softmax attention over all earlier positions, and
the gated delta rule (Yang et al. 2024) written TOKEN BY TOKEN, a
``lax.scan`` over t carrying each head's state.  Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no chunks, no
cache, no kernels, nothing of the program (whose chunked form in
``ops/linear_attention.py`` it has to stay independent of).  Weights come
from ``perfbench.weights`` by leaf name, laid out by the configuration's
``layout``.  What the kinds share with the dense decoder (the head, the
float32 cast) is ``refs/dense_decoder.py``'s.

A linear-attention layer, for the sublayer's input x_t and head h::

    q~, k~, v~ = W_q x, W_k x, W_v x    each channel through a causal
                 depthwise convolution of K taps (no bias), then SiLU
    q = q~ / |q~| * Dk^-1/2,  k = k~ / |k~|           (|.|: sqrt(sum + 1e-6))
    beta = sigmoid(W_b x) (x 2 with linear_allow_neg_eigval)
    alpha = exp(-exp(A_log) * softplus(W_a x + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    y_t = W_o (RMSNorm(S_t q_t) * SiLU(W_g x_t))

The configuration file's ``assumed`` lists what of this the published config
does not state.  ``norm_placement`` "post" puts each sublayer's norm on its
OUTPUT (x + norm(f(x))), "pre" on its input; ``qk_norm`` norms the whole
projected q and k of a full layer.

Entry points: ``served_gaps`` (``refs/dense_decoder.py``'s signature, one
layer at a time so that 16 layers at 3,840 wide fit in float32),
``logits`` (every position's, for the program's tests) and ``delta_rule``
(the recurrence alone).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import spec, weights

dense = spec.load_module(os.path.join(spec.HERE, "refs", "dense_decoder.py"))

F32 = jnp.float32
FULL, LINEAR = "full_attention", "linear_attention"


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def delta_rule(q, k, v, g, beta, state):
    """The recurrence for one sequence: ``q``, ``k`` [T, H, Dk], ``v``
    [T, H, Dv], log decay ``g`` and ``beta`` [T, H], ``state`` [H, Dv, Dk].
    Returns (o [T, H, Dv], the state after the last token)."""
    def step(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[:, None, None] * S
        S = S - beta[:, None, None] * jnp.einsum("hvk,hk,hj->hvj", S, k, k) \
            + beta[:, None, None] * v[:, :, None] * k[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def linear_mixer(model, p, h):
    """``h`` [T, hidden], one sequence from an empty state -> [T, hidden]."""
    H, Dk = model["linear_num_heads"], model["linear_key_head_dim"]
    Dv, K = model["linear_value_head_dim"], model["linear_conv_kernel_dim"]
    T = h.shape[0]
    raw = jnp.concatenate([h @ p["q_proj/kernel"], h @ p["k_proj/kernel"],
                           h @ p["v_proj/kernel"]], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, raw.shape[1]), F32), raw])
    mixed = jax.nn.silu(sum(padded[j:j + T] * p["conv_taps"][j]
                            for j in range(K)))
    q, k, v = jnp.split(mixed, [H * Dk, 2 * H * Dk], axis=-1)
    q, k, v = (a.reshape(T, H, -1) for a in (q, k, v))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(Dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(h @ p["b_proj/kernel"])
    if model["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(h @ p["a_proj/kernel"]
                                               + p["dt_bias"])
    o, _ = delta_rule(q, k, v, g, beta, jnp.zeros((H, Dv, Dk), F32))
    gate = jax.nn.silu(h @ p["g_proj/kernel"]).reshape(T, H, Dv)
    y = _rms(o, p["o_norm/scale"], model.get("norm_eps", 1e-6)) * gate
    return jnp.einsum("thv,hvd->td", y, p["out/kernel"])


def full_mixer(model, p, h):
    """Causal softmax attention of ``h`` [T, hidden] over all positions."""
    heads = model["num_heads"]
    kvh = model.get("kv_heads") or heads
    if kvh != heads or model["pos_encoding"] != "none":
        raise ValueError("this reference has plain multi-head attention "
                         "with no position encoding; the configuration asks "
                         f"for kv_heads={kvh}, pos_encoding="
                         f"{model['pos_encoding']!r}")
    T = h.shape[0]
    qkv = jnp.einsum("th,hcnd->tcnd", h, p["qkv/kernel"]) + p["qkv/bias"]
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    if model.get("qk_norm"):
        eps = model.get("norm_eps", 1e-6)
        q = _rms(q.reshape(T, -1), p["q_norm/scale"], eps).reshape(q.shape)
        k = _rms(k.reshape(T, -1), p["k_norm/scale"], eps).reshape(k.shape)
    scores = jnp.einsum("qnd,knd->nqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("nqk,knd->qnd", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("qnd,ndh->qh", ctx, p["out/kernel"]) + p["out/bias"]


def block(model, kind, p, x):
    """One decoder block of ``kind`` over ``x`` [1, T, hidden]."""
    if model["norm"] != "rmsnorm" or model["activation"] != "swiglu":
        raise ValueError("this reference has RMSNorm and a gated SiLU MLP")
    eps = model.get("norm_eps", 1e-6)
    post = model.get("norm_placement", "pre") == "post"
    mixer = linear_mixer if kind == LINEAR else full_mixer

    def sublayer(x, f, scale):
        if post:
            return x + _rms(f(x), scale, eps)
        return x + f(_rms(x, scale, eps))

    def mlp(h):
        return (jax.nn.silu(h @ p["mlp_gate/kernel"])
                * (h @ p["mlp_in/kernel"])) @ p["mlp_out/kernel"]

    x = sublayer(x[0], functools.partial(mixer, model, p),
                 p["ln_attn/scale"])
    return sublayer(x, mlp, p["ln_mlp/scale"])[None]


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
            return dense._f32(weights.top_leaves(
                weights.base_key_from(halves), model, init, dtype,
                top_leaves))

        def layer_fn(kind):
            leaves = lay.layer(model, kind)
            return jax.jit(lambda halves, i, x: block(
                model, kind, dense._f32(weights.layer_leaves(
                    weights.base_key_from(halves), i, model, init, dtype,
                    leaves)), x))

        self._layer = {kind: layer_fn(kind)
                       for kind in dict.fromkeys(self.kinds)}
        self.embed = jax.jit(lambda halves, tokens: top(halves)[
            "word_emb/embedding"][tokens])
        self.head = jax.jit(lambda halves, x: dense.head(
            model, top(halves), x))

    def hidden(self, tokens):
        """``tokens`` [1, T] -> the stream before the final norm."""
        x = self.embed(self.halves, tokens)
        for i, kind in enumerate(self.kinds):
            x = self._layer[kind](self.halves, jnp.int32(i), x)
        return x


def logits(cfg: dict, seed: int, tokens) -> np.ndarray:
    """Every position's logits [T, V] for one sequence ``tokens`` [T]."""
    with jax.default_matmul_precision("highest"):
        layers = Layers(cfg, seed)
        x = layers.hidden(jnp.asarray(tokens, jnp.int32)[None])
        return np.asarray(layers.head(layers.halves, x[0]))


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                pad_to: int) -> list[np.ndarray]:
    """For each sample ``{"prompt": [...], "served": [...]}``: the gap, per
    served token, between the reference's best logit at that position and
    the served token's logit there (0 where the served token IS the
    reference's choice).  One sequence at a time, padded to ``pad_to``
    (neither kind of layer lets an earlier position see the padding), one
    layer at a time."""
    @jax.jit
    def finish(logits, tokens, first, count):
        rows = jnp.arange(pad_to)
        nxt = jnp.roll(tokens[0], -1)                      # token at t+1
        chosen = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
        gap = jnp.max(logits, -1) - chosen
        mask = (rows >= first) & (rows < first + count)
        return jnp.where(mask, gap, 0.0)

    out = []
    with jax.default_matmul_precision("highest"):
        layers = Layers(cfg, seed)
        for s in samples:
            seq = list(s["prompt"]) + list(s["served"])
            P, n = len(s["prompt"]), len(s["served"])
            if len(seq) > pad_to:
                raise ValueError(f"sample of {len(seq)} tokens, pad {pad_to}")
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, :len(seq)] = seq
            toks = jnp.asarray(toks)
            x = layers.hidden(toks)
            gaps = finish(layers.head(layers.halves, x[0]), toks, P - 1, n)
            out.append(np.asarray(gaps)[P - 1:P - 1 + n])
    return out
