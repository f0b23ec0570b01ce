"""Plain reference for the dense decoders the benchmark runs (GPT-2 style:
layer norm, gelu, learned positions, fused qkv; Mistral style: rms norm,
gated silu, rotary positions, grouped key/value heads).  Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no
cache, no batching tricks.  It imports nothing of the program and takes
nothing the program has made; its weights come from ``perfbench.weights`` by
leaf name, the same rule that filled the program's tree.

Departures from the published descriptions are the configuration file's
``assumed`` list (epsilons, an untied biased head); the reference follows
the configuration AS RUN, since it is the program it has to agree with.

Two entry points:

- ``served_gaps``: teacher-forced forward over prompt + served tokens, one
  layer at a time with that layer's weights made on the spot (a 16-layer
  7B-class model does not fit in float32 otherwise); returns, per served
  token, how far its logit lies below the reference's best.
- ``train_steps``: loss, per-leaf first-gradient norms and per-leaf
  parameter-change norms of Adam steps, gradients accumulated over blocks
  of rows with each layer rematerialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights

F32 = jnp.float32


def _norm(model, x, p, name):
    eps = model.get("norm_eps", 1e-6)
    if model["norm"] == "rmsnorm":
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p[f"{name}/scale"]
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p[f"{name}/scale"] \
        + p[f"{name}/bias"]


def _rope(x, positions, base=10000.0):
    half = x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions[:, :, None].astype(F32) * inv          # [B, T, half]
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(model, p, x):
    """One pre-norm decoder block over ``x`` [B, T, H] (causal)."""
    B, T, _ = x.shape
    heads = model["num_heads"]
    kvh = model.get("kv_heads") or heads
    h = _norm(model, x, p, "ln_attn")
    if kvh == heads:
        qkv = jnp.einsum("bth,hcnd->btcnd", h, p["qkv/kernel"]) + p["qkv/bias"]
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:
        q = jnp.einsum("bth,hnd->btnd", h, p["q_proj/kernel"]) \
            + p["q_proj/bias"]
        kv = jnp.einsum("bth,hcgd->btcgd", h, p["kv_proj/kernel"]) \
            + p["kv_proj/bias"]
        k, v = kv[:, :, 0], kv[:, :, 1]
    if model["pos_encoding"] == "rope":
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q, k = _rope(q, pos), _rope(k, pos)
    if kvh != heads:
        k = jnp.repeat(k, heads // kvh, axis=2)
        v = jnp.repeat(v, heads // kvh, axis=2)
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / jnp.sqrt(F32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    ctx = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(scores, -1), v)
    x = x + jnp.einsum("bqnd,ndh->bqh", ctx, p["out/kernel"]) + p["out/bias"]
    h = _norm(model, x, p, "ln_mlp")
    if model["activation"] == "swiglu":
        h = jax.nn.silu(h @ p["mlp_gate/kernel"]) * (h @ p["mlp_in/kernel"])
        return x + h @ p["mlp_out/kernel"]
    h = jax.nn.gelu(h @ p["mlp_in/kernel"] + p["mlp_in/bias"],
                    approximate=True)
    return x + h @ p["mlp_out/kernel"] + p["mlp_out/bias"]


def embed(model, top, tokens):
    x = top["word_emb/embedding"][tokens]
    if model["pos_encoding"] != "rope":
        x = x + top["pos_emb/embedding"][jnp.arange(tokens.shape[1])][None]
    return x


def head(model, top, x):
    return _norm(model, x, top, "ln_final") @ top["lm_head/kernel"] \
        + top["lm_head/bias"]


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


# ------------------------------------------------------------ serving


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                pad_to: int) -> list[np.ndarray]:
    """For each sample ``{"prompt": [...], "served": [...]}``: the gap, per
    served token, between the reference's best logit at that position and
    the served token's logit there (0 where the served token IS the
    reference's choice).  One sequence at a time, padded to ``pad_to`` (a
    causal model's earlier positions do not see the padding), one layer at
    a time."""
    model, init = cfg["model"], cfg["init"]
    dtype = jnp.dtype(cfg["param_dtype"])
    halves = weights.seed_halves(seed)

    @jax.jit
    def start(halves, tokens):
        top = _f32(weights.top_leaves(weights.base_key_from(halves), model,
                                      init, dtype))
        return embed(model, top, tokens)

    @jax.jit
    def layer(halves, i, x):
        p = _f32(weights.layer_leaves(weights.base_key_from(halves), i,
                                      model, init, dtype))
        return block(model, p, x)

    @jax.jit
    def finish(halves, x, tokens, first, count):
        top = _f32(weights.top_leaves(weights.base_key_from(halves), model,
                                      init, dtype))
        rows = jnp.arange(pad_to)
        logits = head(model, top, x[0])                    # [T, V]
        nxt = jnp.roll(tokens[0], -1)                      # token at t+1
        chosen = jnp.take_along_axis(logits, nxt[:, None], 1)[:, 0]
        gap = jnp.max(logits, -1) - chosen
        mask = (rows >= first) & (rows < first + count)
        return jnp.where(mask, gap, 0.0)

    out = []
    with jax.default_matmul_precision("highest"):
        for s in samples:
            seq = list(s["prompt"]) + list(s["served"])
            P, n = len(s["prompt"]), len(s["served"])
            if len(seq) > pad_to:
                raise ValueError(f"sample of {len(seq)} tokens, pad {pad_to}")
            toks = np.zeros((1, pad_to), np.int32)
            toks[0, :len(seq)] = seq
            toks = jnp.asarray(toks)
            x = start(halves, toks)
            for i in range(model["num_layers"]):
                x = layer(halves, jnp.int32(i), x)
            gaps = finish(halves, x, toks, P - 1, n)
            out.append(np.asarray(gaps)[P - 1:P - 1 + n])
    return out


# ----------------------------------------------------------- training


def _stacked(cfg, halves):
    model, init = cfg["model"], cfg["init"]
    key = weights.base_key_from(halves)
    layers = jax.lax.map(
        lambda i: weights.layer_leaves(key, i, model, init, F32),
        jnp.arange(model["num_layers"]))
    return {"layers": layers,
            "top": weights.top_leaves(key, model, init, F32)}


def _sum_loss(model, params, tokens):
    """Sum over the block's positions of -log p(next token)."""
    x = embed(model, params["top"], tokens)
    step = jax.checkpoint(lambda x, p: (block(model, p, x), None))
    x, _ = jax.lax.scan(step, x, params["layers"])
    logp = jax.nn.log_softmax(head(model, params["top"], x)[:, :-1], -1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    return -jnp.sum(ll)


def _leaf_norms(tree) -> dict:
    """name -> L2 norm, per layer for the stacked leaves."""
    out = {}
    for n, a in tree["top"].items():
        out[n] = jnp.sqrt(jnp.sum(a * a))
    for n, a in tree["layers"].items():
        per = jnp.sqrt(jnp.sum(a * a, axis=tuple(range(1, a.ndim))))
        for i in range(a.shape[0]):
            out[f"layer{i}/{n}"] = per[i]
    return out


def train_steps(cfg: dict, seed: int, batches: list[np.ndarray],
                learning_rate: float, rows_per_block: int = 2) -> dict:
    """Adam (b1 .9, b2 .999, eps 1e-8, no decay) over ``batches`` from the
    seeded weights.  Returns each step's loss, the per-leaf norms of the
    first gradient and the per-leaf norms of the parameters' change."""
    model = cfg["model"]
    halves = weights.seed_halves(seed)
    make = jax.jit(functools.partial(_stacked, cfg))
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(_sum_loss, model)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    @jax.jit
    def adam(params, m, v, g, t):
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: p - learning_rate * (m / c1)
            / (jnp.sqrt(v / c2) + eps), params, m, v)
        return params, m, v

    norms = jax.jit(_leaf_norms)
    delta = jax.jit(lambda a, b: _leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))

    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        params = make(halves)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        for t, batch in enumerate(batches, start=1):
            rows, seq = batch.shape
            count = rows * (seq - 1)
            total, grads = 0.0, None
            for r in range(0, rows, rows_per_block):
                loss, g = grad_fn(params,
                                  jnp.asarray(batch[r:r + rows_per_block]))
                total += float(loss)
                grads = g if grads is None else add(grads, g)
            grads = jax.tree.map(lambda g: g / count, grads)
            losses.append(total / count)
            if first is None:
                first = {k: float(x) for k, x in
                         jax.device_get(norms(grads)).items()}
            params, m, v = adam(params, m, v, grads, F32(t))
        del m, v, grads
        change = {k: float(x) for k, x in jax.device_get(
            delta(params, make(halves))).items()}
    return {"losses": losses, "first_grad_norm": first,
            "param_change_norm": change}
