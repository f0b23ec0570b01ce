"""The leaves of a decoder whose stack of layers is applied
``loop_steps`` times over the SAME weights, as ``models/gpt.py`` lays them
out for ``loop_steps`` > 1 with ``norm_placement="sandwich"`` and
``exit_gate``.  No JAX.

The tree holds every layer ONCE, whatever the loop count: a layer is the
dense decoder's block (``perfbench/layouts/dense_decoder.py``: the fused
qkv projection, the output projection, the gated MLP's three kernels, the
norm on each sublayer's input) and a norm on each sublayer's OUTPUT
(``ln_attn_post``, ``ln_mlp_post``: scales of 1, drawn by ``weights.leaf``'s
rule for ``ln_*``).  Outside the layers: the dense decoder's leaves and the
exit gate, a Dense(1) with a bias over the normed stream (kernel normal /
sqrt(hidden), bias zero under the configuration's ``bias_std`` 0).
"""

from __future__ import annotations

from perfbench.layouts import dense_decoder

BLOCK = dense_decoder.BLOCK
kinds = dense_decoder.kinds


def layer(model: dict, kind: str = BLOCK) -> dict:
    h = model["hidden_size"]
    out = dense_decoder.layer(model, kind)
    out["ln_attn_post/scale"] = out["ln_mlp_post/scale"] = (h,)
    return out


def top(model: dict) -> dict:
    out = dense_decoder.top(model)
    out["exit_gate/kernel"] = (model["hidden_size"], 1)
    out["exit_gate/bias"] = (1,)
    return out
