"""GPT-mini: decoder-only causal language model — the autoregressive
counterpart of the BERT family (not in the reference, which has no attention
at all, ``distributed.py:75-81``; built TPU-first like :mod:`.bert`).

Pre-LayerNorm transformer decoder: bfloat16 activations (MXU-native) with
fp32 LayerNorm/softmax, causal attention through the shared
:mod:`..ops.attention` entry point (xla / pallas flash / ring backends all
support ``causal=True``), Megatron-style tensor-parallel sharding rules over
the ``model`` mesh axis, optional per-layer rematerialization.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import linear_attention as linear_ops
from ..ops import routed_experts as experts_ops
from ..ops.attention import dot_product_attention
from ..ops.pallas import paged_attention as paged_ops
from ..parallel.sharding import ShardingRules
from ..utils import profiling


FULL_ATTENTION = "full_attention"
LINEAR_ATTENTION = "linear_attention"
#: A full layer's parameters under another mask and another cache entry:
#: a token attends its ``GptConfig.sliding_window`` most recent
#: predecessors, and a paged pool holds a ring of that many rows a lane.
SLIDING_ATTENTION = "sliding_attention"
#: What ``GptConfig.kinds`` calls a full-attention layer under
#: ``latent_kv_rank`` (never written in ``layer_kinds``).
LATENT_ATTENTION = "latent_attention"
#: A gated short convolution in place of attention: ``[B | C | X] = a W_in``,
#: ``y = C * conv(B * X)`` over ``GptConfig.short_conv_kernel_dim`` causal
#: depthwise taps, ``x + y W_out``.  A sequence keeps the last ``taps - 1``
#: rows of ``B * X`` and nothing else, whatever its length.
SHORT_CONV = "short_conv"


@dataclasses.dataclass(frozen=True)
class GptConfig:
    vocab_size: int = 256           # byte-level
    hidden_size: int = 128
    num_layers: int = 4
    num_heads: int = 4
    intermediate_size: int = 512
    max_position: int = 512
    dropout_rate: float = 0.0
    dtype: str = "bfloat16"
    attention_backend: str = "xla"
    remat: bool = False
    # Route LayerNorms through the fused pallas kernel (--fused_layer_norm);
    # same math and parameter tree as nn.LayerNorm.
    fused_ln: bool = False
    # Position encoding: "learned" (absolute embedding table, the default),
    # "rope" (rotary: q/k rotated per position in each block; no table) or
    # "none" (no table, no rotation: causality alone orders the tokens).
    pos_encoding: str = "learned"
    # Grouped-query attention: number of K/V heads (0 = num_heads, plain
    # MHA; 1 = MQA).  Query heads share K/V in groups of num_heads/kv_heads,
    # shrinking the decode KV cache — and its HBM reads — by that factor.
    kv_heads: int = 0
    # Sliding-window attention (0 = full causal): each token attends its
    # `attention_window` most recent predecessors only (Mistral-style local
    # attention).  With the pallas backend whole blocks outside the band are
    # skipped — O(S * window) attention compute for long sequences.
    attention_window: int = 0
    # MLP activation: "gelu" (GPT-2 style, the default) or "swiglu"
    # (gated SiLU, the Llama family's block: silu(gate(x)) * up(x) — adds a
    # third MLP matrix; pick intermediate_size accordingly).
    activation: str = "gelu"
    # Normalization: "layernorm" (default) or "rmsnorm" (no mean-centering,
    # no bias — the Llama family's choice; fp32 compute like LN).
    norm: str = "layernorm"
    # Route the MLP matmuls (2/3 of the block's matmul FLOPs) through the
    # MXU's int8 path at TRAIN time: int8 forward + input-gradient
    # matmuls, full-precision weight gradients (SwitchBack recipe, see
    # ops/quant_train.py).  Same parameter tree as the bf16 model —
    # checkpoints are interchangeable.  Inference-side weight-only int8
    # is a separate, orthogonal lever (ops/quant.py / --gen_quantize).
    matmul_int8: bool = False
    # Also route the ATTENTION projections (qkv / q / kv / out — the other
    # 1/3 of the block's matmul FLOPs) through the int8 path.  Plain
    # matmuls with no activation epilogue, so the int8 rate applies
    # cleanly (flax dot_general injection; ops/quant_train.py
    # int8_dot_general).  Same parameter tree.
    attn_int8: bool = False
    # Where a sublayer's norm sits: "pre" (on its input, the default),
    # "post" (on its OUTPUT, before the residual add: x + norm(f(x)), the
    # Olmo family's reordered norm; the residual stream itself is never
    # normed before the final norm) or "sandwich" (one on its input AND one
    # on its output, x + norm(f(norm(x))): four norms a block, the output
    # ones named ``ln_attn_post`` / ``ln_mlp_post``).
    norm_placement: str = "pre"
    # RMSNorm over the whole projected q and over the whole projected k
    # (all heads together) in the softmax-attention layers.
    qk_norm: bool = False
    # RMSNorm over each HEAD's entries of q and of k (one scale vector of
    # ``head_dim`` for q, one for k), before any rotation.
    qk_head_norm: bool = False
    # A head's size where it is not ``hidden_size // num_heads`` (0): the
    # q projection is then ``num_heads * head_size`` wide, whatever the
    # stream's width.
    head_size: int = 0
    # A gate on the softmax-attention layers' output: one more projection
    # of the mixer's input, as wide as q, whose sigmoid multiplies the
    # heads' contexts entry by entry before the out projection.
    attn_output_gate: bool = False
    # The embedding's output times sqrt(hidden_size).
    scale_embedding: bool = False
    # The token mixer of each layer, ``num_layers`` of FULL_ATTENTION /
    # LINEAR_ATTENTION / SLIDING_ATTENTION / SHORT_CONV; empty = every
    # layer full attention (the same parameter tree and the same programs
    # as before the field existed).
    # A linear-attention layer is the gated delta rule of
    # ops/linear_attention.py: per sequence it keeps a fixed-size
    # recurrent state and a convolution tail where a full layer keeps
    # keys and values, and its MLP, norms and residual path are the full
    # layer's.  Only GptLM.__call__, .prefill and .decode_paged carry
    # that state; every other cache path refuses such a config by name.
    layer_kinds: tuple = ()
    # A SLIDING_ATTENTION layer has a full layer's parameters and attends
    # only keys with ``0 <= pos_q - pos_k < sliding_window``.  Where a full
    # layer's paged pool holds a sequence's every row, its pool holds a
    # RING of ``ring_pages(page_size)`` pages a decode slot
    # (:func:`init_kv_pool`): position ``p`` in ring page ``(p //
    # page_size) % ring_pages``.  Only GptLM.__call__, .prefill and
    # .decode_paged know the kind; every other cache path refuses it by
    # name.  (``attention_window`` below is the older, global form: one
    # window for ALL layers on the unpaged paths, and no kind.)
    sliding_window: int = 0
    # The kinds of layer that rotate q and k under pos_encoding="rope";
    # empty = all of them.
    rope_kinds: tuple = ()
    linear_num_heads: int = 0          # key heads = value heads
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4    # taps of the causal depthwise conv
    # Taps of a SHORT_CONV layer's causal depthwise convolution over the
    # stream's ``hidden_size`` channels (no bias): such a layer projects
    # its normed input to three thirds ``[B | C | X]``, runs the taps over
    # ``B * X`` and multiplies by ``C`` before its out projection.  Its
    # cache entry is the last ``short_conv_kernel_dim - 1`` rows of
    # ``B * X`` a sequence, one row a decode slot in a paged pool, no
    # float32 state and no pages.  It composes with full_attention layers
    # (grouped heads, qk_head_norm, rotation) and with routed experts;
    # only GptLM.__call__, .prefill and .decode_paged carry the tail.
    short_conv_kernel_dim: int = 0
    # beta = 2 * sigmoid(.) instead of sigmoid(.): the state transition
    # I - beta k k^T may then have an eigenvalue in (-1, 0).
    linear_allow_neg_eigval: bool = False
    # The rotation's base (pos_encoding="rope", and the latent layers').
    rope_base: float = 10000.0
    # Latent attention (MLA): ``latent_kv_rank`` > 0 makes every layer's
    # token mixer the latent one.  A token's keys and values are then ONE
    # row of ``latent_kv_rank + qk_rope_head_dim`` entries shared by all
    # heads (the normed latent and one rotated key, an array each), which
    # is all a cache holds of it; queries go through a rank of
    # ``latent_q_rank``.  A head scores over ``qk_nope_head_dim`` entries
    # expanded from the latent plus the ``qk_rope_head_dim`` rotated ones,
    # and reads values of ``v_head_dim``.  EXPANDED where the whole sequence
    # is at hand (``__call__``, ``prefill``: per-head keys and values
    # through the attention backend), ABSORBED in ``decode_paged`` (the
    # expansion folded into the query and the output, scores over the
    # cached rows themselves).  Only those three paths carry the row; every
    # other cache path refuses such a config by name, as they do a sparse
    # MLP's.
    latent_kv_rank: int = 0
    latent_q_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Sparse MLP (ops/routed_experts.py): ``num_experts`` > 0 gives every
    # layer after the first ``first_dense_layers`` (which keep the dense
    # MLP at ``intermediate_size``) ``num_experts`` routed gated experts
    # of ``expert_intermediate_size``, ``experts_per_token`` of them a
    # token by sigmoid scores (renormalised, times
    # ``routed_scaling_factor``; no token is dropped), beside
    # ``num_shared_experts`` that every token passes.
    num_experts: int = 0
    experts_per_token: int = 0
    expert_intermediate_size: int = 0
    num_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_dense_layers: int = 0
    # What a sparse layer's router reads: "mlp_in" (the MLP's own normed
    # input, the stream AFTER the token mixer) or "mixer_in" (the token
    # mixer's normed input, the block's INPUT: the choice of experts then
    # owes nothing to the mixer, two streams reach the sparse MLP, and the
    # block lays the route down BEFORE the mixer runs,
    # ``GptBlock._route_ahead``).
    router_input: str = "mlp_in"
    # How the router scores: "sigmoid" (above) or "softmax" (the
    # ``experts_per_token`` largest LOGITS, weighted by a softmax over
    # those alone; no selection bias, so no ``router_bias`` leaf, and
    # ``routed_scaling_factor`` must be 1).
    router_score: str = "sigmoid"
    # The routed experts' activation on the gate branch: "silu" or "relu"
    # (``(relu(x Wg) * (x Wu)) Wd``); the shared experts' is SiLU.
    expert_activation: str = "silu"
    # A weight-shared loop: the stack of ``num_layers`` blocks is applied
    # ``loop_steps`` times over the SAME weights, the final norm after
    # every application (the normed stream is what the next one starts
    # from, and what the head reads after the last).  Each application
    # attends its OWN keys and values, so a cached token holds
    # ``loop_steps * num_layers`` rows: a layer's cache entry gains a
    # leading axis of ``loop_steps`` (:func:`init_kv_cache`) and its pool
    # holds ``loop_steps`` runs of pages (:func:`init_kv_pool`).  Only
    # GptLM.__call__, .prefill and .decode_paged walk the loop; every
    # other cache path refuses such a config by name.
    loop_steps: int = 1
    # With ``loop_steps`` > 1: a Dense(1) over the normed stream after
    # every application, lambda_t = sigmoid(.), from which the mass that
    # leaves at each step is sown (``loop/exit_mass``, :func:`exit_masses`).
    # Nothing here acts on it: every token runs every step.
    exit_gate: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def window_layers(self) -> int:
        return self.layer_kinds.count(SLIDING_ATTENTION)

    def ring_pages(self, page_size: int) -> int:
        """Pages of ``page_size`` rows a decode slot holds of a sliding
        layer's pool: the window and a page more, so that the rows a
        prompt's padding (under a page of it) writes fall on positions the
        window has left."""
        return -(-self.sliding_window // page_size) + 1

    @property
    def num_kv_heads(self) -> int:
        return self.kv_heads or self.num_heads

    @property
    def kinds(self) -> tuple:
        """The kind of each layer, ``num_layers`` long."""
        if self.latent_kv_rank:
            return (LATENT_ATTENTION,) * self.num_layers
        return self.layer_kinds or (FULL_ATTENTION,) * self.num_layers

    @property
    def sparse_layers(self) -> tuple:
        """Whether each layer's MLP is the routed experts', ``num_layers``
        long."""
        return tuple(bool(self.num_experts) and i >= self.first_dense_layers
                     for i in range(self.num_layers))

    @property
    def latent_row_dim(self) -> int:
        """Entries of the one row a token keeps a latent layer."""
        return self.latent_kv_rank + self.qk_rope_head_dim

    @property
    def has_state_layers(self) -> bool:
        """Whether a layer keeps a fixed-size row a sequence beside the
        pages: a recurrent state with its convolution tail, or a short
        convolution's tail alone."""
        return any(kind in STATE_KINDS for kind in self.layer_kinds)

    @property
    def conv_layers(self) -> int:
        return self.layer_kinds.count(SHORT_CONV)

    @property
    def linear_conv_channels(self) -> int:
        """Channels the convolution runs over: q, k and v side by side."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)

    def refuse_state_layers(self, path: str) -> None:
        """Called first by every cache path that holds per-head K and V
        rows around a dense MLP and walks the stack once: no place for a
        linear-attention layer's recurrent state, for a short
        convolution's tail, for a sliding layer's ring beside the full
        layers' rows, for a latent row, for a routed-expert MLP's
        histogram and idle lanes, nor for the rows of a weight-shared
        loop's further steps."""
        if self.window_layers:
            raise ValueError(
                f"{path} holds one kind of cache entry for every layer and "
                f"GptConfig.layer_kinds has {self.window_layers} "
                "sliding_attention layer(s), whose entry is a ring; the "
                "paths that hold both are GptLM.__call__, GptLM.prefill and "
                "GptLM.decode_paged")
        for kind, what in ((LINEAR_ATTENTION, "recurrent state"),
                           (SHORT_CONV, "convolution tail")):
            if kind in self.layer_kinds:
                raise ValueError(
                    f"{path} does not carry a {kind} layer's {what} (a row "
                    f"a sequence, no pages) and GptConfig.layer_kinds has "
                    f"{self.layer_kinds.count(kind)} such layer(s); the "
                    "paths that do are GptLM.__call__, GptLM.prefill and "
                    "GptLM.decode_paged (the serving engine's whole-bucket "
                    "prefill and its decode step), where it lies beside "
                    "full_attention layers' pages")
        for field, what in (("latent_kv_rank", "a latent-attention row"),
                            ("num_experts", "a routed-expert MLP")):
            if getattr(self, field):
                raise ValueError(
                    f"{path} does not carry {what} and GptConfig.{field} is "
                    f"{getattr(self, field)}; the paths that do are "
                    "GptLM.__call__, GptLM.prefill and GptLM.decode_paged")
        if self.loop_steps > 1:
            raise ValueError(
                f"{path} does not walk a weight-shared loop (a cache row a "
                f"loop step a layer) and GptConfig.loop_steps is "
                f"{self.loop_steps}; the paths that do are GptLM.__call__, "
                "GptLM.prefill and GptLM.decode_paged")

    def __post_init__(self):
        if self.pos_encoding not in ("learned", "rope", "none"):
            raise ValueError(f"Unknown pos_encoding {self.pos_encoding!r}; "
                             "one of ('learned', 'rope', 'none')")
        if self.norm_placement not in ("pre", "post", "sandwich"):
            raise ValueError(f"Unknown norm_placement "
                             f"{self.norm_placement!r}; one of "
                             "('pre', 'post', 'sandwich')")
        if self.loop_steps < 1 or (self.exit_gate and self.loop_steps < 2):
            raise ValueError(
                f"loop_steps must be >= 1 (got {self.loop_steps}), and "
                "exit_gate needs a loop to leave: loop_steps >= 2")
        if self.loop_steps > 1 and (self.layer_kinds or self.latent_kv_rank
                                    or self.num_experts
                                    or self.attention_window):
            raise ValueError(
                "loop_steps > 1 walks full-attention layers around dense "
                "MLPs: it composes with none of layer_kinds (a "
                "linear_attention, a sliding_attention or a short_conv "
                "layer among them), latent_kv_rank, num_experts and "
                "attention_window")
        if self.layer_kinds:
            known = (FULL_ATTENTION, LINEAR_ATTENTION, SLIDING_ATTENTION,
                     SHORT_CONV)
            bad = sorted(set(self.layer_kinds) - set(known))
            if bad or len(self.layer_kinds) != self.num_layers:
                raise ValueError(
                    f"layer_kinds must name one of {known} for each of "
                    f"num_layers={self.num_layers} layers, got "
                    f"{len(self.layer_kinds)} entries"
                    + (f" with {bad}" if bad else ""))
        if bool(self.window_layers) != bool(self.sliding_window) \
                or self.sliding_window < 0:
            raise ValueError(
                "sliding_window >= 1 is the window of the sliding_attention "
                "layers in layer_kinds: one needs the other (got "
                f"sliding_window={self.sliding_window} and "
                f"{self.window_layers} such layer(s))")
        if self.window_layers and (self.has_state_layers
                                   or self.attention_window):
            raise ValueError(
                "a sliding_attention layer composes with full_attention "
                "layers, grouped-query heads and routed experts; not with a "
                "linear_attention or a short_conv layer (no path carries a "
                "ring beside a state row) nor with attention_window (the "
                "one window of ALL layers on the unpaged paths)")
        if set(self.rope_kinds) - set(self.kinds) \
                or (self.rope_kinds and self.pos_encoding != "rope"):
            raise ValueError(
                f"rope_kinds {self.rope_kinds} names the kinds of layer "
                "that rotate under pos_encoding='rope': each must be a kind "
                f"this config has, {sorted(set(self.kinds))}")
        if self.head_size < 0 or self.head_dim < 1:
            raise ValueError(f"head_size must be >= 0, got {self.head_size}")
        if LINEAR_ATTENTION in self.layer_kinds:
            if min(self.linear_num_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim) < 1 \
                    or self.linear_conv_kernel_dim < 2:
                raise ValueError(
                    "a linear_attention layer needs linear_num_heads, "
                    "linear_key_head_dim, linear_value_head_dim >= 1 and "
                    "linear_conv_kernel_dim >= 2")
        if bool(self.conv_layers) != bool(self.short_conv_kernel_dim) \
                or self.short_conv_kernel_dim == 1 \
                or self.short_conv_kernel_dim < 0:
            raise ValueError(
                "short_conv_kernel_dim >= 2 is the taps of the short_conv "
                "layers in layer_kinds: one needs the other (got "
                f"short_conv_kernel_dim={self.short_conv_kernel_dim} and "
                f"{self.conv_layers} such layer(s))")
        if self.has_state_layers and (self.attention_window
                                      or self.attn_int8):
            raise ValueError(
                "layer_kinds with a linear_attention or a short_conv layer "
                "composes with full_attention layers (grouped-query heads, "
                "qk_head_norm, rotation; a short_conv layer with routed "
                "experts too): neither with attention_window nor with "
                "attn_int8")
        if self.latent_kv_rank:
            if min(self.latent_q_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) < 1 \
                    or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent_kv_rank needs latent_q_rank, qk_nope_head_dim, "
                    "v_head_dim >= 1 and an even qk_rope_head_dim >= 2")
            if self.qk_nope_head_dim + self.qk_rope_head_dim \
                    != self.v_head_dim:
                raise ValueError(
                    "the attention backends take one head size: "
                    "qk_nope_head_dim + qk_rope_head_dim must equal "
                    f"v_head_dim, got {self.qk_nope_head_dim} + "
                    f"{self.qk_rope_head_dim} and {self.v_head_dim}")
            if self.layer_kinds or self.kv_heads or self.attention_window \
                    or self.attn_int8 or self.qk_norm or self.qk_head_norm \
                    or self.head_size or self.attn_output_gate \
                    or self.pos_encoding != "none":
                raise ValueError(
                    "latent_kv_rank makes every layer a latent one, with "
                    "head sizes and norms of its own: it composes with none "
                    "of layer_kinds (so with no sliding_attention and no "
                    "short_conv layer), "
                    "kv_heads, attention_window, attn_int8, qk_norm, "
                    "qk_head_norm, head_size and attn_output_gate, and "
                    "rotates inside the mixer: pos_encoding must be 'none'")
        if self.num_experts:
            if not 1 <= self.experts_per_token <= self.num_experts \
                    or self.expert_intermediate_size < 1 \
                    or self.num_shared_experts < 0 \
                    or not 0 <= self.first_dense_layers <= self.num_layers:
                raise ValueError(
                    "num_experts needs 1 <= experts_per_token <= "
                    "num_experts, expert_intermediate_size >= 1, "
                    "num_shared_experts >= 0 and 0 <= first_dense_layers "
                    "<= num_layers")
            if self.router_input not in ("mlp_in", "mixer_in") \
                    or self.router_score not in experts_ops.SCORES \
                    or self.expert_activation not in experts_ops.ACTIVATIONS:
                raise ValueError(
                    "num_experts: router_input is one of ('mlp_in', "
                    f"'mixer_in'), router_score of {experts_ops.SCORES}, "
                    f"expert_activation of {tuple(experts_ops.ACTIVATIONS)}"
                    f"; got {self.router_input!r}, {self.router_score!r}, "
                    f"{self.expert_activation!r}")
            if self.router_score == "softmax" \
                    and self.routed_scaling_factor != 1.0:
                raise ValueError(
                    "router_score='softmax' weighs the chosen experts by a "
                    "softmax over their logits alone: "
                    "routed_scaling_factor is the sigmoid score's and must "
                    f"be 1.0, got {self.routed_scaling_factor}")
            if self.expert_activation != "silu" and self.num_shared_experts:
                raise ValueError(
                    "expert_activation is the ROUTED experts'; a shared "
                    "expert's gate is SiLU and no configuration has both: "
                    f"got {self.expert_activation!r} beside "
                    f"{self.num_shared_experts} shared expert(s)")
            if self.activation != "swiglu" or self.matmul_int8 \
                    or self.norm_placement == "post":
                raise ValueError(
                    "num_experts: the experts are gated SiLU MLPs behind a "
                    "norm on their input (activation='swiglu', "
                    "norm_placement 'pre' or 'sandwich'), and matmul_int8 "
                    "has no grouped form")
        elif (self.router_input, self.router_score,
              self.expert_activation) != ("mlp_in", "sigmoid", "silu"):
            raise ValueError(
                "router_input, router_score and expert_activation are the "
                "routed experts': num_experts is 0")
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(f"Unknown activation {self.activation!r}; "
                             "one of ('gelu', 'swiglu')")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"Unknown norm {self.norm!r}; "
                             "one of ('layernorm', 'rmsnorm')")
        if self.norm == "rmsnorm" and self.fused_ln:
            raise ValueError("fused_ln is the pallas LayerNorm kernel; "
                             "it does not apply to norm='rmsnorm'")
        if self.kv_heads < 0 or (self.kv_heads
                                 and self.num_heads % self.kv_heads):
            raise ValueError(
                f"num_heads={self.num_heads} must be divisible by "
                f"kv_heads={self.kv_heads} (and kv_heads must be >= 0)")


def mini() -> GptConfig:
    return GptConfig()


def infer_arch_from_layer0(layer0: dict) -> dict:
    """Architecture knobs a checkpoint's first decoder block reveals —
    ONE definition shared by generate and export (they must reconstruct the
    same model from the same tree): swiglu adds a gate matrix, rmsnorm's
    norm params carry no bias, GQA's kv projection is [in, 2, G, D].
    A block tells nothing of its neighbours' kinds, so a checkpoint with a
    linear-attention or a short-convolution layer is refused."""
    if "conv_taps" in layer0:
        raise ValueError(
            "infer_arch_from_layer0 cannot infer GptConfig.layer_kinds: "
            "layer0 is a linear_attention or a short_conv layer and says "
            "nothing of the other layers' kinds; build the GptConfig from "
            "the run's configuration file")
    if "kv_a" in layer0 or "router" in layer0:
        raise ValueError(
            "infer_arch_from_layer0 cannot infer a latent-attention or "
            "routed-expert GptConfig (ranks, head sizes, experts a token, "
            "the leading dense layers); build the GptConfig from the run's "
            "configuration file")
    arch = {
        "activation": "swiglu" if "mlp_gate" in layer0 else "gelu",
        "norm": ("layernorm" if "bias" in layer0.get("ln_attn", {})
                 else "rmsnorm"),
    }
    if "kv_proj" in layer0:
        arch["kv_heads"] = int(layer0["kv_proj"]["kernel"].shape[-2])
    if "ln_attn_post" in layer0:
        arch["norm_placement"] = "sandwich"
    return arch


class RMSNorm(nn.Module):
    """Root-mean-square norm (no mean-centering, no bias): fp32 compute like
    the LayerNorm path; parameter tree is ``{scale}`` only — generate/export
    infer ``norm='rmsnorm'`` from the missing bias."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                       + self.epsilon)
        return ((x32 / rms) * scale).astype(x.dtype)


def _inv_softplus(y: jax.Array) -> jax.Array:
    return y + jnp.log(-jnp.expm1(-y))


def _layer_norm(cfg: GptConfig, name: str | None = None) -> nn.Module:
    if cfg.norm == "rmsnorm":
        return RMSNorm(name=name)
    from ..ops.pallas.layer_norm import make_layer_norm
    return make_layer_norm(cfg.fused_ln, name=name)


def apply_rope(x: jax.Array, positions: jax.Array,
               base: float = 10000.0) -> jax.Array:
    """Rotary position embedding on [B, S, H, D] (D even): rotate each
    (x[..2i], x[..2i + D/2]) pair by position * base^(-2i/D).  The q·k dot
    then depends only on RELATIVE position.  ``positions``: [S] or [B, S]."""
    D = x.shape[-1]
    if D % 2:
        raise ValueError(f"rope needs an even head_dim, got {D}")
    half = D // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,S,half]
    sin = jnp.sin(angles)[:, :, None, :]                          # [B,S,1,half]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin,
                               x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


class GptBlock(nn.Module):
    """One pre-LN decoder block; ``setup``-style so the training ``__call__``
    and the KV-cached ``decode_step`` share the same parameters.

    ``kind`` selects the token mixer, and its row of :data:`KINDS` names
    the mixer's three forms here: softmax attention over cached keys and
    values (FULL_ATTENTION; SLIDING_ATTENTION the same parameters under a
    banded mask, its paged pool a ring), the gated delta rule over a
    recurrent state (LINEAR_ATTENTION), softmax attention over one cached
    latent row a token (LATENT_ATTENTION) or a gated short convolution
    over a tail of its inputs (SHORT_CONV).  ``sparse`` selects the MLP:
    the dense one, or routed experts beside shared ones.  Norms and the
    residual path are shared.  The forms a row of :data:`KINDS` names end
    at the mixer's residual add; the MLP follows in ``__call__`` and in
    the two functions that walk the layers' prefill and step forms."""

    cfg: GptConfig
    kind: str = FULL_ATTENTION
    sparse: bool = False

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        self.ln_attn = _layer_norm(cfg)
        self.ln_mlp = _layer_norm(cfg)
        if cfg.norm_placement == "sandwich":
            self.ln_attn_post = _layer_norm(cfg)
            self.ln_mlp_post = _layer_norm(cfg)
        if self.sparse:
            self._setup_experts(dtype)
        else:
            self._setup_mlp(dtype)
        self.drop = nn.Dropout(cfg.dropout_rate)
        getattr(self, KINDS[self.kind].setup)(dtype)

    def _setup_conv(self, dtype):
        cfg = self.cfg
        # The three thirds [B | C | X] of the mixer's input, side by side.
        self.in_proj = nn.Dense(3 * cfg.hidden_size, dtype=dtype,
                                use_bias=False)
        # Depthwise taps over the stream's channels, oldest first; no bias.
        self.conv_taps = self.param(
            "conv_taps", nn.initializers.normal(
                cfg.short_conv_kernel_dim ** -0.5),
            (cfg.short_conv_kernel_dim, cfg.hidden_size))
        self.out = nn.Dense(cfg.hidden_size, dtype=dtype, use_bias=False)

    def _setup_latent(self, dtype):
        cfg = self.cfg
        flat = {"dtype": dtype, "use_bias": False}
        H = cfg.num_heads
        self.q_a = nn.Dense(cfg.latent_q_rank, **flat)
        self.q_a_norm = RMSNorm()
        self.q_b = nn.DenseGeneral(
            (H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), **flat)
        # The latent and the one rotary key of a token, side by side.
        self.kv_a = nn.Dense(cfg.latent_row_dim, **flat)
        self.kv_a_norm = RMSNorm()
        # Latent -> a head's un-rotated key part and its value, side by
        # side: [latent_kv_rank, H, qk_nope_head_dim + v_head_dim].
        self.kv_b = nn.DenseGeneral(
            (H, cfg.qk_nope_head_dim + cfg.v_head_dim), **flat)
        self.out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), **flat)

    def _setup_experts(self, dtype):
        cfg = self.cfg
        E, I = cfg.num_experts, cfg.expert_intermediate_size
        # Applied in float32 whatever type the kernel is stored in: the
        # fourth and fifth score of a token are often a rounding apart.
        self.router = nn.Dense(E, dtype=jnp.float32, use_bias=False)
        if cfg.router_score == "sigmoid":
            # Steers which experts are chosen, never their weights.
            self.router_bias = self.param("router_bias",
                                          nn.initializers.zeros, (E,))
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        self.experts_gate = self.param("experts_gate", init,
                                       (E, cfg.hidden_size, I))
        self.experts_up = self.param("experts_up", init,
                                     (E, cfg.hidden_size, I))
        self.experts_down = self.param("experts_down", init,
                                       (E, I, cfg.hidden_size))
        if cfg.num_shared_experts:
            shared = {"dtype": dtype, "use_bias": False}
            self.shared_in = nn.Dense(I * cfg.num_shared_experts, **shared)
            self.shared_gate = nn.Dense(I * cfg.num_shared_experts, **shared)
            self.shared_out = nn.Dense(cfg.hidden_size, **shared)

    def _setup_linear(self, dtype):
        cfg = self.cfg
        H = cfg.linear_num_heads
        Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        flat = {"dtype": dtype, "use_bias": False}
        self.q_proj = nn.Dense(H * Dk, **flat)
        self.k_proj = nn.Dense(H * Dk, **flat)
        self.v_proj = nn.Dense(H * Dv, **flat)
        self.g_proj = nn.Dense(H * Dv, **flat)      # the output gate
        self.a_proj = nn.Dense(H, **flat)           # decay, a head
        self.b_proj = nn.Dense(H, **flat)           # step beta, a head
        # Depthwise taps over q, k and v side by side, oldest first.
        self.conv_taps = self.param(
            "conv_taps", nn.initializers.normal(
                cfg.linear_conv_kernel_dim ** -0.5),
            (cfg.linear_conv_kernel_dim, cfg.linear_conv_channels))
        # Mamba-2's draw: A in [1, 16), a step in [1e-3, 1e-1).
        self.A_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, minval=1.0, maxval=16.0)),
            (H,))
        self.dt_bias = self.param(
            "dt_bias", lambda key, shape: _inv_softplus(jnp.exp(
                jax.random.uniform(key, shape, minval=jnp.log(1e-3),
                                   maxval=jnp.log(1e-1)))), (H,))
        self.o_norm = RMSNorm()
        self.out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), **flat)

    def _setup_attention(self, dtype):
        cfg = self.cfg
        # attn_int8: same modules, same tree — only the contraction is
        # routed through the int8 matmul (flax's dot_general injection).
        proj_kw = {"dtype": dtype}
        if cfg.attn_int8:
            from ..ops.quant_train import int8_dot_general
            proj_kw["dot_general"] = int8_dot_general
        if cfg.num_kv_heads == cfg.num_heads:
            # Plain MHA: one fused projection (the historical param tree —
            # existing checkpoints keep loading).
            self.qkv = nn.DenseGeneral((3, cfg.num_heads, cfg.head_dim),
                                       **proj_kw)
        else:
            # GQA/MQA: queries keep all heads; K/V carry only kv_heads.
            self.q_proj = nn.DenseGeneral((cfg.num_heads, cfg.head_dim),
                                          **proj_kw)
            self.kv_proj = nn.DenseGeneral((2, cfg.num_kv_heads,
                                            cfg.head_dim), **proj_kw)
        self.out = nn.DenseGeneral(cfg.hidden_size, axis=(-2, -1), **proj_kw)
        if cfg.attn_output_gate:
            self.gate_proj = nn.DenseGeneral((cfg.num_heads, cfg.head_dim),
                                             use_bias=False, **proj_kw)
        if cfg.qk_norm or cfg.qk_head_norm:
            self.q_norm = RMSNorm()
            self.k_norm = RMSNorm()

    def _setup_mlp(self, dtype):
        cfg = self.cfg
        if cfg.matmul_int8:
            from ..ops.quant_train import Int8Dense
            dense_cls = Int8Dense
        else:
            dense_cls = nn.Dense
        if cfg.activation == "swiglu":
            # Llama convention: the whole gated MLP (gate/up/down) is
            # bias-free.  The swiglu tree is new anyway (mlp_gate never
            # existed before), so there is no compatibility reason to keep
            # the gelu path's biases.
            self.mlp_in = dense_cls(cfg.intermediate_size, dtype=dtype,
                                    use_bias=False)
            self.mlp_gate = dense_cls(cfg.intermediate_size, dtype=dtype,
                                      use_bias=False)
            self.mlp_out = dense_cls(cfg.hidden_size, dtype=dtype,
                                     use_bias=False)
        else:
            self.mlp_in = dense_cls(cfg.intermediate_size, dtype=dtype)
            self.mlp_out = dense_cls(cfg.hidden_size, dtype=dtype)

    def _mixer_in(self, x: jax.Array) -> jax.Array:
        """What the token mixer's projections read: the normed stream, or
        under ``norm_placement="post"`` the stream itself."""
        if self.cfg.norm_placement == "post":
            return x
        return self.ln_attn(x).astype(jnp.dtype(self.cfg.dtype))

    @property
    def window(self) -> int:
        """This layer's attention window, 0 for none: its kind's, or the
        one ``attention_window`` gives all layers."""
        if self.kind == SLIDING_ATTENTION:
            return self.cfg.sliding_window
        return self.cfg.attention_window

    def _add_mixed(self, x: jax.Array, ctx: jax.Array,
                   deterministic: bool = True) -> jax.Array:
        """The token mixer's ``ctx`` through its out projection, and the
        residual add.  Under ``attn_output_gate`` a softmax-attention
        layer's ``ctx`` [B, T, H, D] is first multiplied by the sigmoid of
        the gate's projection of the mixer's input."""
        if self.cfg.attn_output_gate and self.kind in (FULL_ATTENTION,
                                                       SLIDING_ATTENTION):
            with profiling.region("attn.gate"):
                gate = nn.sigmoid(
                    self.gate_proj(self._mixer_in(x)).astype(jnp.float32))
                ctx = (ctx * gate).astype(ctx.dtype)
        with profiling.region("attn.out"):
            y = self.out(ctx)
            if self.cfg.norm_placement == "post":
                y = self.ln_attn(y).astype(x.dtype)
            elif self.cfg.norm_placement == "sandwich":
                y = self.ln_attn_post(y).astype(x.dtype)
            return x + self.drop(y, deterministic=deterministic)

    def _qkv(self, x: jax.Array, positions: jax.Array | None = None):
        """Returns q [B,S,H,D] and k/v [B,S,G,D] (G = kv heads; G == H in
        plain MHA)."""
        with profiling.region("attn.qkv"):
            cfg = self.cfg
            h = self._mixer_in(x)
            if cfg.num_kv_heads == cfg.num_heads:
                qkv = self.qkv(h)
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            else:
                q = self.q_proj(h)
                kv = self.kv_proj(h)
                k, v = kv[:, :, 0], kv[:, :, 1]
            if cfg.qk_norm:
                q = self.q_norm(q.reshape(*q.shape[:2], -1)).reshape(q.shape)
                k = self.k_norm(k.reshape(*k.shape[:2], -1)).reshape(k.shape)
            if cfg.qk_head_norm:
                q, k = self.q_norm(q), self.k_norm(k)
            if cfg.pos_encoding == "rope" and (
                    not cfg.rope_kinds or self.kind in cfg.rope_kinds):
                if positions is None:
                    positions = jnp.arange(x.shape[1])
                q = apply_rope(q, positions, cfg.rope_base)
                k = apply_rope(k, positions, cfg.rope_base)
            return q, k, v

    def _expand_kv(self, kv: jax.Array) -> jax.Array:
        """Broadcast G kv heads up to the H query heads (on-chip repeat —
        the cache/projection stays at G heads, so HBM sees only G)."""
        groups = self.cfg.num_heads // self.cfg.num_kv_heads
        if groups == 1:
            return kv
        return jnp.repeat(kv, groups, axis=2)

    def _route(self, flat: jax.Array):
        """``flat`` [T, hidden], what the router reads -> (chosen experts
        [T, k], their weights [T, k]) by the configuration's score."""
        cfg = self.cfg
        sigmoid = cfg.router_score == "sigmoid"
        return experts_ops.route(
            self.router(flat), self.router_bias if sigmoid else None,
            cfg.experts_per_token, cfg.routed_scaling_factor,
            cfg.router_score)

    def _route_ahead(self, x: jax.Array):
        """Under ``router_input="mixer_in"``, of the block's INPUT ``x``:
        (the chosen experts [T, k], their weights [T, k]), all a sparse
        MLP takes from its router, laid down before the token mixer has
        run: nothing of it waits for the mixer.  None for any other
        block."""
        cfg = self.cfg
        if not self.sparse or cfg.router_input != "mixer_in":
            return None
        with profiling.region("moe.route"):
            return self._route(
                self._mixer_in(x).reshape(-1, cfg.hidden_size))

    def _experts(self, x: jax.Array, deterministic: bool,
                 live: jax.Array | None = None, routed=None) -> jax.Array:
        """The sparse MLP: every token through its ``experts_per_token``
        routed experts and through the shared ones.  ``live`` [B] (the
        decode step's): a row that is no sequence is routed nowhere.  With
        ``routed`` (:meth:`_route_ahead`'s, of the block's input) the
        route is given and the router reads nothing of this stream.  How
        many (token, expert) pairs each expert got is sown as
        ``routing/counts`` [E] for whoever applies the model with that
        collection mutable (the serving engine's step)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        h = self.ln_mlp(x).astype(dtype)
        flat = h.reshape(-1, cfg.hidden_size)
        if routed is None:
            with profiling.region("moe.route"):
                routed = self._route(flat)
        chosen, weights = routed
        with profiling.region("moe.experts"):
            rows = None if live is None else jnp.repeat(
                live, flat.shape[0] // live.shape[0])
            y, counts = experts_ops.routed_experts(
                flat, chosen, weights, self.experts_gate.astype(dtype),
                self.experts_up.astype(dtype),
                self.experts_down.astype(dtype), rows,
                cfg.expert_activation)
        self.sow("routing", "counts", counts)
        y = y.reshape(x.shape)
        if cfg.num_shared_experts:
            with profiling.region("moe.shared"):
                y = y + self.shared_out(
                    nn.silu(self.shared_gate(h)) * self.shared_in(h))
        if cfg.norm_placement == "sandwich":
            y = self.ln_mlp_post(y).astype(x.dtype)
        return x + self.drop(y, deterministic=deterministic)

    def _mlp(self, x: jax.Array, deterministic: bool,
             live: jax.Array | None = None, routed=None) -> jax.Array:
        with profiling.region("mlp"):
            if self.sparse:
                return self._experts(x, deterministic, live, routed)
            cfg = self.cfg
            post = cfg.norm_placement == "post"
            h = x if post else self.ln_mlp(x).astype(jnp.dtype(cfg.dtype))
            if cfg.matmul_int8 and cfg.activation == "gelu" and not post:
                from ..ops import quant_train
                M = 1
                for d in h.shape[:-1]:
                    M *= d
                if quant_train.use_fused_mlp(M, cfg.hidden_size,
                                             cfg.intermediate_size):
                    # Whole-MLP fused path: both layers' params come from the
                    # SAME submodules (identical checkpoint tree), computation
                    # runs through the pallas kernels with bias/gelu fused
                    # (see ops/quant_train.int8_gelu_mlp).
                    w_in, b_in = self.mlp_in(h, return_params=True)
                    w_out, b_out = self.mlp_out(
                        jnp.zeros((0, cfg.intermediate_size), h.dtype),
                        return_params=True)
                    # The residual add stays OUTSIDE the kernels by default:
                    # folding it into the second kernel's epilogue measured
                    # 7 ms/step slower (the extra input block degrades
                    # pipelining more than the saved XLA add pass).  The
                    # fused form stays wired behind FUSED_MLP_RESIDUAL so
                    # the trade re-measures in one line — dropout must be a
                    # no-op for it (the fused add cannot see the mask).
                    h2 = h.reshape(M, cfg.hidden_size)
                    if (quant_train.FUSED_MLP_RESIDUAL
                            and (deterministic or cfg.dropout_rate == 0.0)):
                        y = quant_train.int8_gelu_mlp_res(
                            h2, w_in, b_in, w_out, b_out,
                            x.reshape(M, cfg.hidden_size))
                        return y.reshape(x.shape)
                    y = quant_train.int8_gelu_mlp(h2, w_in, b_in, w_out,
                                                  b_out)
                    return x + self.drop(y.reshape(x.shape),
                                         deterministic=deterministic)
            if cfg.activation == "swiglu":
                h = nn.silu(self.mlp_gate(h)) * self.mlp_in(h)
            else:
                h = nn.gelu(self.mlp_in(h))
            h = self.mlp_out(h)
            if post:
                h = self.ln_mlp(h).astype(x.dtype)
            elif cfg.norm_placement == "sandwich":
                h = self.ln_mlp_post(h).astype(x.dtype)
            return x + self.drop(h, deterministic=deterministic)

    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        routed = self._route_ahead(x)
        x = getattr(self, KINDS[self.kind].mix)(x, deterministic)
        return self._mlp(x, deterministic, routed=routed)

    def attention_mix(self, x: jax.Array, deterministic: bool = True):
        """The whole sequence, nothing cached (the training forward)."""
        q, k, v = self._qkv(x)
        with profiling.region("attn.scores"):
            ctx = dot_product_attention(
                q, self._expand_kv(k), self._expand_kv(v), causal=True,
                window=self.window, backend=self.cfg.attention_backend)
        return self._add_mixed(x, ctx, deterministic)

    # ---------------------------------------------- short convolution

    def _conv_gated(self, x: jax.Array, tail: jax.Array | None):
        """The gated convolution of ``x`` [B, T, hidden] after ``tail``
        [B, K-1, hidden] (zeros when None): returns (``u = B * X``
        [B, T, hidden], what the taps read and a tail keeps, and ``y = C *
        conv(u)``), both in the compute type.  The product, the taps and
        the gate are float32 from the projections' type; ``u`` is rounded
        to the compute type BEFORE the taps read it, so that a row read
        back from a tail is the row the whole-sequence form read."""
        with profiling.region("attn.qkv"):
            gate_in, gate_out, value = jnp.split(
                self.in_proj(self._mixer_in(x)), 3, axis=-1)
        dtype = jnp.dtype(self.cfg.dtype)
        u = (gate_in.astype(jnp.float32)
             * value.astype(jnp.float32)).astype(dtype)
        y = gate_out.astype(jnp.float32) * linear_ops.causal_conv(
            u, self.conv_taps, tail)
        return u, y.astype(dtype)

    def conv_mix(self, x: jax.Array, deterministic: bool = True):
        """The whole sequence from an empty tail (the training forward)."""
        with profiling.region("short_conv.mix"):
            _, y = self._conv_gated(x, None)
        return self._add_mixed(x, y, deterministic)

    def conv_prefill(self, x: jax.Array, tail: jax.Array,
                     lengths: jax.Array):
        """``x`` [B, T, hidden] through the block after ``tail`` (an empty
        sequence's: zeros): returns (y, the ``K - 1`` rows of ``B * X``
        before position ``lengths[b]``; zeros stand before position 0).
        The convolution is causal, so padding past ``lengths[b]`` reaches
        no real position; ``y`` at or past it is meaningless."""
        with profiling.region("short_conv.mix"):
            u, y = self._conv_gated(x, tail)
        with profiling.region("cache.write"):
            new_tail = linear_ops.conv_tail(
                jnp.concatenate([tail, u], axis=1),
                lengths + tail.shape[1], tail.shape[1])
        return self._add_mixed(x, y), new_tail

    def conv_decode_step(self, x: jax.Array, tail: jax.Array,
                         live: jax.Array):
        """One token a row: ``x`` [B, 1, hidden] against ``tail``
        [B, K-1, hidden].  A row where ``live`` [B] is False keeps its
        tail bit for bit."""
        with profiling.region("short_conv.step"):
            u, y = self._conv_gated(x, tail)
        with profiling.region("cache.write"):
            shifted = jnp.concatenate([tail[:, 1:], u], axis=1)
            tail = jnp.where(live[:, None, None], shifted, tail)
        return self._add_mixed(x, y), tail

    # ---------------------------------------------- linear attention

    def _linear_inputs(self, x: jax.Array, tail: jax.Array | None,
                       keep: jax.Array | None):
        """The gated delta rule's inputs for ``x`` [B, T, hidden]: the raw
        q/k/v projections side by side (what the convolution reads, and
        what its tail keeps), then q [B,T,H,Dk] (unit, scaled by
        Dk^-1/2), k (unit), v [B,T,H,Dv], the log decay ``g`` and the step
        ``beta`` [B,T,H], all float32.  Where ``keep`` [B, T] is False the
        token is made to change nothing (g = 0, beta = 0)."""
        with profiling.region("attn.qkv"):
            cfg = self.cfg
            H, Dk = cfg.linear_num_heads, cfg.linear_key_head_dim
            B, T = x.shape[:2]
            h = self._mixer_in(x)
            raw = jnp.concatenate(
                [self.q_proj(h), self.k_proj(h), self.v_proj(h)], axis=-1)
            mixed = nn.silu(linear_ops.causal_conv(raw, self.conv_taps, tail))
            q, k, v = jnp.split(mixed, [H * Dk, 2 * H * Dk], axis=-1)
            q = linear_ops.l2_normalize(q.reshape(B, T, H, Dk)) * Dk ** -0.5
            k = linear_ops.l2_normalize(k.reshape(B, T, H, Dk))
            v = v.reshape(B, T, H, -1)
            beta = nn.sigmoid(self.b_proj(h).astype(jnp.float32))
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(self.A_log.astype(jnp.float32)) * nn.softplus(
                self.a_proj(h).astype(jnp.float32)
                + self.dt_bias.astype(jnp.float32))
            if keep is not None:
                g = jnp.where(keep[..., None], g, 0.0)
                beta = jnp.where(keep[..., None], beta, 0.0)
            return h, raw, q, k, v, g, beta

    def _linear_close(self, x: jax.Array, h: jax.Array, o: jax.Array,
                      deterministic: bool = True) -> jax.Array:
        """From the rule's output ``o`` [B,T,H,Dv] to the block's: the
        gated per-head norm, the output projection and the residual
        add."""
        with profiling.region("attn.out"):
            gate = nn.silu(
                self.g_proj(h).astype(jnp.float32)).reshape(o.shape)
            y = (self.o_norm(o) * gate).astype(jnp.dtype(self.cfg.dtype))
        return self._add_mixed(x, y, deterministic)

    def linear_mix(self, x: jax.Array, deterministic: bool = True):
        """The whole sequence from an empty state (the training forward)."""
        h, _, q, k, v, g, beta = self._linear_inputs(x, None, None)
        o, _ = linear_ops.gated_delta_chunked(q, k, v, g, beta)
        return self._linear_close(x, h, o, deterministic)

    def linear_prefill(self, x: jax.Array, state: jax.Array,
                       tail: jax.Array, lengths: jax.Array):
        """``x`` [B, T, hidden] through the block from ``state`` /
        ``tail`` (an empty sequence's: zeros), absorbing only the tokens
        before ``lengths[b]``: returns (y, the state after token
        ``lengths[b] - 1``, the ``K - 1`` projections before position
        ``lengths[b]``).  ``y`` at positions >= ``lengths[b]`` is
        meaningless."""
        keep = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
        h, raw, q, k, v, g, beta = self._linear_inputs(x, tail, keep)
        o, state = linear_ops.gated_delta_chunked(q, k, v, g, beta, state)
        with profiling.region("cache.write"):
            new_tail = linear_ops.conv_tail(
                jnp.concatenate([tail, raw.astype(tail.dtype)], axis=1),
                lengths + tail.shape[1], tail.shape[1])
        return self._linear_close(x, h, o), state, new_tail

    def linear_decode_step(self, x: jax.Array, state: jax.Array,
                           tail: jax.Array, live: jax.Array):
        """One token a row: ``x`` [B, 1, hidden] against ``state``
        [B, H, Dv, Dk] and ``tail`` [B, K-1, channels].  A row where
        ``live`` [B] is False keeps its state and its tail bit for bit."""
        h, raw, q, k, v, g, beta = self._linear_inputs(x, tail,
                                                       live[:, None])
        o, state = linear_ops.gated_delta_step(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
        with profiling.region("cache.write"):
            shifted = jnp.concatenate(
                [tail[:, 1:], raw.astype(tail.dtype)], axis=1)
            tail = jnp.where(live[:, None, None], shifted, tail)
        return self._linear_close(x, h, o[:, None]), state, tail

    # ---------------------------------------------- latent attention

    def _latent_q_row(self, x: jax.Array, positions: jax.Array):
        """What both forms share, for ``x`` [B, T, hidden] at ``positions``
        ([T] or [B, T]): a head's un-rotated query part [B,T,H,nope], its
        rotated part [B,T,H,rope], and the token's cache row in its two
        parts: the latent AFTER its norm [B,T,latent_kv_rank] and the one
        key all heads share AFTER its rotation [B,T,rope]."""
        with profiling.region("attn.qkv"):
            cfg = self.cfg
            h = self._mixer_in(x)
            q = self.q_b(self.q_a_norm(self.q_a(h)))
            q_nope, q_rot = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
            q_rot = apply_rope(q_rot, positions, cfg.rope_base)
            kv = self.kv_a(h)
            latent = self.kv_a_norm(kv[..., :cfg.latent_kv_rank])
            k_rot = apply_rope(kv[..., None, cfg.latent_kv_rank:], positions,
                               cfg.rope_base)[:, :, 0]
            return q_nope, q_rot, latent, k_rot

    def _latent_attend(self, x: jax.Array, backend: str):
        """EXPANDED form over the whole sequence: per-head keys (the part
        expanded from the latent beside the shared rotated key) and values
        through the attention backend.  Returns (the heads' contexts
        [B,T,H,v_head_dim], the cache row's two parts)."""
        cfg = self.cfg
        q_nope, q_rot, latent, k_rot = self._latent_q_row(
            x, jnp.arange(x.shape[1]))
        with profiling.region("mla.expand"):
            k_nope, v = jnp.split(self.kv_b(latent),
                                  [cfg.qk_nope_head_dim], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rot[:, :, None, :], (*k_nope.shape[:3],
                                       cfg.qk_rope_head_dim))], axis=-1)
            q = jnp.concatenate([q_nope, q_rot], axis=-1)
        with profiling.region("attn.scores"):
            ctx = dot_product_attention(q, k, v, causal=True, backend=backend)
        return ctx, latent, k_rot

    def latent_mix(self, x: jax.Array, deterministic: bool = True):
        """The whole sequence, nothing cached (the training forward)."""
        y, _, _ = self._latent_attend(x, self.cfg.attention_backend)
        return self._add_mixed(x, y, deterministic)

    def latent_prefill(self, x: jax.Array, latent_cache: jax.Array,
                       key_cache: jax.Array, lengths: None = None):
        """The prompt's P tokens in one causal pass, their rows written to
        the caches ([B, M, latent_kv_rank] and [B, M, rope]) at [0, P):
        every position of a padded prompt, so ``lengths`` (the place every
        kind's prefill form has for it) is None, ``GptLM.prefill`` sees."""
        backend = ("xla" if self.cfg.attention_backend in ("ring", "ulysses")
                   else self.cfg.attention_backend)
        y, latent, k_rot = self._latent_attend(x, backend)
        return (self._add_mixed(x, y),
                self._write_prefill(latent_cache, latent),
                self._write_prefill(key_cache, k_rot))

    def latent_decode_step_paged(self, x: jax.Array, latent_pool: jax.Array,
                                 key_pool: jax.Array,
                                 page_table: jax.Array,
                                 positions: jax.Array,
                                 live: jax.Array | None = None):
        """One token a row against the PAGED pools of latent rows
        ([num_pages + 1, page_size, latent_kv_rank] and the rotated keys'
        [num_pages + 1, page_size / 2, 2 * rope], two tokens a row of 128
        lanes: :func:`init_kv_pool`; addressing, sentinel page and masks
        as in :meth:`decode_step_paged`, and its TWO forms of the read:
        the latent kernel over the pages a lane holds where
        :func:`paged_kernel_attends` says so, else the gather of every
        entry of the table), in the
        ABSORBED form: with ``kv_b`` split a head into W^K [latent, nope]
        and W^V [latent, v], the query's un-rotated part is folded through
        W^K into the latent's space and scored against the cached latents
        themselves, its rotated part against the cached rotated keys, the
        weights average the cached LATENTS, and W^V expands that one
        average a head.  The same mathematics as the expanded form; no key
        or value is ever expanded over the context."""
        cfg = self.cfg
        sentinel, page = latent_pool.shape[0] - 1, latent_pool.shape[1]
        MP = page_table.shape[1]
        rope, rows = cfg.qk_rope_head_dim, key_pool.shape[1]
        q_nope, q_rot, latent, k_rot = self._latent_q_row(
            x, positions[:, None])
        with profiling.region("cache.write"):
            lpage = (positions // page).astype(jnp.int32)
            off = (positions % page).astype(jnp.int32)
            phys = written_pages(jnp.take_along_axis(
                page_table, jnp.clip(lpage, 0, MP - 1)[:, None],
                axis=1)[:, 0], latent_pool.shape[0])
            latent_pool = latent_pool.at[phys, off].set(
                latent[:, 0].astype(latent_pool.dtype), mode="drop")
            fresh, row = k_rot[:, 0].astype(key_pool.dtype), off
            if rows < page:
                # Two tokens a row of 128 lanes (``init_kv_pool``): the
                # row as it is with this token's part of it replaced.
                row = off % rows
                part = jnp.arange(key_pool.shape[2])[None, :] // rope
                fresh = jnp.where(
                    part == (off // rows)[:, None],
                    jnp.tile(fresh, (1, page // rows)),
                    key_pool.at[phys, row].get(mode="clip"))
            key_pool = key_pool.at[phys, row].set(fresh, mode="drop")
        compute = q_nope.dtype

        def absorbed():
            # W^K [latent, H, nope] and W^V [latent, H, v] of ``kv_b``.
            return jnp.split(
                self.kv_b.variables["params"]["kernel"].astype(compute),
                [cfg.qk_nope_head_dim], axis=-1)

        if paged_kernel_attends(cfg, latent_pool, key_pool):
            # The same sums over the pages the lane HOLDS, each copied
            # once: its latents are keys and values both
            # (ops/pallas/paged_attention.py).
            with profiling.region("mla.absorb"):
                w_k, w_v = absorbed()
                q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_k)
            with profiling.region("attn.scores"):
                mean = paged_ops.latent_paged_attention(
                    q_lat, q_rot[:, 0], latent_pool, key_pool, page_table,
                    positions,
                    scale=1.0 / (cfg.qk_nope_head_dim + rope) ** 0.5)
        else:
            with profiling.region("cache.gather"):
                s = jnp.arange(MP * page)
                allocated = jnp.take_along_axis(
                    page_table, (s[None, :] // page), axis=1) < sentinel
                valid = (s[None, :] <= positions[:, None]) & allocated
            with profiling.region("mla.absorb"):
                w_k, w_v = absorbed()
                latents = gather_pages(latent_pool, page_table).astype(
                    compute)
                scale = 1.0 / jnp.sqrt(jnp.float32(
                    cfg.qk_nope_head_dim + rope))
                logits = (jnp.einsum(
                    "bhc,bsc->bhs",
                    jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_k), latents,
                    preferred_element_type=jnp.float32) + jnp.einsum(
                    "bhr,bsr->bhs", q_rot[:, 0],
                    self._gathered_keys(key_pool, page_table).astype(compute),
                    preferred_element_type=jnp.float32)) * scale
                logits = jnp.where(valid[:, None, :], logits,
                                   jnp.finfo(jnp.float32).min)
                weights = jax.nn.softmax(logits, axis=-1).astype(compute)
                mean = jnp.einsum("bhs,bsc->bhc", weights, latents)
        with profiling.region("mla.absorb"):
            ctx = jnp.einsum("bhc,chd->bhd", mean, w_v)
        return self._add_mixed(x, ctx[:, None]), latent_pool, key_pool

    def _gathered_keys(self, key_pool: jax.Array,
                       page_table: jax.Array) -> jax.Array:
        """Every entry of the table's rotated keys side by side, a row a
        token [B, MP * page_size, rope], whichever way the pool holds a
        page of them (:func:`init_kv_pool`)."""
        keys = gather_pages(key_pool, page_table)
        rope = self.cfg.qk_rope_head_dim
        if key_pool.shape[2] == rope:
            return keys
        B, MP = page_table.shape
        return paged_ops.unpack_keys(
            keys.reshape(B, MP, *key_pool.shape[1:]), rope).reshape(
                B, -1, rope)

    def _write_prefill(self, cache: jax.Array, fresh: jax.Array) -> jax.Array:
        """Write the prompt's K or V rows into the cache.

        Plain cache (M >= P): positions [0, P) land at slots [0, P).  Ring
        cache (sliding window, M < P): only the last M positions matter —
        position p lives at slot ``p % M``, which for the contiguous tail
        is a roll by ``(P - M) % M``."""
        with profiling.region("cache.write"):
            P, M = fresh.shape[1], cache.shape[1]
            fresh = fresh.astype(cache.dtype)
            if P <= M:
                return jax.lax.dynamic_update_slice_in_dim(cache, fresh, 0,
                                                           axis=1)
            return jnp.roll(fresh[:, P - M:], (P - M) % M, axis=1)

    def _write_prefill_ragged(self, cache: jax.Array, fresh: jax.Array,
                              lengths: jax.Array) -> jax.Array:
        """Ragged-prompt cache write: row ``b`` contributes only its
        ``lengths[b]`` real positions — pad K/V never enters the cache.

        GATHER formulation (no scatter, no duplicate-index ordering
        hazard): for each slot ``s``, ``p*(b, s)`` is the LAST real
        position of row b landing there (``p ≡ s (mod M)``,
        ``p < lengths[b]``); slots no real position reaches keep their
        old (zero-init) content and stay masked by position arithmetic in
        :meth:`decode_step_ragged`.  This is what makes the RING cache
        ragged-safe: with slot reuse, a junk pad written at slot ``s``
        would alias a masked-in real position — so it is never written.
        """
        with profiling.region("cache.write"):
            P, M = fresh.shape[1], cache.shape[1]
            lb1 = (lengths - 1).astype(jnp.int32)                    # [B]
            s = jnp.arange(M)
            p_star = lb1[:, None] - ((lb1[:, None] - s[None, :]) % M)  # [B, M]
            src = jnp.take_along_axis(
                fresh, jnp.clip(p_star, 0, P - 1)[..., None, None], axis=1)
            return jnp.where((p_star >= 0)[..., None, None],
                             src.astype(cache.dtype), cache)

    def prefill(self, x: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                lengths: jax.Array | None = None):
        """The prompt's P tokens through the block in ONE causal attention
        pass (MXU-batched), writing positions [0, P) into the caches —
        O(P²) parallel work instead of P sequential decode steps, which is
        what makes long-prompt generation usable (see
        :func:`generate_cached`).  ``lengths`` ([B], optional) marks
        right-padded ragged prompts: pad positions are then excluded from
        the cache write (required for the ring cache, where slot reuse
        would alias them onto valid positions)."""
        q, k, v = self._qkv(x)   # rope positions default to arange(P)
        if lengths is None:
            k_cache = self._write_prefill(k_cache, k)
            v_cache = self._write_prefill(v_cache, v)
        else:
            k_cache = self._write_prefill_ragged(k_cache, k, lengths)
            v_cache = self._write_prefill_ragged(v_cache, v, lengths)
        # Decode is single-host: the sequence-parallel backends (training-time
        # sequence sharding) have no mesh here, so prefill falls back to plain
        # XLA attention for them.
        backend = ("xla" if self.cfg.attention_backend in ("ring", "ulysses")
                   else self.cfg.attention_backend)
        with profiling.region("attn.scores"):
            ctx = dot_product_attention(
                q, self._expand_kv(k), self._expand_kv(v), causal=True,
                window=self.window, backend=backend)
        return self._add_mixed(x, ctx), k_cache, v_cache

    def _check_ring(self, M: int) -> None:
        if self.cfg.attention_window and M > self.cfg.attention_window:
            # Ring addressing IS the window mask: a longer cache would keep
            # out-of-band keys resident and silently attend them.  Caches
            # must come from init_kv_cache (which clamps to the window).
            raise ValueError(
                f"windowed decode cache has {M} rows > attention_window="
                f"{self.cfg.attention_window}; allocate via init_kv_cache")

    def _attend_cache(self, q: jax.Array, k_cache: jax.Array,
                      v_cache: jax.Array, valid: jax.Array) -> jax.Array:
        """Grouped attention of ``q`` [B, Q, H, D] against the cache —
        the ONE cached-attention body every contiguous decode variant
        (:meth:`decode_step` / :meth:`decode_step_ragged` /
        :meth:`decode_chunk`) shares; only cache addressing and the
        ``valid`` mask (broadcastable to [B, G, R, Q, M]) differ per
        caller.  (The paged step attends rows it leaves flat:
        :meth:`_attend_rows`.)

        Caches may ride a narrower dtype than compute (float8 KV): upcast
        ON READ — XLA fuses the cast into the einsum, so HBM traffic is
        the narrow cache while the MXU sees the compute dtype.  (Never
        downcast the softmax weights to the cache dtype — fp8 weights
        would destroy the distribution.)  GQA contracts GROUPED: q splits
        into [G, H/G] and attends the G-head cache directly — no
        materialized H-head expansion, so cache reads stay at G heads.
        """
        with profiling.region("attn.scores"):
            cfg = self.cfg
            depth = q.shape[-1]
            scale = 1.0 / jnp.sqrt(jnp.float32(depth))
            compute = q.dtype
            B, Q = q.shape[0], q.shape[1]
            G, R = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
            qg = q.reshape(B, Q, G, R, depth)
            logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                                k_cache.astype(compute),
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(valid, logits, jnp.finfo(jnp.float32).min)
            weights = jax.nn.softmax(logits, axis=-1)
            ctx = jnp.einsum("bgrqk,bkgd->bqgrd", weights.astype(compute),
                             v_cache.astype(compute))
            return ctx.reshape(B, Q, cfg.num_heads, depth)

    def _attend_rows(self, q: jax.Array, k_rows: jax.Array,
                     v_rows: jax.Array, valid: jax.Array) -> jax.Array:
        """:meth:`_attend_cache` for ONE query a row, ``q`` [B, 1, H, D],
        against rows gathered from a paged pool and left as the pool holds
        them, flat: ``k_rows``/``v_rows`` [B, M, G * D]; ``valid`` [B, M].

        The same sums, placed so that the rows are never re-laid out: a
        query head is widened to the whole row, zero outside its own kv
        head's D lanes, so scores and weighted values are two plain
        matmuls over [M, G * D] and the head's own D lanes of the result
        are its context.  Splitting the gathered rows into [B, M, G, D]
        instead costs the chip two more passes over them a layer (a
        float32 copy of the keys among them: PERF.md, PR 37); the G-fold
        products ride in the shadow of reading the rows once.
        """
        with profiling.region("attn.scores"):
            B, _, H, depth = q.shape
            G = self.cfg.num_kv_heads
            scale = 1.0 / jnp.sqrt(jnp.float32(depth))
            own = (jnp.arange(H)[:, None] // (H // G)
                   == jnp.arange(G)[None, :])[None, :, :, None]     # [1,H,G,1]
            wide = jnp.where(own, q[:, 0, :, None, :], 0).reshape(B, H, -1)
            logits = jnp.einsum("bhc,bkc->bhk", wide, k_rows.astype(q.dtype),
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(valid[:, None, :], logits,
                               jnp.finfo(jnp.float32).min)
            weights = jax.nn.softmax(logits, axis=-1)
            ctx = jnp.einsum("bhk,bkc->bhc", weights.astype(q.dtype),
                             v_rows.astype(q.dtype)).reshape(B, H, G, depth)
            return jnp.where(own, ctx, 0).sum(axis=2)[:, None]

    def _attend_cache_chunk(self, q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, prefix_valid: jax.Array,
                            chunk_valid: jax.Array) -> jax.Array:
        """Shared-prefix chunk-verify attention — the cheap-verify
        formulation every K-wide verifier (:meth:`decode_chunk`, its tree
        variant, :meth:`decode_chunk_paged`) shares.

        Two phases folded into ONE softmax: (1) all K queries attend the
        COMMITTED cache through a single shared ``prefix_valid`` [B, M]
        mask — the cache is read once for the whole chunk and no
        per-(row, query) M-wide mask is ever materialized (the old
        formulation built [B, K, M], K-fold the bytes of the scores
        themselves); (2) the chunk's own fresh ``k_new``/``v_new``
        [B, K, G, D] are attended directly from registers through the
        static ``chunk_valid`` intra-chunk mask ([..., K, K]: causal
        lower-triangle for linear verify, the ancestor matrix for tree
        verify) — the scattered cache writes are off the critical path of
        the attention reads.  Same math as masking the post-write cache
        (the key set is identical), so chunk logits equal sequential
        decode logits to float tolerance.
        """
        with profiling.region("attn.scores"):
            cfg = self.cfg
            depth = q.shape[-1]
            scale = 1.0 / jnp.sqrt(jnp.float32(depth))
            compute = q.dtype
            B, K, M = q.shape[0], q.shape[1], k_cache.shape[1]
            G, R = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
            qg = q.reshape(B, K, G, R, depth)
            neg = jnp.finfo(jnp.float32).min
            lp = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache.astype(compute),
                            preferred_element_type=jnp.float32) * scale
            lp = jnp.where(prefix_valid[:, None, None, None, :], lp, neg)
            lc = jnp.einsum("bqgrd,bjgd->bgrqj", qg, k_new.astype(compute),
                            preferred_element_type=jnp.float32) * scale
            lc = jnp.where(chunk_valid, lc, neg)
            w = jax.nn.softmax(jnp.concatenate([lp, lc], axis=-1), axis=-1)
            ctx = (jnp.einsum("bgrqk,bkgd->bqgrd", w[..., :M].astype(compute),
                              v_cache.astype(compute))
                   + jnp.einsum("bgrqj,bjgd->bqgrd",
                                w[..., M:].astype(compute),
                                v_new.astype(compute)))
            return ctx.reshape(B, K, cfg.num_heads, depth)

    def decode_step(self, x: jax.Array, k_cache: jax.Array,
                    v_cache: jax.Array, position: jax.Array):
        """One token through the block against the KV cache.

        ``x``: [B, 1, hidden]; caches: [B, M, H, D]; ``position``: scalar
        ABSOLUTE index being generated.  Returns (y [B,1,hidden], new
        caches).  O(M) work — no S×S score matrix.

        The cache is addressed as a ring: position ``p`` lives at slot
        ``p % M``.  With a full-length cache (M = total, no window) the
        modulo is the identity; with a sliding window the cache holds only
        the last ``attention_window`` entries (see :func:`init_kv_cache`) —
        constant cache bytes no matter how long the generation runs.  Keys
        are stored rope-rotated at their absolute positions, so scores
        need no slot arithmetic.
        """
        M = k_cache.shape[1]
        self._check_ring(M)
        slot = position % M
        q, k, v = self._qkv(x, positions=position[None])  # [B, 1, H, D]
        with profiling.region("cache.write"):
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k.astype(k_cache.dtype), slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v.astype(v_cache.dtype), slot, axis=1)
        # Slot s holds absolute position  position - ((position - s) mod M)
        # ∈ [position - M + 1, position]: with M == attention_window every
        # written slot is inside the band BY CONSTRUCTION (training's
        # window mask falls out of the ring addressing), so the only
        # invalid slots are the never-written ones of a not-yet-full ring.
        k_slot = jnp.arange(M)
        valid = (k_slot <= position) | (position >= M)
        ctx = self._attend_cache(q, k_cache, v_cache,
                                 valid[None, None, None, None, :])
        x = self._add_mixed(x, ctx)
        return self._mlp(x, deterministic=True), k_cache, v_cache

    def decode_step_ragged(self, x: jax.Array, k_cache: jax.Array,
                           v_cache: jax.Array, positions: jax.Array):
        """One token PER ROW at per-row absolute ``positions`` [B] —
        :meth:`decode_step`'s ring addressing with :meth:`decode_chunk`'s
        ragged frontiers, which is what the exported serving pair needs
        for sliding-window checkpoints (VERDICT r4 #3).

        Ring-safe by position arithmetic: row b's slot ``s`` nominally
        holds position ``pos_b - ((pos_b - s) mod M)``; provided every
        position in ``[0, pos_b]`` has actually been written (ragged
        prefill + sequential decode guarantee it — pads are NEVER
        written, see :meth:`_write_prefill_ragged`), a slot is valid iff
        that nominal position is >= 0, i.e. ``s <= pos_b or pos_b >= M``.
        With M == attention_window the ring IS the training window mask;
        with a full-length cache (M >= total) this reduces exactly to
        :meth:`decode_chunk` at K=1.
        """
        M = k_cache.shape[1]
        self._check_ring(M)
        B = x.shape[0]
        slot = (positions % M).astype(jnp.int32)
        q, k, v = self._qkv(x, positions=positions[:, None])  # [B,1,G,D]
        rows = jnp.arange(B)
        with profiling.region("cache.write"):
            k_cache = k_cache.at[rows, slot].set(
                k[:, 0].astype(k_cache.dtype), mode="drop")
            v_cache = v_cache.at[rows, slot].set(
                v[:, 0].astype(v_cache.dtype), mode="drop")
        k_slot = jnp.arange(M)
        valid = ((k_slot[None, :] <= positions[:, None])
                 | (positions[:, None] >= M))                  # [B, M]
        ctx = self._attend_cache(q, k_cache, v_cache,
                                 valid[:, None, None, None, :])
        x = self._add_mixed(x, ctx)
        return self._mlp(x, deterministic=True), k_cache, v_cache

    def decode_chunk(self, x: jax.Array, k_cache: jax.Array,
                     v_cache: jax.Array, positions: jax.Array,
                     depths: jax.Array | None = None,
                     anc: jax.Array | None = None):
        """K tokens through the block against the cache in ONE pass.

        ``x``: [B, K, hidden]; ``positions``: [B] per-row start — row b's
        chunk occupies cache SLOTS ``positions[b] .. positions[b]+K-1``
        (rows may be at different frontiers, e.g. speculative decoding
        after per-row acceptance).  The chunk's K/V are written, and every
        query attends the committed cache once through a shared prefix
        mask plus the chunk's fresh K/V through a static intra-chunk mask
        (:meth:`_attend_cache_chunk`) — MXU-batched verification instead
        of K sequential decode steps.

        **Linear** (``depths``/``anc`` None): chunk token i is the row's
        next token at depth i — logical position ``positions[b]+i``,
        intra-chunk mask the causal lower triangle.

        **Tree** (SpecInfer-style draft trees, see docs/speculative.md):
        ``depths`` [K] gives each node's depth below the frontier and
        ``anc`` [K, K] its ancestor-or-self matrix; node i embeds/ropes at
        LOGICAL position ``positions[b]+depths[i]`` but writes its K/V at
        slot ``positions[b]+i`` (two same-depth siblings cannot share a
        slot), and attends exactly the committed prefix plus its own
        ancestors — so each node's hidden state equals what sequential
        decode of its root path would produce.  After acceptance the
        caller compacts the winning path's K/V down to slot == position
        (:func:`fixup_tree_caches`); rejected nodes leave junk past the
        frontier, masked by position arithmetic until overwritten.

        Full-length caches only (each position owns a unique slot, so a
        later overwrite of a speculatively-written slot is automatically
        correct); the windowed ring cache is rejected by the caller.
        """
        cfg = self.cfg
        if cfg.attention_window:
            raise ValueError(
                "decode_chunk needs the full-length cache (slot == absolute "
                "position); the windowed ring cache would silently attend "
                "stale entries — use sequential decode_step instead")
        B, K = x.shape[0], x.shape[1]
        M = k_cache.shape[1]
        slot = positions[:, None] + jnp.arange(K)[None, :]       # [B, K]
        if depths is None:
            pos = slot
            chunk_valid = (jnp.arange(K)[:, None]
                           >= jnp.arange(K)[None, :])            # causal
        else:
            pos = positions[:, None] + depths[None, :]
            chunk_valid = anc
        q, k, v = self._qkv(x, positions=pos)                    # [B,K,H,D]
        rows = jnp.arange(B)[:, None]
        # The fresh chunk K/V ride at CACHE dtype from here on: the
        # intra-chunk attention must see exactly the (possibly fp8/bf16-
        # rounded) values sequential decode_step would read back from the
        # cache, or narrow-KV chunk logits drift from the step path's.
        k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
        # mode="drop" is load-bearing, not just JAX's scatter default made
        # explicit: callers (serve.py's chunked loop, the speculative
        # finisher) deliberately let already-finished rows' positions run
        # past capacity, and an OOB write must vanish — a clamping
        # primitive here would corrupt the last cache slot.
        with profiling.region("cache.write"):
            k_cache = k_cache.at[rows, slot].set(k, mode="drop")
            v_cache = v_cache.at[rows, slot].set(v, mode="drop")
        # Committed prefix: slots strictly before the row's frontier.
        # Slots at/past it hold this chunk (attended fresh) or junk from
        # rejected speculative writes — masked until real tokens arrive.
        prefix_valid = jnp.arange(M)[None, :] < positions[:, None]
        ctx = self._attend_cache_chunk(
            q, k_cache, v_cache, k, v, prefix_valid,
            chunk_valid[None, None, None, :, :])
        x = self._add_mixed(x, ctx)
        return self._mlp(x, deterministic=True), k_cache, v_cache

    def decode_chunk_paged(self, x: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_table: jax.Array,
                           positions: jax.Array):
        """K tokens per row against the PAGED pool in one pass — the
        serving tier's speculative-verify body (:mod:`..serving.engine`).

        :meth:`decode_chunk`'s linear verify with :meth:`decode_step_paged`'s
        addressing: row b's chunk token i lives at logical position
        ``positions[b]+i``, physical page ``page_table[b, p // page]``.
        Rejected speculative page writes are masked by the per-row
        frontier exactly like the full-cache variant: the prefix mask
        admits only slots before ``positions[b]``, junk written past the
        frontier stays unread until real tokens overwrite it.  Writes
        whose logical page falls OUTSIDE the page table (drafts past the
        row's reservation) go one past the pool and drop, as a write
        through a sentinel entry does (:func:`written_pages`) — never
        clamped onto the last real page, which may hold committed K/V,
        nor onto the page of zeros.  The pools' row is flat,
        [num_pages + 1, page_size, G * D]
        (:func:`init_kv_pool`; PR 37); the gathered rows get their head
        axis back for :meth:`_attend_cache_chunk` (two passes over them on
        the chip that :meth:`decode_step_paged` avoids; no cell runs this).
        """
        cfg = self.cfg
        if cfg.attention_window:
            raise ValueError(
                "the paged chunk needs position == logical slot: "
                "GptConfig.attention_window, one window for all layers, is "
                "the unpaged paths' (and a sliding_attention layer's ring "
                "is GptLM.decode_paged's alone)")
        sentinel, page = k_pool.shape[0] - 1, k_pool.shape[1]
        B, MP = page_table.shape
        K = x.shape[1]
        pos = positions[:, None] + jnp.arange(K)[None, :]        # [B, K]
        q, k, v = self._qkv(x, positions=pos)                    # [B,K,*,D]
        with profiling.region("cache.write"):
            lpage = (pos // page).astype(jnp.int32)
            off = (pos % page).astype(jnp.int32)
            phys = jnp.take_along_axis(page_table,
                                       jnp.clip(lpage, 0, MP - 1), axis=1)
            phys = written_pages(jnp.where(lpage < MP, phys, sentinel),
                                 k_pool.shape[0])
            # Cache-dtype round trip before attending (see decode_chunk).
            k, v = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
            k_pool = k_pool.at[phys, off].set(k.reshape(B, K, -1),
                                              mode="drop")
            v_pool = v_pool.at[phys, off].set(v.reshape(B, K, -1),
                                              mode="drop")
        def gather(pool):
            return gather_pages(pool, page_table).reshape(
                B, MP * page, *k.shape[2:])
        with profiling.region("cache.gather"):
            s = jnp.arange(MP * page)
            allocated = jnp.take_along_axis(
                page_table, (s[None, :] // page), axis=1) < sentinel  # [B, S]
            prefix_valid = (s[None, :] < positions[:, None]) & allocated
        chunk_valid = (jnp.arange(K)[:, None] >= jnp.arange(K)[None, :])
        ctx = self._attend_cache_chunk(
            q, gather(k_pool), gather(v_pool), k, v, prefix_valid,
            chunk_valid[None, None, None, :, :])
        x = self._add_mixed(x, ctx)
        return self._mlp(x, deterministic=True), k_pool, v_pool

    def decode_step_paged(self, x: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, page_table: jax.Array,
                          positions: jax.Array,
                          live: jax.Array | None = None):
        """One token per row against a PAGED KV pool — the serving tier's
        decode body (:mod:`..serving.engine`).

        The pool holds every resident sequence's cache as fixed-size pages
        (``k_pool``/``v_pool``: [num_pages + 1, page_size, G * D], the
        row flat so that the chip keeps the pool as the scatter below
        indexes it, and attended flat, :meth:`_attend_rows`:
        :func:`init_kv_pool`, PR 37); row ``b``'s
        logical position ``p`` lives at physical page
        ``page_table[b, p // page_size]``, offset ``p % page_size``.
        ``page_table`` [B, MP] uses ``num_pages`` as the not-allocated
        sentinel, and that is the pool's LAST page: all zeros, handed out
        by no allocator and never written.  The gather reads it like any
        page, in bounds, so no pass blanks the gathered rows (PR 39), and
        what it reads is zeros under a weight of zero: a row's output
        depends on no page the row does not own.  A write through the
        sentinel (an idle slot's) is sent one past the pool and drops
        (:func:`written_pages` — same drop-don't-clip discipline as
        :meth:`decode_chunk`).

        Distinct slots never share a page (the allocator's invariant), so
        the per-row scatter has no duplicate indices.

        TWO forms of the read, the same sums (:func:`paged_kernel_attends`
        chooses by what it can observe; PR 45): on a TPU under a Pallas
        configuration the paged-attention kernel copies a lane's HELD
        pages up to its position's out of the pools, once, and scores
        them chunk by chunk (an idle lane costs nothing; no page a lane
        does not own is touched); otherwise, and on the CPU, the plain
        form below gathers every entry of the table and masks.

        A SLIDING_ATTENTION layer's ``page_table`` [B, RP] is the row's
        RING (``RP = cfg.ring_pages(page_size)``; its pool is its own, the
        sentinel that pool's last page): position ``p`` lives in ring page
        ``(p // page_size) % RP``, so ring slot ``s`` holds the newest
        position ``<= positions[b]`` congruent to ``s`` modulo the ring's
        ``RP * page_size`` rows, and counts only where that position is at
        least 0 and less than ``sliding_window`` behind.  A row a newer
        token overwrote is thereby never attended, what a prompt's padding
        wrote (under a page past the prompt) lies further back than the
        window, and a slot not yet written reads, through a sentinel
        entry, zeros under a weight of zero.  The gather is over the ring,
        ``RP * page_size`` rows a lane whatever the context.

        The global ``attention_window`` (one window for ALL layers, no
        kind) stays with the unpaged paths: here it is refused.

        ``live`` [B] (the place every kind's step form has for it) is
        not read: an idle row's table is all sentinel and its write drops.
        Returns the stream after the mixer's residual add and the pools.
        """
        cfg = self.cfg
        if cfg.attention_window:
            raise ValueError(
                "paged decode holds a window as a KIND of layer "
                "(layer_kinds 'sliding_attention' with sliding_window, a "
                "ring of pages a lane); GptConfig.attention_window, one "
                "window for all layers, is the unpaged paths'")
        sentinel, page = k_pool.shape[0] - 1, k_pool.shape[1]
        B, MP = page_table.shape
        ring = self.kind == SLIDING_ATTENTION
        q, k, v = self._qkv(x, positions=positions[:, None])  # [B,1,*,D]
        with profiling.region("cache.write"):
            lpage = (positions // page).astype(jnp.int32)
            off = (positions % page).astype(jnp.int32)
            lpage = lpage % MP if ring else jnp.clip(lpage, 0, MP - 1)
            phys = written_pages(jnp.take_along_axis(
                page_table, lpage[:, None], axis=1)[:, 0], k_pool.shape[0])
            k_pool = k_pool.at[phys, off].set(
                k.reshape(B, -1).astype(k_pool.dtype), mode="drop")
            v_pool = v_pool.at[phys, off].set(
                v.reshape(B, -1).astype(v_pool.dtype), mode="drop")
        if paged_kernel_attends(cfg, k_pool):
            # The same sums over the pages the lane HOLDS, read once where
            # they lie (ops/pallas/paged_attention.py).
            with profiling.region("attn.scores"):
                ctx = paged_ops.paged_attention(
                    q[:, 0], k_pool, v_pool, page_table, positions,
                    window=cfg.sliding_window if ring else 0)[:, None]
        else:
            with profiling.region("cache.gather"):
                s = jnp.arange(MP * page)
                allocated = jnp.take_along_axis(
                    page_table, (s[None, :] // page), axis=1) < sentinel
                if ring:
                    # How far behind the row's position slot s's newest
                    # row is.
                    behind = (positions[:, None] - s[None, :]) % (MP * page)
                    valid = ((behind < cfg.sliding_window)
                             & (behind <= positions[:, None]) & allocated)
                else:
                    valid = (s[None, :] <= positions[:, None]) & allocated
            ctx = self._attend_rows(q, gather_pages(k_pool, page_table),
                                    gather_pages(v_pool, page_table), valid)
        return self._add_mixed(x, ctx), k_pool, v_pool


class GptLM(nn.Module):
    """Token + position embeddings → pre-LN decoder stack → LM head."""

    cfg: GptConfig

    def setup(self):
        cfg = self.cfg
        self.word_emb = nn.Embed(cfg.vocab_size, cfg.hidden_size)
        self.pos_emb = nn.Embed(cfg.max_position, cfg.hidden_size)
        self.emb_drop = nn.Dropout(cfg.dropout_rate)
        # static_argnums counts self at 0: (self, x, deterministic).
        block_cls = (nn.remat(GptBlock, static_argnums=(2,)) if cfg.remat
                     else GptBlock)
        self.layers = [block_cls(cfg, kind, sparse, name=f"layer{i}")
                       for i, (kind, sparse) in enumerate(
                           zip(cfg.kinds, cfg.sparse_layers))]
        self.ln_final = _layer_norm(cfg)
        self.lm_head = nn.Dense(cfg.vocab_size)
        if cfg.exit_gate:
            # Applied in float32 whatever type its kernel is stored in.
            self.exit_gate = nn.Dense(1, dtype=jnp.float32)

    def _embed(self, input_ids: jax.Array, positions: jax.Array,
               deterministic: bool) -> jax.Array:
        with profiling.region("embed"):
            x = self.word_emb(input_ids)
            if self.cfg.scale_embedding:
                x = x.astype(jnp.float32) * self.cfg.hidden_size ** 0.5
            if self.cfg.pos_encoding == "learned":
                x = x + self.pos_emb(positions)
            x = self.emb_drop(x, deterministic=deterministic)
            return x.astype(jnp.dtype(self.cfg.dtype))

    def _head(self, x: jax.Array, normed: bool = False) -> jax.Array:
        """The final norm and the vocabulary projection; ``normed``: the
        looped stack's stream, which left its last step normed."""
        with profiling.region("head"):
            return self.lm_head(x if normed else self.ln_final(x))

    def _loop(self, stack, x: jax.Array, carry=None, rows=None):
        """The stack ``loop_steps`` times over the same weights (``cfg.
        loop_steps`` > 1): ``stack(mdl, x, carry, t, rows_t) -> (x, carry,
        rows_t)`` is one application, step ``t`` of the loop; the final
        norm follows each, and what it gives is what the next application
        starts from.  ``carry`` is handed from step to step whole (the
        paged pools, which a step addresses by ``t``); ``rows`` is sliced
        along its leading axis, a slice a step, and comes back stacked the
        same way (contiguous caches).  Returns the normed stream after the
        last step (the HEAD's input: no further norm), the carry and the
        rows.

        One ``scan`` and not ``loop_steps`` copies of the stack: the
        compiled program holds ``num_layers`` layer applications whatever
        the loop count.  Sows ``loop/steps_run`` (per sequence, the steps
        it ran: all of them) and ``loop/exit_mass`` (:func:`exit_masses`)
        for whoever applies the model with ``loop`` mutable."""
        cfg = self.cfg

        def step(mdl, state, step_in):
            x, carry = state
            t, rows_t = step_in
            with profiling.region("loop.step"):
                x, carry, rows_t = stack(mdl, x, carry, t, rows_t)
                x = mdl.ln_final(x).astype(x.dtype)
            gate = None
            if cfg.exit_gate:
                with profiling.region("loop.exit_gate"):
                    gate = mdl.exit_gate(x)[..., 0]
            return (x, carry), (rows_t, gate)

        (x, carry), (rows, gates) = nn.scan(
            step, variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            length=cfg.loop_steps)(
                self, (x, carry), (jnp.arange(cfg.loop_steps), rows))
        self.sow("loop", "steps_run", jnp.full(
            x.shape[:1], cfg.loop_steps, jnp.int32))
        self.sow("loop", "exit_mass", exit_masses(
            gates, cfg.loop_steps, x.shape[:-1]))
        return x, carry, rows

    def __call__(self, input_ids: jax.Array,
                 deterministic: bool = True) -> jax.Array:
        S = input_ids.shape[1]
        x = self._embed(input_ids, jnp.arange(S)[None, :], deterministic)
        if self.cfg.loop_steps > 1:
            def stack(mdl, x, carry, t, rows):
                for layer in mdl.layers:
                    x = layer(x, deterministic)
                return x, carry, rows
            return self._head(self._loop(stack, x)[0], normed=True)
        for layer in self.layers:
            x = layer(x, deterministic)
        return self._head(x)  # [B, S, vocab]

    def decode_step(self, token: jax.Array, caches, position: jax.Array):
        """One generation step: ``token`` [B] at ``position`` (scalar) against
        per-layer KV caches (see :func:`init_kv_cache`).  Returns
        (logits [B, vocab], new caches)."""
        self.cfg.refuse_state_layers("GptLM.decode_step")
        x = self._embed(token[:, None], position[None, None], True)
        new_caches = []
        for layer, (k_cache, v_cache) in zip(self.layers, caches):
            x, k_cache, v_cache = layer.decode_step(x, k_cache, v_cache,
                                                    position)
            new_caches.append((k_cache, v_cache))
        return self._head(x)[:, 0], new_caches

    def decode_chunk(self, tokens: jax.Array, caches, positions: jax.Array,
                     depths: jax.Array | None = None,
                     anc: jax.Array | None = None):
        """K tokens per row against the caches in one MXU-batched pass:
        ``tokens`` [B, K] at per-row absolute positions
        ``positions[b] .. positions[b]+K-1``.  Returns (logits [B, K,
        vocab] — one next-token distribution per fed token — and new
        caches).  The speculative-verification primitive (see
        :func:`generate_cached_speculative`); full-length caches only.

        ``depths``/``anc`` select TREE verification (see
        ``GptBlock.decode_chunk`` and :func:`spec_tree`): token i then
        embeds at logical position ``positions[b]+depths[i]`` and attends
        only its ancestors — one call verifies a whole draft tree."""
        self.cfg.refuse_state_layers("GptLM.decode_chunk")
        B, K = tokens.shape
        if depths is None:
            pos = positions[:, None] + jnp.arange(K)[None, :]
        else:
            pos = positions[:, None] + depths[None, :]
        x = self._embed(tokens, pos, True)
        new_caches = []
        for layer, (k_cache, v_cache) in zip(self.layers, caches):
            x, k_cache, v_cache = layer.decode_chunk(x, k_cache, v_cache,
                                                     positions, depths, anc)
            new_caches.append((k_cache, v_cache))
        return self._head(x), new_caches

    def _chunk_paged_body(self, tokens: jax.Array, pools,
                          page_tables: jax.Array, positions: jax.Array):
        """Shared chunk-against-the-pool body: embed K tokens per row at
        their per-row positions and run the layer stack's paged chunk
        attention.  ONE definition for the speculative verify and the
        chunked prefill — the chunked/whole-bucket parity invariant must
        not be breakable by editing one twin.  Returns (x, new pools)."""
        self.cfg.refuse_state_layers("GptLM.decode_chunk_paged / GptLM.prefill_chunk_paged")
        B, K = tokens.shape
        pos = positions[:, None] + jnp.arange(K)[None, :]
        x = self._embed(tokens, pos, True)
        new_pools = []
        for layer, (k_pool, v_pool) in zip(self.layers, pools):
            x, k_pool, v_pool = layer.decode_chunk_paged(
                x, k_pool, v_pool, page_tables, positions)
            new_pools.append((k_pool, v_pool))
        return x, new_pools

    def decode_chunk_paged(self, tokens: jax.Array, pools,
                           page_tables: jax.Array, positions: jax.Array):
        """K tokens per row against per-layer PAGED pools — the serving
        engine's speculative verify (``GptBlock.decode_chunk_paged``).
        ``tokens`` [B, K]; returns (logits [B, K, vocab], new pools)."""
        x, new_pools = self._chunk_paged_body(tokens, pools, page_tables,
                                              positions)
        return self._head(x), new_pools

    def prefill_chunk_paged(self, tokens: jax.Array, pools,
                            page_tables: jax.Array, positions: jax.Array):
        """Chunked-prefill body: :meth:`decode_chunk_paged` WITHOUT the
        LM head — the serving engine's per-step prompt-chunk advance
        (docs/serving.md, "Chunked prefill").

        Prefill only needs the K/V writes; skipping ``_head`` saves the
        [hidden, vocab] matmul over every chunk position (at vocab sizes
        the head is the single largest matmul a chunk would pay).  Row
        ``b``'s chunk token ``i`` lands at logical position
        ``positions[b] + i`` through ``page_tables`` exactly like the
        speculative verify (same ``_chunk_paged_body``); rows that are
        not prefilling this step ride along with sentinel tables (writes
        drop, compute ignored) so the program's shapes never depend on
        which lanes are prefilling.  Returns the new pools."""
        _, new_pools = self._chunk_paged_body(tokens, pools, page_tables,
                                              positions)
        return new_pools

    def decode_ragged(self, token: jax.Array, caches, positions: jax.Array):
        """One token PER ROW at per-row absolute ``positions`` [B], ring-
        cache safe (sliding-window checkpoints; see
        ``GptBlock.decode_step_ragged``).  ``token`` [B].  Returns
        (logits [B, vocab], new caches)."""
        self.cfg.refuse_state_layers("GptLM.decode_ragged")
        x = self._embed(token[:, None], positions[:, None], True)
        new_caches = []
        for layer, (k_cache, v_cache) in zip(self.layers, caches):
            x, k_cache, v_cache = layer.decode_step_ragged(
                x, k_cache, v_cache, positions)
            new_caches.append((k_cache, v_cache))
        return self._head(x)[:, 0], new_caches

    def decode_paged(self, token: jax.Array, pools, page_tables: jax.Array,
                     positions: jax.Array, live: jax.Array | None = None,
                     window_tables: jax.Array | None = None):
        """One token PER ROW against the per-layer pools of
        :func:`init_kv_pool`, each layer through the step form and the
        table its row of :data:`KINDS` names.  ``token`` [B]; ``positions``
        [B]; ``page_tables`` [B, MP] is shared by every layer that holds a
        run of pages (each has its own pool tensor, the same page
        geometry), ``window_tables`` [B, ring pages], the rows' rings, by
        the sliding-attention layers; an entry that is a row a slot is
        indexed by ROW.  ``live`` [B] says which rows are sequences: a row
        that is not keeps its state entry bit for bit and is routed to no
        expert (without ``live`` every row is routed).  With
        ``cfg.loop_steps`` > 1 the stack is walked that many times
        (:meth:`_loop`), step ``t`` writing and attending its own run of
        pages of each layer's pool.  Returns (logits [B, vocab], new
        pools)."""
        if self.cfg.window_layers and window_tables is None:
            raise ValueError(
                "GptLM.decode_paged needs window_tables= [B, ring pages] "
                "for a config whose layer_kinds has a sliding_attention "
                "layer: its pool is a ring a row, not a run of pages")
        if self.cfg.has_state_layers and live is None:
            raise ValueError(
                "GptLM.decode_paged needs live= [B] for a config whose "
                "layer_kinds has a linear_attention or a short_conv layer: "
                "a state row has no sentinel page to drop an idle row's "
                "write")
        x = self._embed(token[:, None], positions[:, None], True)
        if self.cfg.loop_steps > 1:
            def stack(mdl, x, pools, t, rows):
                # Step t's rows lie in its own run of pages of each pool.
                tables = loop_step_pages(page_tables, t,
                                         pools[0][0].shape[0],
                                         mdl.cfg.loop_steps)
                return *_step_layers(mdl, x, pools, {"pages": tables},
                                     positions, live), rows
            x, new_pools, _ = self._loop(stack, x, list(pools))
            return self._head(x, normed=True)[:, 0], new_pools
        x, new_pools = _step_layers(
            self, x, pools, {"pages": page_tables, "ring": window_tables},
            positions, live)
        return self._head(x)[:, 0], new_pools

    def prefill(self, tokens: jax.Array, caches,
                lengths: jax.Array | None = None):
        """Parallel cache fill: the whole prompt [B, P] in one forward,
        K/V written to cache positions [0, P).  Returns (logits for the
        next position [B, vocab], new caches).  ``lengths`` ([B],
        optional): right-padded ragged prompts — pad positions are
        excluded from the cache write (REQUIRED for ring caches, see
        ``GptBlock.prefill``).

        With a linear-attention or a short-convolution layer in
        ``layer_kinds`` (caches from :func:`init_kv_cache`: such a layer's
        entry is its state and its convolution tail, or its tail alone)
        ``lengths`` is required and bounds what those
        layers absorb: their state comes back as after token
        ``lengths[b] - 1`` and padding changes nothing.  The full layers
        then write every position of the padded prompt as they do without
        ``lengths``: whoever decodes next overwrites a position at or
        past ``lengths[b]`` before reading it (the paged engine's
        contract), and the returned logits are the last PADDED position's.
        A latent-attention layer writes every position's row and takes no
        ``lengths``.  A sliding-attention layer's entry may be shorter than
        the prompt (:func:`init_kv_cache` with ``ring_rows``): it then
        comes back as a ring of the prompt's last rows, position ``p`` at
        row ``p % rows``.  With ``cfg.loop_steps`` > 1 a layer's entry has a
        leading axis of that length, a slice a loop step
        (:func:`init_kv_cache`)."""
        B, P = tokens.shape
        if lengths is not None and self.cfg.latent_kv_rank:
            raise ValueError(
                "GptLM.prefill takes no lengths= for a config with "
                "latent_kv_rank: a latent layer writes every position of "
                "the padded prompt")
        x = self._embed(tokens, jnp.arange(P)[None], True)
        if self.cfg.has_state_layers and lengths is None:
            raise ValueError(
                "GptLM.prefill needs lengths= [B] for a config whose "
                "layer_kinds has a linear_attention or a short_conv layer: "
                "padding must not enter a state row")
        if self.cfg.loop_steps > 1:
            def stack(mdl, x, carry, t, caches):
                x, caches = _prefill_layers(mdl, x, caches, lengths)
                return x, carry, caches
            x, _, new_caches = self._loop(stack, x, rows=list(caches))
            return (self._head(x[:, -1:], normed=True)[:, 0],
                    new_caches)
        x, new_caches = _prefill_layers(self, x, caches, lengths)
        # Only the LAST position's logits matter — slice before the
        # [hidden, vocab] head so its matmul runs on one position, not P.
        return self._head(x[:, -1:])[:, 0], new_caches


def _prefill_layers(mdl: GptLM, x: jax.Array, caches, lengths):
    """``x`` [B, P, hidden] through every layer's prefill form (its row of
    :data:`KINDS`) against its entry of ``caches``.  A state row absorbs
    the tokens before ``lengths``; the rows beside one are written at every
    position of the padded prompt, as without ``lengths``."""
    stateful, new_caches = mdl.cfg.has_state_layers, []
    for layer, entry in zip(mdl.layers, caches):
        row = KINDS[layer.kind]
        routed = layer._route_ahead(x)
        x, *entry = getattr(layer, row.prefill)(
            x, *entry, lengths if row.table is None or not stateful else None)
        x = layer._mlp(x, True, routed=routed)
        new_caches.append(tuple(entry))
    return x, new_caches


def _step_layers(mdl: GptLM, x: jax.Array, pools, tables: dict,
                 positions: jax.Array, live):
    """``x`` [B, 1, hidden] through every layer's paged decode step (its
    row of :data:`KINDS`) against its entry of ``pools``: a kind that
    holds pages is addressed through the table its row names, a kind that
    holds a row a slot through none."""
    new_pools = []
    for layer, entry in zip(mdl.layers, pools):
        row = KINDS[layer.kind]
        where = () if row.table is None else (tables[row.table], positions)
        routed = layer._route_ahead(x)
        x, *entry = getattr(layer, row.step)(x, *entry, *where, live)
        x = layer._mlp(x, True, live, routed)
        new_pools.append(tuple(entry))
    return x, new_pools


def exit_masses(gates: jax.Array | None, steps: int,
                shape: tuple) -> jax.Array:
    """The mass that leaves a weight-shared loop at each of its ``steps``
    steps, [steps, *shape] float32, from the exit gate's logits ``gates``
    of the same shape: lambda_t = sigmoid(gate_t), p_t = lambda_t x the
    product of (1 - lambda_j) over the steps before, and the LAST step
    takes what is left (its own gate decides nothing).  Without a gate all
    of it leaves at the last step."""
    if gates is None:
        return jnp.zeros((steps, *shape), jnp.float32).at[-1].set(1.0)
    lam = jax.nn.sigmoid(gates.astype(jnp.float32))[:-1]
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([lam * before, stay[-1:]])


def loop_step_pages(pages: jax.Array, step, rows: int,
                    steps: int) -> jax.Array:
    """Where loop step ``step``'s rows of ``pages`` lie in a pool of
    ``rows`` = ``steps`` x num_pages + 1 pages (:func:`init_kv_pool`):
    step t holds the run ``[t x num_pages, (t + 1) x num_pages)``.  The
    not-allocated sentinel (``num_pages``, one past a step's run) becomes
    ``rows - 1``, the one page of zeros after the last run: the sentinel
    of a pool of that size, to a gather and to :func:`written_pages`."""
    num_pages = (rows - 1) // steps
    return jnp.where(pages < num_pages, pages + step * num_pages, rows - 1)


def written_pages(pages: jax.Array, rows: int) -> jax.Array:
    """``pages`` as a WRITE addresses a pool of ``rows`` pages: the last
    page is the sentinel's, all zeros and never written, so an entry that
    names it (an idle or passenger lane's, a page never allocated) goes
    one past the pool, where ``.at[...].set(mode="drop")`` drops it."""
    return jnp.where(pages < rows - 1, pages, rows)


def paged_kernel_attends(cfg: GptConfig, pool, key_pool=None) -> bool:
    """Whether ``GptBlock.decode_step_paged`` attends a K/V ``pool`` (an
    array or its shape and type) through the paged-attention kernel, or,
    with ``key_pool``, ``GptBlock.latent_decode_step_paged`` a latent
    layer's two pools through the latent one: where the configuration asks
    for Pallas kernels, the backend is a TPU and the pools are such as the
    kernel can walk (``paged_ops.supports`` / ``supports_latent``: a
    float8 pool's page of 16 rows is half a tile).  Otherwise, and so on
    the CPU, the plain form: :func:`gather_pages` and a softmax over every
    entry of the table."""
    return (cfg.attention_backend == "pallas"
            and jax.default_backend() == "tpu"
            and (paged_ops.supports(pool, cfg.head_dim) if key_pool is None
                 else paged_ops.supports_latent(pool, key_pool)))


def paged_kernel_layers(cfg: GptConfig, pools) -> int:
    """The layers of ``cfg`` whose entry of ``pools``
    (:func:`init_kv_pool`) the decode step attends through a kernel."""
    reads = [KINDS[kind].kernel_reads for kind in cfg.kinds]
    return sum(bool(n) and paged_kernel_attends(cfg, *entry[:n])
               for n, entry in zip(reads, pools))


def gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Every row's pages side by side, [B, MP * page_size, row].  In
    bounds by construction, a sentinel entry reading the pool's last page
    (zeros: :func:`init_kv_pool`), so nothing is filled in afterwards:
    ``mode="fill"`` cost a pass of its own over the gathered rows, 1.4 ms
    a full layer in the hybrid's step (PERF.md, PR 39)."""
    with profiling.region("cache.gather"):
        B, MP = page_table.shape
        return jnp.take(pool, page_table, axis=0, mode="clip").reshape(
            B, MP * pool.shape[1], -1)


def init_kv_cache(cfg: GptConfig, batch_size: int, max_len: int,
                  dtype=None, ring_rows: int = 0):
    """Per-layer (k, v) cache arrays [B, max_len, H, D]; with
    ``cfg.loop_steps`` > 1 [loop_steps, B, max_len, H, D], a loop step's
    own keys and values a slice of the leading axis.

    ``dtype`` overrides the compute dtype — ``float8_e4m3fn`` halves the
    cache's HBM bytes vs bf16 (the long-context decode-bandwidth lever;
    attention upcasts on read, so compute stays bf16 on the MXU).  With
    grouped-query attention (``cfg.kv_heads``) the cache carries only the
    kv heads — the same bytes lever from the head-count side.

    With sliding-window attention the cache is a RING of
    ``attention_window`` entries (position ``p`` at slot ``p % window``):
    out-of-band keys are unreachable anyway, so cache bytes — and every
    decode step's cache reads — stay O(window) no matter how long the
    prompt or generation runs.

    A SLIDING_ATTENTION layer's entry (``GptLM.prefill``'s, on its way to
    the paged pool) is a ring of ``min(max_len, ring_rows)`` entries where
    the full layers beside it hold ``max_len``; ``ring_rows`` is the rows
    of a decode slot's ring of pages, ``cfg.ring_pages(page_size) *
    page_size``, and without it the window itself.
    """
    if cfg.attention_window:
        max_len = min(max_len, cfg.attention_window)
    dtype = jnp.dtype(cfg.dtype) if dtype is None else jnp.dtype(dtype)

    def lead(table):
        rows = max_len if table != "ring" \
            else min(max_len, ring_rows or cfg.sliding_window)
        return (batch_size, rows) if cfg.loop_steps == 1 \
            else (cfg.loop_steps, batch_size, rows)

    return [row.entry(cfg, batch_size) if row.table is None
            else row.entry(cfg, lead(row.table), dtype)
            for row in map(KINDS.get, cfg.kinds)]


def _kv_entry(cfg: GptConfig, lead: tuple, dtype, flat: bool = False):
    """A softmax-attention layer's cache entry of one row a token, zeroed,
    ``lead`` being the axes that address a token: (keys, values)
    [*lead, G, D], or with ``flat`` (the paged pool's form,
    :func:`init_kv_pool`) [*lead, G * D]."""
    shape = ((*lead, cfg.num_kv_heads * cfg.head_dim) if flat
             else (*lead, cfg.num_kv_heads, cfg.head_dim))
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _latent_entry(cfg: GptConfig, lead: tuple, dtype, flat: bool = False):
    """A latent layer's: the row's two parts, (the normed latent [*lead,
    latent_kv_rank], the rotated key all heads share [*lead,
    qk_rope_head_dim]): no head axis and no values of their own,
    ``latent_row_dim`` entries a token together.  Two arrays and not one
    of their sum: 512 entries fill whole lanes of 128 and the chip keeps
    the array as it is indexed, where it laid one array of 576 out with
    the PAGES minor-most and every decode step copied every pool into the
    indexed order and back (8.2 of a step's 24.2 ms; PERF.md, PR 35)."""
    rope = cfg.qk_rope_head_dim
    if flat:
        # A page's rotated keys in whole lanes of 128, two tokens a row
        # (``paged_ops.key_rows``): 64 entries a row the chip laid out
        # with the PAGES minor-most and copied into the indexed order and
        # back twice a layer a step (PERF.md, PR 47).
        rows = paged_ops.key_rows(lead[-1], rope)
        keys = (*lead[:-1], rows, lead[-1] // rows * rope)
    else:
        keys = (*lead, rope)
    return (jnp.zeros((*lead, cfg.latent_kv_rank), dtype),
            jnp.zeros(keys, dtype))


def _linear_entry(cfg: GptConfig, rows: int):
    """A linear-attention layer's, for ``rows`` sequences, all empty:
    (state [rows, H, Dv, Dk] float32, the convolution's tail [rows, K-1,
    channels]: the raw q/k/v projections of the last K-1 tokens, in the
    type they were computed in)."""
    return (jnp.zeros((rows, cfg.linear_num_heads,
                       cfg.linear_value_head_dim, cfg.linear_key_head_dim),
                      jnp.float32),
            jnp.zeros((rows, cfg.linear_conv_kernel_dim - 1,
                       cfg.linear_conv_channels), jnp.dtype(cfg.dtype)))


def _conv_entry(cfg: GptConfig, rows: int):
    """A short-convolution layer's: (the tail [rows, K-1, hidden],), the
    last K-1 rows of ``B * X`` in the compute type, and no matrix."""
    return (jnp.zeros((rows, cfg.short_conv_kernel_dim - 1,
                       cfg.hidden_size), jnp.dtype(cfg.dtype)),)


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What the model knows of one kind of layer, a row of :data:`KINDS`:
    ``GptBlock``'s methods by name (looked up on the layer where a program
    is traced; nothing is wrapped) and what the layer keeps of a sequence.
    A further kind is one row and the methods it names.  A form is the
    token MIXER's, to the residual add behind its out projection; the
    block's MLP is whoever looks the form up's to run behind it
    (``GptBlock.__call__``, :func:`_prefill_layers`, :func:`_step_layers`:
    each lays the route of a block that routes ahead down first,
    ``GptBlock._route_ahead``)."""

    setup: str        # the mixer's parameters: (dtype)
    mix: str          # the whole sequence, nothing cached: (x, deterministic)
    prefill: str      # the prompt into a contiguous entry: (x, *entry, lengths)
    step: str         # a token a row against the pool's entry:
    #                   (x, *entry, [table, positions,] live)
    # The entry, zeroed: (cfg, lead, dtype, flat) of a kind that holds a
    # row a token, (cfg, rows) of one that holds a fixed-size row a
    # sequence (a decode slot's) and no pages.
    entry: Callable
    # What addresses the entry in a paged pool: "pages" (a run of the
    # pool's pages a sequence), "ring" (a ring of a pool of its own, a
    # decode slot's) or None (a row a slot: no sentinel page drops an idle
    # row's write, so its step needs ``live``).
    table: str | None = "pages"
    # How many of the entry's pools :func:`paged_kernel_attends` is asked
    # about, 0 where no kernel attends the kind.
    kernel_reads: int = 0


_SOFTMAX = ("_setup_attention", "attention_mix", "prefill",
            "decode_step_paged", _kv_entry)
KINDS = {
    FULL_ATTENTION: LayerKind(*_SOFTMAX, "pages", 1),
    SLIDING_ATTENTION: LayerKind(*_SOFTMAX, "ring", 1),
    LATENT_ATTENTION: LayerKind(
        "_setup_latent", "latent_mix", "latent_prefill",
        "latent_decode_step_paged", _latent_entry, "pages", 2),
    LINEAR_ATTENTION: LayerKind(
        "_setup_linear", "linear_mix", "linear_prefill",
        "linear_decode_step", _linear_entry, None),
    SHORT_CONV: LayerKind("_setup_conv", "conv_mix", "conv_prefill",
                          "conv_decode_step", _conv_entry, None),
}
#: The kinds whose cache entry is one fixed-size row a sequence (a decode
#: slot's) and no pages.
STATE_KINDS = tuple(kind for kind, row in KINDS.items() if row.table is None)


def _entry_bytes(entry: Callable) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.eval_shape(entry))


def kv_row_bytes_per_token(cfg: GptConfig, dtype=None,
                           window: bool = False) -> int:
    """Bytes ONE cached token holds over all layers' pages that grow with
    the sequence (a linear-attention or a short-convolution layer holds
    none; a weight-shared loop holds a row a step a layer); with ``window``
    over the sliding-attention layers' rings instead, which hold a token
    only while it is inside the window."""
    dtype = jnp.dtype(cfg.dtype) if dtype is None else jnp.dtype(dtype)
    return cfg.loop_steps * sum(
        _entry_bytes(lambda row=row: row.entry(cfg, (1,), dtype))
        for row in map(KINDS.get, cfg.kinds)
        if row.table == ("ring" if window else "pages"))


def state_bytes_per_slot(cfg: GptConfig) -> int:
    """Bytes ONE sequence holds in rows of its own beside the pages, over
    all layers that keep one: a linear-attention layer's recurrent state
    and convolution tail, a short-convolution layer's tail (0 for a config
    without either)."""
    return sum(_entry_bytes(lambda row=row: row.entry(cfg, 1))
               for row in map(KINDS.get, cfg.kinds) if row.table is None)


def init_kv_pool(cfg: GptConfig, num_pages: int, page_size: int,
                 dtype=None, num_slots: int = 0):
    """Per-layer (k, v) PAGED pool arrays [num_pages + 1, page_size, G * D]
    — the serving tier's shared KV memory (:mod:`..serving.kv_pool` owns
    the page accounting).  Unlike :func:`init_kv_cache` there is no batch
    axis: every resident sequence draws pages from the same pool, so HBM
    is sized by total resident tokens, not num_slots × max_len.  Same
    dtype lever (``float8_e4m3fn`` halves cache bytes; upcast on read).

    A token's row is held FLAT, its kv heads side by side, so that the
    chip keeps the pool as the decode step's two-axis scatter
    ``pool.at[page, offset]`` indexes it: a head axis of 30 it laid out
    above the page's tokens, and copied every pool there and back on every
    step (sixteen copies of 228 MB; PERF.md, PR 37).  The head axis exists
    only on what is written; the decode step attends the gathered rows
    flat as well (``GptBlock._attend_rows``).

    The page after the allocator's ``num_pages`` is the SENTINEL's: the
    index a page table holds where a row has no page names it, it is all
    zeros from here on, no allocator hands it out and nothing writes it
    (:func:`written_pages`).  So a decode step's gather is in bounds
    whatever the table holds, reads zeros where ``mode="fill"`` wrote
    them, and a row reads no page it does not own (PR 39).

    A linear-attention layer holds no pages: its entry is one fixed-size
    row per decode SLOT (``num_slots`` of them: state float32, convolution
    tail), whatever the sequence's length.  A short-convolution layer's is
    its tail alone, ``[num_slots, taps - 1, hidden]``.  A latent-attention layer's entry
    is its row's two parts, [num_pages + 1, page_size, latent_kv_rank] and
    the rotated keys, ``128 // qk_rope_head_dim`` tokens a row of whole
    lanes where the page divides so (at 64: [num_pages + 1, page_size / 2,
    128], token ``o`` of a page in row ``o % 8`` at lanes ``o // 8 * 64``;
    ``paged_ops.key_rows`` / ``pack_keys``), else [num_pages + 1,
    page_size, qk_rope_head_dim] (:func:`_latent_entry`).

    With ``cfg.loop_steps`` > 1 a layer's pool holds ``loop_steps`` runs of
    ``num_pages`` pages and the one sentinel page after the last,
    [loop_steps * num_pages + 1, page_size, G * D]: page ``p`` of loop
    step ``t`` is row ``t * num_pages + p`` (:func:`loop_step_pages`).  One
    array a layer and not one a step: the scatter and the gather address a
    step's run by an offset, the same two operations as without a loop, and
    the compiled step carries ``num_layers`` pairs of buffers through its
    loop in place.

    A SLIDING_ATTENTION layer's pool is SMALL and of its own geometry:
    ``num_slots * cfg.ring_pages(page_size)`` pages and its own sentinel
    page after them, [num_slots * ring_pages + 1, page_size, G * D]: a
    ring of ``sliding_window + page_size`` rows a decode slot whatever the
    context (``GptBlock.decode_step_paged``), its pages handed out by the
    allocator's second count (``serving/kv_pool.py``)."""
    if cfg.attention_window:
        raise ValueError(
            "a paged pool holds a window as a KIND of layer (layer_kinds "
            "'sliding_attention' with sliding_window); "
            "GptConfig.attention_window, one window for all layers, is the "
            "unpaged paths'")
    if (cfg.has_state_layers or cfg.window_layers) and num_slots < 1:
        raise ValueError("init_kv_pool needs num_slots >= 1 for a config "
                         "whose layer_kinds has a linear_attention, a "
                         "short_conv or a sliding_attention layer")
    dtype = jnp.dtype(cfg.dtype) if dtype is None else jnp.dtype(dtype)

    pages = {"ring": num_slots * cfg.ring_pages(page_size) + 1,
             "pages": cfg.loop_steps * num_pages + 1}
    return [row.entry(cfg, num_slots) if row.table is None
            else row.entry(cfg, (pages[row.table], page_size), dtype,
                           flat=True)
            for row in map(KINDS.get, cfg.kinds)]


def land_prefill(cfg: GptConfig, caches, pools, page_size: int,
                 phys: jax.Array, slot=None, ring=None):
    """One prompt's ``caches`` (``GptLM.prefill``'s over a batch of one,
    from :func:`init_kv_cache`) written onto ``pools``
    (:func:`init_kv_pool`), each layer's entry by what addresses it
    (:data:`KINDS`): a row a slot whole onto row ``slot``, so that nothing
    of the row's last tenant survives; a ring's rows, the prompt's last
    (position p at ring row p % the ring's rows), onto the whole ring
    pages ``ring`` (the lane's ring pages this prompt reaches, in ring
    order); a run's onto the prompt's physical pages ``phys``, under a
    weight-shared loop onto the ``loop_steps`` runs of them.  A page the
    table does not name (the sentinel) drops."""
    def land(table, cache, pool):
        if table is None:
            return pool.at[slot].set(cache[0])
        pages, R = ring if table == "ring" else phys, cfg.loop_steps
        if R > 1:
            # A run of pages a loop step: [R, 1, P, G, D] lands on the R
            # runs of the prompt's pages.
            pages = loop_step_pages(
                phys[None, :], jnp.arange(R)[:, None], pool.shape[0],
                R).reshape(-1)
        # (A latent layer's rotated keys lie two tokens a row.)
        return pool.at[written_pages(pages, pool.shape[0])].set(
            paged_ops.pack_keys(
                (cache if R > 1 else cache[0]).reshape(
                    pages.shape[0], page_size, -1), pool.shape[1]),
            mode="drop")

    with profiling.region("cache.write"):
        return [tuple(land(KINDS[kind].table, c, p)
                      for c, p in zip(cache, pool))
                for kind, cache, pool in zip(cfg.kinds, caches, pools)]


@dataclasses.dataclass(frozen=True)
class PoolGeometry:
    """What a server of ``cfg`` has to know of its pools to size, fill and
    step them, and what its spans say of them (:func:`pool_geometry`)."""

    row_bytes: int           # a cached token's, over the paged layers
    window_row_bytes: int    # and over the rings
    state_bytes: int         # a decode slot's rows beside the pages
    latent_row_bytes: int    # ``row_bytes`` where the rows are latent, else 0
    ring_pages: int          # pages of a slot's ring; 0 without such layers
    state_layers: int        # layers that keep a row a slot,
    conv_layers: int         # those of them that keep a tail alone
    sparse_layers: int       # layers whose MLP is routed experts
    route_ahead_layers: int  # those of them routed before their mixer runs
    window_layers: int       # layers whose pool is a ring
    loop_steps: int          # times the stack is applied to a token
    cache_rows: int          # rows a cached token holds: steps x paged layers
    # The collections ``GptLM.decode_paged`` is applied with mutable and
    # :func:`pack_step_output` reads, each a rider behind the tokens.
    riders: tuple

    @property
    def needs_live(self) -> bool:
        """Whether the step takes ``live`` [B]: a state row has no sentinel
        page, a routed token of an idle row no expert, and a loop's
        counters no lane to skip otherwise."""
        return bool(self.state_layers or self.riders)


def pool_geometry(cfg: GptConfig, page_size: int, dtype=None) -> PoolGeometry:
    """``cfg``'s pools in pages of ``page_size`` rows of ``dtype`` (the
    compute type when None), as :func:`init_kv_pool` makes them."""
    row_bytes = kv_row_bytes_per_token(cfg, dtype)
    state_layers = sum(kind in STATE_KINDS for kind in cfg.kinds)
    sparse_layers = sum(cfg.sparse_layers)
    return PoolGeometry(
        row_bytes=row_bytes,
        window_row_bytes=kv_row_bytes_per_token(cfg, dtype, window=True),
        state_bytes=state_bytes_per_slot(cfg),
        latent_row_bytes=row_bytes if cfg.latent_kv_rank else 0,
        ring_pages=cfg.ring_pages(page_size) if cfg.window_layers else 0,
        state_layers=state_layers, conv_layers=cfg.conv_layers,
        sparse_layers=sparse_layers,
        route_ahead_layers=sparse_layers * (cfg.router_input == "mixer_in"),
        window_layers=cfg.window_layers,
        loop_steps=cfg.loop_steps,
        cache_rows=cfg.loop_steps * (cfg.num_layers - state_layers),
        riders=("routing",) * bool(sparse_layers)
        + ("loop",) * (cfg.loop_steps > 1))


def pack_step_output(cfg: GptConfig, tokens: jax.Array, sown: dict,
                     live: jax.Array | None) -> jax.Array:
    """The decode step's ONE int32 array: ``tokens`` [B] and behind them
    what ``GptLM.decode_paged`` sowed into ``PoolGeometry.riders``, in the
    array the host fetches anyway (no second copy to wait for);
    :func:`unpack_step_output` takes it apart.  ``routing``: the routed
    layers' histograms [sparse layers x experts], live lanes only.
    ``loop``: two numbers a lane, the loop steps it ran and its expected
    exit step, the sum of t x (mass leaving at step t), float32 bit for
    bit in the array's int32; an idle lane reads 0 and 0.0."""
    out = tokens
    if "routing" in sown:
        counts = [sown["routing"][f"layer{i}"]["counts"][0]
                  for i, sparse in enumerate(cfg.sparse_layers) if sparse]
        out = jnp.concatenate([out, *counts])
    if "loop" in sown:
        loop = sown["loop"]
        masses = loop["exit_mass"][0][..., 0]                    # [R, B]
        at = jnp.arange(1, masses.shape[0] + 1, dtype=masses.dtype)
        expected = jnp.where(live, at @ masses, 0.0)
        out = jnp.concatenate([
            out, jnp.where(live, loop["steps_run"][0], 0),
            jax.lax.bitcast_convert_type(expected, jnp.int32)])
    return out


def unpack_step_output(cfg: GptConfig, out, lanes: int):
    """(tokens [lanes], the riders by name) of :func:`pack_step_output`'s
    array, fetched or on the device: ``routing_counts`` [sparse layers x
    experts]; ``loop_steps_run`` [lanes] and ``exit_step_expected`` [lanes]
    float32.  A model has the names its configuration gives it."""
    tokens, behind, riders = out[:lanes], out[lanes:], {}
    routed = sum(cfg.sparse_layers) * cfg.num_experts
    if routed:
        riders["routing_counts"] = behind[:routed].reshape(-1,
                                                           cfg.num_experts)
    if cfg.loop_steps > 1:
        ran, expected = behind[routed:].reshape(2, lanes)
        riders.update(loop_steps_run=ran,
                      exit_step_expected=expected.view(np.float32))
    return tokens, riders


def lm_loss(logits: jax.Array, tokens: jax.Array,
            label_smoothing: float = 0.0) -> tuple[jax.Array, jax.Array]:
    """Next-token cross-entropy over positions 0..S-2 predicting 1..S-1.

    ``logits``: [B, S, vocab] from ``GptLM(tokens)``; targets are the same
    token stream shifted left.  Returns (loss, next-token accuracy).
    ``label_smoothing`` mixes the targets with uniform (see ``mlm_loss``).
    """
    with profiling.region("loss"):
        pred = logits[:, :-1]
        targets = tokens[:, 1:]
        logp = jax.nn.log_softmax(pred, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        if label_smoothing > 0.0:
            ll = ((1.0 - label_smoothing) * ll
                  + label_smoothing * jnp.mean(logp, axis=-1))
        loss = -jnp.mean(ll)
        acc = jnp.mean((jnp.argmax(pred, -1) == targets).astype(jnp.float32))
        return loss, acc


def synthetic_lm_batch(seed: int, batch_size: int, seq_len: int,
                       cfg: GptConfig) -> dict:
    """Deterministic learnable byte stream: position-dependent affine bigram.

    ``x[t+1] = (3 * x[t] + t) % vocab`` with a random start and occasional
    noise tokens — a model must use both the previous token and its position,
    so a decoder learns it quickly while a unigram baseline cannot.
    """
    rng = np.random.default_rng(seed)
    vocab = cfg.vocab_size
    toks = np.empty((batch_size, seq_len), np.int32)
    toks[:, 0] = rng.integers(0, vocab, batch_size)
    for t in range(seq_len - 1):
        toks[:, t + 1] = (3 * toks[:, t] + t) % vocab
    noise = rng.random((batch_size, seq_len)) < 0.02
    toks = np.where(noise, rng.integers(0, vocab, toks.shape), toks)
    return {"tokens": toks.astype(np.int32)}


def _sort_descending(logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``logits`` [B, V] sorted high to low along the vocabulary, and the
    index each value came from: ``(sorted_logits, order)``.

    ONE two-operand sort carries the indices along with the keys, so the
    sorted values come out of the sort itself.  ``order`` is what
    ``jnp.argsort(-logits)`` returns (the same stable sort of the same
    keys, ties in index order) and the values are bit-equal to
    ``take_along_axis(logits, order)`` (negation is exact) without that
    element-wise gather over the vocabulary, which cost fifteen times
    the sort on a TPU v5e (PERF.md, PR 30).
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    neg_sorted, order = jax.lax.sort((-logits, iota), dimension=-1,
                                     num_keys=1, is_stable=True)
    return -neg_sorted, order


def sample_logits(step_logits: jax.Array, rng: jax.Array, *,
                  temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 0.0) -> jax.Array:
    """Sample next tokens from [B, V] logits with temperature / top-k / top-p.

    ``top_k > 0`` keeps only the k highest-logit tokens; ``top_p`` in (0, 1)
    keeps the smallest nucleus whose cumulative probability reaches it (the
    highest-probability token always survives).  Filters compose (k first).
    """
    logits = step_logits / jnp.maximum(temperature, 1e-6)
    neg = jnp.finfo(logits.dtype).min
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, neg, logits)
    if 0.0 < top_p < 1.0:
        sorted_logits, order = _sort_descending(logits)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        # Exclusive cumulative mass: the first token is always kept.
        keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        rows = jnp.arange(logits.shape[0])[:, None]
        keep = jnp.zeros_like(logits, bool).at[rows, order].set(keep_sorted)
        logits = jnp.where(keep, logits, neg)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def sample_logits_dynamic(step_logits: jax.Array, key: jax.Array,
                          temperature: jax.Array, top_k: jax.Array,
                          top_p: jax.Array) -> jax.Array:
    """Traced-parameter :func:`sample_logits`: temperature / top-k /
    top-p are per-row ARRAYS [B], so ONE compiled program — e.g. the
    exported serving artifact's sampled decode — serves any mix of
    sampling configs without recompiling (and a micro-batch can carry a
    different config per request).

    Same filter semantics: ``top_k[b] > 0`` keeps the k highest logits,
    ``0 < top_p[b] < 1`` keeps the smallest nucleus reaching that mass
    (highest-probability token always kept), filters compose.  Rows with
    ``temperature[b] <= 0`` take the greedy argmax.  Selection is
    Gumbel-max over the filtered scaled logits (= categorical sampling),
    computed in sorted space: one sort serves the k-threshold, the
    nucleus mass, and the final pick.

    The sampler does no vocabulary-wide work its inputs do not ask for.
    A call whose rows are ALL greedy takes the argmax and nothing else:
    the sorted-space path lies under a ``lax.cond`` on
    ``any(temperature > 0)``, decided on the device inside the one
    compiled program, so the caller compiles nothing twice and chooses
    nothing.  (Keep the call outside any ``vmap``: under one the cond
    becomes a select and both arms run.)  One sampled row runs the whole
    path for the batch, as before; its greedy rows still take the argmax.
    Either way the tokens are those the unconditional form returns, bit
    for bit.

    ``key``: a TYPED prng key — scalar (one draw for the whole batch) or
    [B] (one key per row).  Per-row keys are what make a served sample
    reproducible regardless of MICRO-BATCH COMPOSITION: each row's noise
    then depends only on its own key, never on which other requests
    shared the device call (see ``export_gpt_decode``'s key schedule).
    """
    V = step_logits.shape[-1]

    def greedy_arm():
        return jnp.argmax(step_logits, axis=-1).astype(jnp.int32)

    def sampled_arm():
        t = jnp.maximum(temperature, 1e-6)[:, None]
        sl, order = _sort_descending(step_logits)                # [B, V]
        sl = sl / t
        probs = jax.nn.softmax(sl, axis=-1)
        idx = jnp.arange(V)[None, :]
        keep_k = (top_k[:, None] <= 0) | (idx < top_k[:, None])
        p = top_p[:, None]
        excl = jnp.cumsum(probs, axis=-1) - probs   # exclusive mass
        keep_p = ~((p > 0.0) & (p < 1.0)) | (excl < p)
        neg = jnp.finfo(sl.dtype).min
        filt = jnp.where(keep_k & keep_p, sl, neg)
        if key.ndim == 1:   # typed keys: ndim 1 == one key per row
            u = jax.vmap(lambda k: jax.random.uniform(
                k, (V,), minval=1e-20, maxval=1.0))(key)
        else:
            u = jax.random.uniform(key, filt.shape, minval=1e-20,
                                   maxval=1.0)
        gumbel = -jnp.log(-jnp.log(u))
        samp_sorted = jnp.argmax(filt + gumbel, axis=-1)
        sampled = jnp.take_along_axis(order, samp_sorted[:, None],
                                      axis=-1)[:, 0]
        return jnp.where(temperature > 0.0, sampled, greedy_arm())

    return jax.lax.cond(jnp.any(temperature > 0.0), sampled_arm, greedy_arm)


def _next_token(step_logits, rng, temperature, top_k, top_p):
    """Shared greedy-or-sampled selection for both decode paths."""
    if temperature > 0.0:
        rng, key = jax.random.split(rng)
        return sample_logits(step_logits, key, temperature=temperature,
                             top_k=top_k, top_p=top_p), rng
    return jnp.argmax(step_logits, -1).astype(jnp.int32), rng


def _validate_sampling(model, total, temperature, top_p, rng):
    if total > model.cfg.max_position:
        raise ValueError(f"prompt + num_tokens = {total} exceeds "
                         f"max_position {model.cfg.max_position}")
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")


def _validate_eos(model, eos_id):
    if eos_id is not None and not 0 <= eos_id < model.cfg.vocab_size:
        raise ValueError(f"eos_id must be in [0, {model.cfg.vocab_size}), "
                         f"got {eos_id}")


def generate(model: GptLM, params, prompt: jax.Array, num_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             rng: jax.Array | None = None,
             eos_id: int | None = None) -> jax.Array:
    """Autoregressive decoding: greedy (``temperature=0``) or sampled
    (temperature with optional top-k / nucleus top-p filtering).

    ``prompt``: [B, P] token ids.  Returns [B, P + num_tokens].  Static
    shapes throughout (XLA compiles one program): the sequence is padded to
    its final length up front and each iteration runs the full forward —
    causality guarantees positions < t ignore the padding.  O(S²) per token;
    fine for the mini scale this model targets (a KV-cache decode path is
    the optimization when generation becomes a workload).

    ``eos_id``: per-sequence stop token.  A row that emits it stops
    changing (later positions are ``eos_id`` padding), and the loop exits
    early once EVERY row has stopped — a ``lax.while_loop`` with the same
    static shapes, so mixed-length batches pay for the longest row only.
    """
    B, P = prompt.shape
    total = P + num_tokens
    _validate_sampling(model, total, temperature, top_p, rng)
    _validate_eos(model, eos_id)
    toks = jnp.zeros((B, total), jnp.int32).at[:, :P].set(prompt)
    rng = jax.random.PRNGKey(0) if rng is None else rng

    def step(t, toks, rng, done):
        logits = model.apply({"params": params}, toks)  # [B, total, V]
        step_logits = jax.lax.dynamic_slice_in_dim(
            logits, t - 1, 1, axis=1)[:, 0]  # [B, V] — predictor position
        nxt, rng = _next_token(step_logits, rng, temperature, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        toks = jax.lax.dynamic_update_slice_in_dim(
            toks, nxt[:, None], t, axis=1)
        return toks, rng, done

    if eos_id is None:
        def body(t, carry):
            toks, rng = carry
            toks, rng, _ = step(t, toks, rng, None)
            return toks, rng
        toks, _ = jax.lax.fori_loop(P, total, body, (toks, rng))
        return toks

    def cond(carry):
        t, _, _, done = carry
        return (t < total) & ~jnp.all(done)

    def body(carry):
        t, toks, rng, done = carry
        toks, rng, done = step(t, toks, rng, done)
        return t + 1, toks, rng, done

    # Pre-fill the generated region with eos padding so positions past an
    # early all-done exit read as "stopped", not as token 0.
    toks = toks.at[:, P:].set(eos_id)
    _, toks, _, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(P), toks, rng, jnp.zeros((B,), bool)))
    return toks


def _decode_setup(model: GptLM, params, quantize: str, kv_dtype: str):
    """Shared decode-path config: validates quantize/kv_dtype and returns
    ``(get_params, cache_dtype)`` — the int8 weight closure and the KV-cache
    dtype — used by both :func:`generate_cached` and
    :func:`beam_search_cached` (one recipe, shared with the serving engine
    through :mod:`..ops.quant`'s prepare/load pair)."""
    from ..ops.quant import (load_inference_tree, prepare_inference_tree,
                             resolve_kv_dtype)
    cache_dtype = resolve_kv_dtype(kv_dtype)
    tree = prepare_inference_tree(params, quantize)
    if quantize == "int8":
        tree = jax.tree.map(jnp.asarray, tree)
    compute_dtype = jnp.dtype(model.cfg.dtype)

    def get_params():
        return load_inference_tree(tree, quantize, compute_dtype)
    return get_params, cache_dtype


def generate_cached(model: GptLM, params, prompt: jax.Array, num_tokens: int,
                    *, temperature: float = 0.0, top_k: int = 0,
                    top_p: float = 0.0,
                    rng: jax.Array | None = None,
                    quantize: str = "",
                    kv_dtype: str = "",
                    eos_id: int | None = None) -> jax.Array:
    """KV-cached autoregressive decoding — O(total_len) work per token.

    Same contract as :func:`generate` (greedy when ``temperature=0``), but
    each step attends against per-layer K/V caches instead of re-running the
    full O(S²) forward: the prompt prefills the caches in ONE parallel
    causal pass (:meth:`GptLM.prefill`), then the generation loop feeds
    each new token back through :meth:`GptLM.decode_step`.  Static shapes
    throughout; one compiled program.

    ``quantize="int8"`` stores the weight matrices as per-channel int8 in
    HBM and dequantizes inside each traced step (XLA fuses the multiply
    into the matmul) — decode is memory-bound, so halving the weight bytes
    is the decode-rate lever (see :mod:`..ops.quant`).

    ``kv_dtype="float8"`` keeps the KV caches in ``float8_e4m3fn`` (half of
    bf16's bytes; upcast on read) — the same bandwidth lever for the cache
    side, which dominates at long contexts.

    ``eos_id`` stops each row at its own terminator and exits the decode
    loop early once every row has stopped (see :func:`generate`); the
    per-step KV append still runs for already-stopped rows (their writes
    are eos padding) so shapes stay static.
    """
    model.cfg.refuse_state_layers("generate_cached")
    B, P = prompt.shape
    total = P + num_tokens
    _validate_sampling(model, total, temperature, top_p, rng)
    _validate_eos(model, eos_id)
    get_params, cache_dtype = _decode_setup(model, params, quantize, kv_dtype)
    rng = jax.random.PRNGKey(0) if rng is None else rng
    caches = init_kv_cache(model.cfg, B, total, dtype=cache_dtype)

    def step_fn(token, caches, position):
        return model.apply({"params": get_params()}, token, caches, position,
                           method=GptLM.decode_step)

    # Parallel prefill: the whole prompt in ONE causal forward (the same
    # math `generate` uses), not P sequential decode steps — long prompts
    # cost one MXU-batched pass instead of an O(P) scan.
    last_logits, caches = model.apply(
        {"params": get_params()}, prompt, caches, method=GptLM.prefill)

    toks = jnp.zeros((B, total), jnp.int32).at[:, :P].set(prompt)

    def step(t, toks, last_logits, caches, rng, done):
        nxt, rng = _next_token(last_logits, rng, temperature, top_k, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        toks = jax.lax.dynamic_update_slice_in_dim(
            toks, nxt[:, None], t, axis=1)
        last_logits, caches = step_fn(nxt, caches, t)
        return toks, last_logits, caches, rng, done

    if eos_id is None:
        def body(t, carry):
            toks, last_logits, caches, rng = carry
            toks, last_logits, caches, rng, _ = step(
                t, toks, last_logits, caches, rng, None)
            return toks, last_logits, caches, rng

        toks, _, _, _ = jax.lax.fori_loop(P, total, body,
                                          (toks, last_logits, caches, rng))
        return toks

    def cond(carry):
        t = carry[0]
        done = carry[-1]
        return (t < total) & ~jnp.all(done)

    def body(carry):
        t, toks, last_logits, caches, rng, done = carry
        toks, last_logits, caches, rng, done = step(
            t, toks, last_logits, caches, rng, done)
        return t + 1, toks, last_logits, caches, rng, done

    toks = toks.at[:, P:].set(eos_id)
    _, toks, _, _, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.int32(P), toks, last_logits, caches, rng,
         jnp.zeros((B,), bool)))
    return toks


def beam_search_cached(model: GptLM, params, prompt: jax.Array,
                       num_tokens: int, *, beam_size: int,
                       quantize: str = "",
                       kv_dtype: str = "",
                       eos_id: int | None = None,
                       length_penalty: float = 1.0
                       ) -> tuple[jax.Array, jax.Array]:
    """Beam search over the KV-cached decode path.

    Classic width-``beam_size`` search: every step extends each live beam
    with every vocabulary token, keeps the ``beam_size`` highest cumulative
    log-probabilities per batch row, and reorders the K/V caches to the
    surviving beams' parents.  Greedy decoding is the ``beam_size=1``
    special case; larger widths can only raise the returned sequence
    log-probability.

    ``eos_id``: a beam that emits it is FROZEN — its continuation
    distribution collapses to "emit eos at logp 0", so its cumulative score
    stops changing, its tokens stop growing (later positions are eos
    padding), and it keeps competing in the top-K pool at its final score.
    The loop exits early once every beam of every row is frozen.  Final
    selection divides each beam's score by the GNMT length penalty
    ``((5 + gen_len) / 6) ** length_penalty`` so short finished beams and
    long live ones compare fairly (with no eos all lengths are equal and
    the penalty cancels — identical to the fixed-length search).  A frozen
    beam CAN still be displaced from the pool by a live beam that
    overtakes it; the returned logprob is the selected beam's raw
    cumulative score.

    ``quantize``/``kv_dtype`` mean what they do in :func:`generate_cached`.
    Returns ``(tokens [B, P + num_tokens], logprob [B])`` — the best beam
    per batch row and its cumulative generated-token log-probability.
    """
    model.cfg.refuse_state_layers("beam_search_cached")
    B, P = prompt.shape
    K = beam_size
    total = P + num_tokens
    _validate_sampling(model, total, 0.0, 0.0, None)
    _validate_eos(model, eos_id)
    if K < 1:
        raise ValueError(f"beam_size must be >= 1, got {K}")
    if K > model.cfg.vocab_size:
        raise ValueError(
            f"beam_size must be <= vocab_size ({model.cfg.vocab_size}), "
            f"got {K}: the first top-k over the vocabulary cannot seed "
            f"more beams than there are tokens")
    if num_tokens < 1:
        raise ValueError(f"num_tokens must be >= 1, got {num_tokens}")
    if length_penalty <= 0.0:
        raise ValueError(f"length_penalty must be > 0, got {length_penalty}")
    get_params, cache_dtype = _decode_setup(model, params, quantize, kv_dtype)

    V = model.cfg.vocab_size
    NEG = jnp.float32(-1e9)

    # Prefill at batch B, then tile every cache K-fold to [B*K, ...]: beams
    # of one batch row are contiguous (row b's beams at b*K .. b*K+K-1).
    caches = init_kv_cache(model.cfg, B, total, dtype=cache_dtype)
    last_logits, caches = model.apply(
        {"params": get_params()}, prompt, caches, method=GptLM.prefill)
    caches = jax.tree.map(lambda c: jnp.repeat(c, K, axis=0), caches)

    # First step seeds the beams with the top-K distinct first tokens.
    logp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)
    scores, first = jax.lax.top_k(logp0, K)           # [B, K]
    toks = jnp.zeros((B * K, total), jnp.int32)
    toks = toks.at[:, :P].set(jnp.repeat(prompt, K, axis=0))
    if eos_id is not None:
        toks = toks.at[:, P + 1:].set(eos_id)
    toks = toks.at[:, P].set(first.reshape(B * K))
    done = (first == eos_id) if eos_id is not None else None  # [B, K]
    gen_len = jnp.ones((B, K), jnp.int32)

    def step_fn(token, caches, position):
        return model.apply({"params": get_params()}, token, caches, position,
                           method=GptLM.decode_step)

    last_logits, caches = step_fn(toks[:, P], caches, jnp.int32(P))

    def body(t, toks, scores, last_logits, caches, done, gen_len):
        logp = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(B, K, V)
        if eos_id is not None:
            # Frozen continuation for finished beams: only "emit eos" at
            # logp 0, so the beam rides along at a constant score.
            frozen = jnp.full((V,), NEG).at[eos_id].set(0.0)
            logp = jnp.where(done[..., None], frozen, logp)
        # [B, K*V] joint scores; top-K picks (parent beam, token) pairs.
        joint = (scores[..., None] + logp).reshape(B, K * V)
        scores, idx = jax.lax.top_k(joint, K)          # [B, K]
        parent = idx // V                              # [B, K] beam index
        token = (idx % V).astype(jnp.int32)
        flat_parent = (jnp.arange(B)[:, None] * K + parent).reshape(B * K)
        toks = jnp.take(toks, flat_parent, axis=0)
        caches = jax.tree.map(
            lambda c: jnp.take(c, flat_parent, axis=0), caches)
        gen_len = jnp.take_along_axis(gen_len, parent, axis=1)
        if eos_id is not None:
            done = jnp.take_along_axis(done, parent, axis=1)
            gen_len = jnp.where(done, gen_len, gen_len + 1)
            done = done | (token == eos_id)
        else:
            gen_len = gen_len + 1
        flat_token = token.reshape(B * K)
        toks = jax.lax.dynamic_update_slice_in_dim(
            toks, flat_token[:, None], t, axis=1)
        last_logits, caches = step_fn(flat_token, caches, t)
        return toks, scores, last_logits, caches, done, gen_len

    if eos_id is None:
        def fori_body(t, carry):
            toks, scores, last_logits, caches, gen_len = carry
            toks, scores, last_logits, caches, _, gen_len = body(
                t, toks, scores, last_logits, caches, None, gen_len)
            return toks, scores, last_logits, caches, gen_len

        toks, scores, _, _, gen_len = jax.lax.fori_loop(
            P + 1, total, fori_body,
            (toks, scores, last_logits, caches, gen_len))
    else:
        def cond(carry):
            t = carry[0]
            done = carry[-2]
            return (t < total) & ~jnp.all(done)

        def while_body(carry):
            t, toks, scores, last_logits, caches, done, gen_len = carry
            toks, scores, last_logits, caches, done, gen_len = body(
                t, toks, scores, last_logits, caches, done, gen_len)
            return t + 1, toks, scores, last_logits, caches, done, gen_len

        _, toks, scores, _, _, _, gen_len = jax.lax.while_loop(
            cond, while_body, (jnp.int32(P + 1), toks, scores, last_logits,
                               caches, done, gen_len))

    # GNMT length penalty: neutral when every beam has the same length.
    lp = ((5.0 + gen_len.astype(jnp.float32)) / 6.0) ** length_penalty
    best = jnp.argmax(scores / lp, axis=-1)            # [B]
    flat_best = jnp.arange(B) * K + best
    return jnp.take(toks, flat_best, axis=0), jnp.take_along_axis(
        scores, best[:, None], axis=-1)[:, 0]


def spec_tree(spec_k: int, branch_len: int = 0):
    """Static draft-tree arrays for tree-verified speculation.

    The tree is a MAIN chain of ``spec_k - branch_len`` nodes (node 0 is
    the known-correct pending token, node i extends node i-1) plus, when
    ``branch_len > 0``, ONE alternate branch forking at the root: the
    continuation after the tail gram's SECOND-most-recent occurrence —
    the drafter's other candidate at the first uncertain position (an
    ambiguous n-gram has exactly these competing continuations).  When
    the main chain's first draft is wrong, the branch can still carry
    multi-token acceptance instead of collapsing the round to pending +
    correction.

    Returns ``(depths [K], anc [K, K], parent [K], path [K, K])``:
    node depths below the frontier, the ancestor-or-self matrix (the tree
    attention mask), each node's parent (-1 for the root), and
    ``path[i, d]`` = the ancestor of node i at depth d (-1 past its own
    depth) — the table acceptance uses to gather the winning root path.
    """
    K = int(spec_k)
    branch_len = int(branch_len)
    main = K - branch_len
    if main < 2 and K >= 2:
        raise ValueError(f"spec_tree needs a main chain of >= 2 nodes; "
                         f"spec_k={K} branch_len={branch_len}")
    parent = [-1] + list(range(main - 1))
    if branch_len:
        parent += [0] + list(range(main, K - 1))
    depth = np.zeros(K, np.int32)
    anc = np.zeros((K, K), bool)
    path = np.full((K, K), -1, np.int32)
    for i in range(K):
        chain = []
        j = i
        while j >= 0:
            chain.append(j)
            j = parent[j]
        depth[i] = len(chain) - 1
        for j in chain:
            anc[i, j] = True
            path[i, depth[j]] = j
    return depth, anc, np.asarray(parent, np.int32), path


def fixup_tree_caches(caches, positions: jax.Array, sel: jax.Array,
                      accept: jax.Array):
    """Compact the accepted root path's K/V down to slot == position.

    Tree verification stores node i's K/V at slot ``positions[b]+i``
    while its LOGICAL position is ``positions[b]+depth(i)``; once a path
    is accepted, every later round assumes slot == absolute position, so
    the winning nodes' rows are gathered from their tree slots and
    rewritten at ``positions[b] .. positions[b]+accept[b]-1``.  K/V of a
    token depend only on its embedding, position and ancestors — all of
    which the tree mask reproduced exactly — so the moved rows are
    bit-identical to what sequential decode would have written.  ``sel``
    [B, K]: accepted node index per depth (clamped junk past ``accept``
    is masked by the OOB-drop scatter)."""
    B, K = sel.shape
    rows = jnp.arange(B)[:, None]
    write = jnp.arange(K)[None, :] < accept[:, None]
    out = []
    for k_cache, v_cache in caches:
        M = k_cache.shape[1]
        src_idx = jnp.clip(positions[:, None] + sel, 0, M - 1)
        dst = jnp.where(write,
                        positions[:, None] + jnp.arange(K)[None, :], M)

        def move(cache):
            srcv = jnp.take_along_axis(cache, src_idx[..., None, None],
                                       axis=1)
            return cache.at[rows, dst].set(srcv, mode="drop")
        out.append((move(k_cache), move(v_cache)))
    return out


def generate_cached_speculative(model: GptLM, params, prompt: jax.Array,
                                num_tokens: int, *, spec_k: int = 8,
                                ngram: int = 3,
                                eos_id: int | None = None,
                                quantize: str = "",
                                kv_dtype: str = "",
                                fallback_rounds: int = 8,
                                fallback_accept: float = 1.5
                                ) -> tuple[jax.Array, dict]:
    """Greedy decoding with speculative verification — the same greedy
    sequence as :func:`generate_cached`, often in far fewer device calls.
    (Equality holds up to floating-point tie-breaking: the chunked and
    sequential paths are different XLA programs whose logits agree to
    ~1e-5, so an exact argmax tie could in principle resolve differently;
    every accepted token is by construction the verification pass's own
    argmax.)

    Each round feeds ONE chunk of ``spec_k`` tokens per row through
    :meth:`GptLM.decode_chunk`: the row's known-correct next token followed
    by ``spec_k - 1`` prompt-lookup drafts from the shared incremental
    n-gram index (:class:`..models.drafting.NGramIndex` — the same
    drafter, table and hash the device variant uses, updated only with
    the tokens committed last round).  The chunk's logits verify every
    draft at once (MXU-batched); the longest draft prefix matching the
    greedy argmaxes is accepted, plus the free correction/bonus token the
    last accepted logits provide.  Rejected speculative cache writes are
    masked by position until real tokens overwrite them (full-length
    caches make this safe — the windowed ring cache is rejected).

    Greedy only by design: acceptance compares against argmax, which makes
    the output provably equal to plain greedy decoding.

    **Auto-fallback** (VERDICT r3 #6): prompt-lookup drafting only pays on
    text whose n-grams repeat; on non-repetitive text acceptance degrades
    toward 1 token/round and each round still pays a K-wide chunk pass —
    strictly worse than plain cached decode, whose one dispatch also
    yields one token PER ROW.  After ``fallback_rounds`` rounds with
    cumulative PER-ROW acceptance (generated / rounds / batch) below
    ``fallback_accept`` tokens/round/row, the generation abandons
    drafting and finishes with an on-device sequential decode loop over
    the SAME caches (per-row frontiers, one dispatch for the whole
    remainder).  The output is the
    plain greedy sequence either way.  ``fallback_rounds=0`` disables the
    check.

    **When to use which variant** (measured, BENCH r6 cost model): this
    host loop pays one dispatch PER ROUND, so it only wins where rounds
    are much rarer than tokens AND the link is cheap; the on-device
    variant (:func:`generate_cached_speculative_device`) runs the whole
    draft→verify→accept loop in one dispatch with cached compiled
    programs, tree drafting and adaptive K, and is the better default
    everywhere — local chips included (``--gen_speculative_device`` now
    defaults to true).  This loop remains the measured-envelope
    reference: its per-round host stats and explicit fallback are the
    instrumented twin of the device variant's adaptive K.

    Returns ``(tokens [B, P + num_tokens], stats)`` with stats
    ``{"rounds", "tokens_generated", "mean_accepted_per_round",
    "fallback_at_round"}`` — the speedup mechanism made measurable
    (tokens/round > 1 means the chunk replaced that many sequential
    decode steps; ``fallback_at_round`` is None when drafting paid for
    the whole generation).
    """
    model.cfg.refuse_state_layers("generate_cached_speculative")
    B, P = prompt.shape
    total = P + num_tokens
    _validate_sampling(model, total, 0.0, 0.0, None)
    _validate_eos(model, eos_id)
    if model.cfg.attention_window:
        raise ValueError(
            "speculative decoding needs the full-length cache; the windowed "
            "ring cache cannot mask rejected speculative writes")
    if spec_k < 2:
        raise ValueError(f"spec_k must be >= 2, got {spec_k}")
    if num_tokens < 1:
        raise ValueError(f"num_tokens must be >= 1, got {num_tokens}")
    get_params, cache_dtype = _decode_setup(model, params, quantize, kv_dtype)

    caches = init_kv_cache(model.cfg, B, total, dtype=cache_dtype)
    last_logits, caches = model.apply(
        {"params": get_params()}, prompt, caches, method=GptLM.prefill)

    @jax.jit
    def verify(tokens, caches, positions):
        logits, caches = model.apply({"params": get_params()}, tokens,
                                     caches, positions,
                                     method=GptLM.decode_chunk)
        # argmax ON DEVICE: the host loop needs [B, K] token ids, not
        # [B, K, vocab] float logits over the transfer boundary.
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

    @jax.jit
    def finish_plain(tokens, positions, done0, caches, steps):
        """Sequential per-row decode of the remainder, entirely on device:
        ``tokens`` [B] are frontier tokens at ``positions`` [B]; emits up
        to ``num_tokens`` tokens per row (host trims to each row's
        budget).  Rows in ``done0`` emit eos padding."""
        out0 = jnp.zeros((B, num_tokens), jnp.int32)

        def body(i, carry):
            tok, pos, done_m, out = carry[:4]
            ch = carry[4]
            logits, ch = model.apply({"params": get_params()}, tok[:, None],
                                     ch, pos, method=GptLM.decode_chunk)
            nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            if eos_id is not None:
                nxt = jnp.where(done_m, eos_id, nxt)
                done_m = done_m | (nxt == eos_id)
            out = jax.lax.dynamic_update_slice_in_dim(out, nxt[:, None], i,
                                                      axis=1)
            return nxt, pos + jnp.int32(1), done_m, out, ch

        _, _, _, out, caches = jax.lax.fori_loop(
            0, steps, body,
            (tokens, positions, done0, out0, caches))
        return out, caches

    from . import drafting as drafting_lib

    K = spec_k
    toks = np.zeros((B, total), np.int32)
    toks[:, :P] = np.asarray(prompt)
    lens = np.full(B, P)                      # per-row frontier
    pending = np.argmax(np.asarray(last_logits), axis=-1).astype(np.int32)
    done = np.zeros(B, bool)
    indexes = [drafting_lib.NGramIndex(ngram) for _ in range(B)]
    rounds = 0
    fallback_at = None
    while not np.all(done | (lens >= total)):
        if (fallback_rounds and rounds >= fallback_rounds
                and (np.sum(lens - P) / rounds / B) < fallback_accept):
            fallback_at = rounds
            break
        chunk = np.zeros((B, K), np.int32)
        for b in range(B):
            chunk[b, 0] = pending[b]
            # Index the tokens committed since last round (incremental),
            # then draft for the tail ending in the pending token.
            indexes[b].update(toks[b], int(lens[b]))
            row = np.concatenate([toks[b, :lens[b]], pending[b:b + 1]])
            chunk[b, 1:] = indexes[b].draft(row, int(lens[b]) + 1, K - 1)
        # Rows already done still ride the batch (their writes land past
        # their frontier and are never accepted).
        greedy_dev, caches = verify(jnp.asarray(chunk), caches,
                                    jnp.asarray(lens, jnp.int32))
        greedy = np.asarray(greedy_dev)                   # [B, K]
        rounds += 1
        for b in range(B):
            if done[b] or lens[b] >= total:
                continue
            budget = total - lens[b]
            # chunk[b, 0] is known-correct; drafts i accept while they
            # equal the greedy continuation of the previous token.
            accept = 1
            while (accept < min(K, budget)
                   and chunk[b, accept] == greedy[b, accept - 1]
                   and not (eos_id is not None
                            and chunk[b, accept - 1] == eos_id)):
                accept += 1
            wrote = chunk[b, :accept]
            toks[b, lens[b]:lens[b] + accept] = wrote
            lens[b] += accept
            pending[b] = greedy[b, accept - 1]
            if eos_id is not None and eos_id in wrote:
                hit = int(np.flatnonzero(wrote == eos_id)[0])
                lens[b] = lens[b] - accept + hit + 1
                done[b] = True
        done |= lens >= total
    spec_generated = int(np.sum(lens - P))

    if fallback_at is not None and not np.all(done | (lens >= total)):
        # Plain sequential finish over the same caches.  The pending token
        # is known-correct — place it, then decode the rest on device.
        for b in range(B):
            if done[b] or lens[b] >= total:
                continue
            toks[b, lens[b]] = pending[b]
            lens[b] += 1
            if eos_id is not None and pending[b] == eos_id:
                done[b] = True
        live = ~(done | (lens >= total))
        if np.any(live):
            steps = int(np.max(np.where(live, total - lens, 0)))
            frontier = toks[np.arange(B), np.maximum(lens - 1, 0)]
            out, caches = finish_plain(
                jnp.asarray(frontier.astype(np.int32)),
                jnp.asarray((lens - 1).astype(np.int32)),
                jnp.asarray(done), caches, jnp.int32(steps))
            out = np.asarray(out)
            for b in range(B):
                if not live[b]:
                    continue
                wrote = out[b, :total - lens[b]]
                if eos_id is not None and eos_id in wrote:
                    hit = int(np.flatnonzero(wrote == eos_id)[0])
                    wrote = wrote[:hit + 1]
                    done[b] = True
                toks[b, lens[b]:lens[b] + len(wrote)] = wrote
                lens[b] += len(wrote)

    if eos_id is not None:
        for b in range(B):
            toks[b, lens[b]:] = eos_id
    generated = int(np.sum(lens - P))
    stats = {"rounds": rounds, "tokens_generated": generated,
             "mean_accepted_per_round": round(
                 spec_generated / max(rounds, 1), 2),
             "fallback_at_round": fallback_at}
    return jnp.asarray(toks), stats


#: Chunk width of the adaptive loop's SMALL body — just the pending token
#: plus one draft, so a low-acceptance round costs barely more than a
#: plain decode step while still catching the occasional 2-token burst.
_SPEC_K_SMALL = 2


@functools.lru_cache(maxsize=16)
def _spec_device_program(cfg: GptConfig, B: int, P: int, num_tokens: int,
                         spec_k: int, branch_len: int, ngram: int,
                         eos_id: int | None, quantize: str, kv_dtype: str,
                         adaptive: bool, adapt_threshold: float,
                         probe_every: int):
    """Build (once) and cache the compiled speculative-decode program.

    The pre-r6 implementation defined its ``jax.jit`` closures INSIDE the
    generate call, so every invocation paid a full retrace + recompile —
    ~3 s at the bench scale, which is most of why BENCH r4 measured the
    device variant at 0.14x plain.  Programs are now keyed on everything
    shape- or trace-relevant (config, geometry, tree, knobs) and the
    param tree rides as a jit ARGUMENT, so repeated generations — and the
    bench's timed calls — reuse one compilation.
    """
    from . import drafting
    from ..ops.quant import load_inference_tree, resolve_kv_dtype

    model = GptLM(cfg)
    cache_dtype = resolve_kv_dtype(kv_dtype)
    compute = jnp.dtype(cfg.dtype)
    total = P + num_tokens
    K = spec_k
    main = K - branch_len
    n = ngram
    depths_np, anc_np, parent_np, path_np = spec_tree(K, branch_len)
    depths, anc = jnp.asarray(depths_np), jnp.asarray(anc_np)
    parent, path = jnp.asarray(parent_np), jnp.asarray(path_np)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    rows = jnp.arange(B)

    def apply(tree, *args, method):
        params = load_inference_tree(tree, quantize, compute)
        return model.apply({"params": params}, *args, method=method)

    def commit_pending(toks, lens, pending, done):
        # Commit the known-correct pending token at each live frontier.
        # Masked-out writes are routed OUT OF BOUNDS and dropped — never
        # clip-and-write-identity: clipped duplicate indices race the
        # real write in one scatter (last-enumerated wins), which is
        # exactly how the final slot got clobbered in the first cut of
        # this loop.
        keep = (~done) & (lens < total)
        toks = toks.at[rows, jnp.where(keep, lens, total)].set(
            pending, mode="drop")
        return toks, keep

    def finish_round(carry, toks, lens, caches, last, prev, keep, greedy,
                     write, accept, tok_acc, best, full_round,
                     branch_hit):
        """Shared round tail: token writes, pending hand-off, eos,
        incremental two-table index update, acceptance EMA."""
        done, ema = carry[3], carry[7]
        rounds, rounds_full, bhits = carry[8], carry[9], carry[10]
        kw = write.shape[1]
        pos = jnp.where(write, lens[:, None] + jnp.arange(kw)[None, :],
                        total)
        toks = toks.at[rows[:, None], pos].set(tok_acc, mode="drop")
        pending = jnp.take_along_axis(greedy, best[:, None], axis=1)[:, 0]
        hit_eos = (eos >= 0) & jnp.any(
            jnp.where(write, tok_acc == eos, False), axis=1)
        new_lens = lens + accept
        # O(accept) index maintenance: only the grams the just-committed
        # tokens created are inserted (span = chunk width covers them).
        last, prev = drafting.index_update2(last, prev, toks, lens,
                                            new_lens, n=n, span=kw)
        done = done | hit_eos | (new_lens >= total)
        live = jnp.sum(keep.astype(jnp.int32))
        acc_mean = jnp.sum(accept).astype(jnp.float32) / jnp.maximum(
            live, 1).astype(jnp.float32)
        ema = jnp.where(live > 0, 0.7 * ema + 0.3 * acc_mean, ema)
        return (toks, new_lens, pending, done, caches, last, prev,
                ema, rounds + 1, rounds_full + full_round,
                bhits + branch_hit)

    def tree_round(carry, tree):
        """Full-width round: tree-drafted chunk, tree verify, longest
        accepted root path, cache compaction."""
        toks, lens, pending, done, caches, last, prev, *_ = carry
        toks, keep = commit_pending(toks, lens, pending, done)
        eff = lens + keep.astype(lens.dtype)
        tail = drafting.tail_gram(toks, eff, n=n)
        parts = [pending[:, None]]
        if main > 1:
            parts.append(drafting.index_draft(last, toks, tail, eff,
                                              n=n, k=main - 1))
        if branch_len:
            # Branch = the continuation after the SECOND-most-recent
            # occurrence of the same tail gram — the drafter's other
            # candidate at an ambiguous n-gram (e.g. the two "the "
            # continuations of a periodic phrase), which is where a
            # single linear draft collapses to pending + correction.
            parts.append(drafting.index_draft(prev, toks, tail, eff,
                                              n=n, k=branch_len))
        chunk = jnp.concatenate(parts, axis=1)                   # [B, K]
        logits, caches = apply(tree, chunk, caches,
                               lens.astype(jnp.int32), depths, anc,
                               method=GptLM.decode_chunk)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [B, K]
        # Node i matches iff its token is the greedy continuation of its
        # parent and no accepted eos precedes it; the root (the committed
        # pending token) always matches.
        pidx = jnp.maximum(parent, 0)[None, :]
        match = ((chunk == jnp.take_along_axis(greedy, pidx, axis=1))
                 & (jnp.take_along_axis(chunk, pidx, axis=1) != eos))
        match = match.at[:, 0].set(True)
        # A node is ACCEPTED iff every ancestor (incl. itself) matches.
        chain = jnp.all(jnp.where(anc[None, :, :], match[:, None, :],
                                  True), axis=-1)                # [B, K]
        budget = total - lens
        # A node needs BOTH its depth and its slot index inside the
        # budget: node i writes K/V at slot lens+i, and a write past the
        # cache end was dropped — accepting such a branch node would make
        # fixup_tree_caches commit a junk row (branch indices exceed
        # their depth, so depth-in-budget alone does not cover this).
        eligible = (chain & (depths[None, :] < budget[:, None])
                    & (jnp.arange(K)[None, :] < budget[:, None]))
        score = jnp.where(eligible, depths[None, :], -1)
        # Deepest accepted node; argmax's first-wins tie-break prefers
        # the main chain (lower node index at equal depth), minimizing
        # compaction churn.
        best = jnp.argmax(score, axis=1).astype(jnp.int32)
        accept = jnp.take_along_axis(score, best[:, None],
                                     axis=1)[:, 0] + 1
        accept = jnp.where(keep, accept, 0)
        sel = jnp.take(path, best, axis=0)                       # [B, K]
        tok_acc = jnp.take_along_axis(chunk, jnp.maximum(sel, 0), axis=1)
        write = jnp.arange(K)[None, :] < accept[:, None]
        # Move the winning path's K/V down to slot == position (identity
        # when the main chain won).
        caches = fixup_tree_caches(caches, lens, jnp.maximum(sel, 0),
                                   accept)
        # Rounds whose winning leaf sits on the alternate branch — the
        # tree mechanism's observable effect (stats["branch_hits"]).
        branch_hit = jnp.sum(((best >= main) & keep).astype(jnp.int32))
        return finish_round(carry, toks, lens, caches, last, prev, keep,
                            greedy, write, accept, tok_acc, best,
                            jnp.int32(1), branch_hit)

    def small_round(carry, tree):
        """Adaptive-K's LOW-acceptance body: a 2-wide linear chunk —
        nearly decode_step cost, still able to bank a 2-token round —
        the smooth on-device analogue of the host variant's fallback."""
        toks, lens, pending, done, caches, last, prev, *_ = carry
        toks, keep = commit_pending(toks, lens, pending, done)
        eff = lens + keep.astype(lens.dtype)
        tail = drafting.tail_gram(toks, eff, n=n)
        drafts = drafting.index_draft(last, toks, tail, eff, n=n,
                                      k=_SPEC_K_SMALL - 1)
        chunk = jnp.concatenate([pending[:, None], drafts], axis=1)
        logits, caches = apply(tree, chunk, caches,
                               lens.astype(jnp.int32),
                               method=GptLM.decode_chunk)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        budget = total - lens
        i_idx = jnp.arange(1, _SPEC_K_SMALL)[None, :]
        ok = ((chunk[:, 1:] == greedy[:, :-1])
              & (i_idx < budget[:, None])
              & (chunk[:, :-1] != eos))
        accept = 1 + jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                             axis=1)
        accept = jnp.where(keep, jnp.minimum(accept, budget), 0)
        write = jnp.arange(_SPEC_K_SMALL)[None, :] < accept[:, None]
        best = jnp.maximum(accept - 1, 0)
        return finish_round(carry, toks, lens, caches, last, prev, keep,
                            greedy, write, accept, chunk, best,
                            jnp.int32(0), jnp.int32(0))

    def cond_fn(carry):
        _, lens, _, done, *_ = carry
        return jnp.any(~done & (lens < total))

    def run(tree, prompt):
        caches = init_kv_cache(cfg, B, total, dtype=cache_dtype)
        last_logits, caches = apply(tree, prompt, caches,
                                    method=GptLM.prefill)
        toks = jnp.zeros((B, total), jnp.int32).at[:, :P].set(prompt)
        pending = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        last, prev = drafting.index_build2(
            toks, jnp.full((B,), P, jnp.int32), n=n, max_len=P)
        carry = (toks, jnp.full((B,), P, jnp.int32), pending,
                 jnp.zeros((B,), bool), caches, last, prev,
                 jnp.float32(K), jnp.int32(0), jnp.int32(0),
                 jnp.int32(0))

        def body(carry):
            if not adaptive:
                return tree_round(carry, tree)
            ema, rounds = carry[7], carry[8]
            # Probe with a full round every probe_every rounds so a
            # regime shift back to repetitive text is rediscovered (the
            # small body alone can never raise the EMA past its own
            # 2-token ceiling).
            use_full = ((ema >= adapt_threshold)
                        | (rounds % probe_every == 0))
            return jax.lax.cond(use_full,
                                lambda c: tree_round(c, tree),
                                lambda c: small_round(c, tree), carry)

        final = jax.lax.while_loop(cond_fn, body, carry)
        toks, lens = final[0], final[1]
        rounds, rounds_full, bhits = final[8], final[9], final[10]
        if eos_id is not None:
            # Pad each row's tail with eos (the generate_cached
            # convention).
            tail = jnp.arange(total)[None, :] >= lens[:, None]
            toks = jnp.where(tail, eos, toks)
        return toks, lens, rounds, rounds_full, bhits

    return jax.jit(run)


def generate_cached_speculative_device(model: GptLM, params,
                                       prompt: jax.Array, num_tokens: int,
                                       *, spec_k: int = 8, ngram: int = 3,
                                       eos_id: int | None = None,
                                       quantize: str = "",
                                       kv_dtype: str = "",
                                       spec_branch: int = 2,
                                       adaptive: bool = True,
                                       adapt_threshold: float = 1.5,
                                       probe_every: int = 8
                                       ) -> tuple[jax.Array, dict]:
    """Speculative greedy decoding ENTIRELY on device — drafting,
    verification, and acceptance inside one ``lax.while_loop``, ONE
    dispatch per generation, with the compiled program CACHED across
    calls (:func:`_spec_device_program`).  This is the repo's default
    fast decode path; the host loop
    (:func:`generate_cached_speculative`) remains the per-round-
    instrumented reference.

    Three mechanisms raise accepted-tokens-per-round while cutting
    cost-per-round (docs/speculative.md has the full cost model):

    - **incremental n-gram index drafting** (:mod:`.drafting`): the
      prompt is indexed once at prefill, each round inserts only the
      grams its accepted tokens created (O(accept), not O(total)) and
      drafts by one hash lookup — the same table/hash the host drafter
      uses, so the two cannot diverge;
    - **tree verification** (``spec_branch > 0``): the chunk carries a
      main drafted chain plus one alternate branch — the continuation of
      the tail gram's second-most-recent occurrence, the drafter's other
      candidate at an ambiguous n-gram; one :meth:`GptLM.decode_chunk`
      call verifies the whole tree through an ancestor mask and the
      longest accepted root path wins (:func:`spec_tree` /
      :func:`fixup_tree_caches`);
    - **adaptive K**: an acceptance EMA switches between the full tree
      round and a 2-wide linear round (≈ decode-step cost) when drafting
      stops paying, probing back every ``probe_every`` rounds — the
      smooth on-device analogue of the host variant's hard fallback.

    Measured cost model (r6, CPU H=512/L=4 — bench records these live as
    ``spec_chunk_cost_vs_step``/``spec_overhead_vs_chunk``): a K=8 chunk
    costs ~1.7x a decode_step (per-token 0.21x), a full round ~1.3x the
    chunk — so speculation pays whenever acceptance/round clears ~2.2,
    and the old 0.14x-of-plain reading was per-call recompilation, now
    gone.  Greedy-only by design: the output is provably the plain
    greedy sequence (up to float tie-breaks between compiled programs).

    Returns ``(tokens [B, P + num_tokens], stats)`` with
    ``{"rounds", "rounds_full", "rounds_small", "branch_hits",
    "tokens_generated", "mean_accepted_per_round"}`` (``branch_hits``:
    rounds whose winning leaf sat on the alternate branch).
    """
    model.cfg.refuse_state_layers("generate_cached_speculative_device")
    B, P = prompt.shape
    total = P + num_tokens
    _validate_sampling(model, total, 0.0, 0.0, None)
    _validate_eos(model, eos_id)
    if model.cfg.attention_window:
        raise ValueError(
            "speculative decoding needs the full-length cache; the windowed "
            "ring cache cannot mask rejected speculative writes")
    if spec_k < 2:
        raise ValueError(f"spec_k must be >= 2, got {spec_k}")
    if num_tokens < 1:
        raise ValueError(f"num_tokens must be >= 1, got {num_tokens}")
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")
    if probe_every < 1:
        raise ValueError(f"probe_every must be >= 1, got {probe_every}")
    branch_len = int(spec_branch)
    if branch_len < 0:
        raise ValueError(f"spec_branch must be >= 0, got {spec_branch}")
    if branch_len and spec_k - branch_len < 2:
        # Not enough room for a branch beside a 2-node main chain — run
        # linear instead of failing a small-K caller.
        branch_len = max(0, spec_k - 2)
    from ..ops.quant import prepare_inference_tree, resolve_kv_dtype
    resolve_kv_dtype(kv_dtype)  # validate before cache-keying on it
    tree = jax.tree.map(jnp.asarray,
                        prepare_inference_tree(params, quantize))
    run = _spec_device_program(
        model.cfg, B, P, int(num_tokens), int(spec_k), branch_len,
        int(ngram), eos_id, quantize, kv_dtype, bool(adaptive),
        float(adapt_threshold), int(probe_every))
    toks, lens, rounds, rounds_full, bhits = run(tree, prompt)
    rounds, rounds_full = int(rounds), int(rounds_full)
    generated = int(jnp.sum(lens - P))
    stats = {"rounds": rounds, "rounds_full": rounds_full,
             "rounds_small": rounds - rounds_full,
             "branch_hits": int(bhits),
             "tokens_generated": generated,
             "mean_accepted_per_round": round(generated / max(rounds, 1),
                                              2)}
    return toks, stats


def split_params_for_pipeline(params, n_stages: int, num_layers: int):
    """Restructure a GptLM param tree for pipeline execution.

    Returns ``{"embed": {word_emb, pos_emb}, "stages": stacked, "head":
    {ln_final, lm_head}}`` where every ``stages`` leaf gains a leading
    ``[n_stages, layers_per_stage]`` prefix (stage-major) so each pipe rank
    holds exactly its own stage's block parameters.
    """
    if num_layers % n_stages:
        raise ValueError(f"num_layers={num_layers} not divisible by "
                         f"pipeline stages={n_stages}")
    per = num_layers // n_stages
    layers = [params[f"layer{i}"] for i in range(num_layers)]
    if any("A_log" in layer for layer in layers):
        raise ValueError(
            "split_params_for_pipeline stacks layers that are all alike; "
            "these parameters hold a linear_attention layer "
            "(GptConfig.layer_kinds)")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    # [L, ...] -> [n_stages, per, ...]
    stacked = jax.tree.map(
        lambda x: x.reshape(n_stages, per, *x.shape[1:]), stacked)
    embed = {"word_emb": params["word_emb"]}
    if "pos_emb" in params:  # absent under pos_encoding="rope"
        embed["pos_emb"] = params["pos_emb"]
    return {
        "embed": embed,
        "stages": stacked,
        "head": {"ln_final": params["ln_final"], "lm_head": params["lm_head"]},
    }


def merge_pipeline_params(pp_params, num_layers: int, n_virtual: int = 1):
    """Inverse of :func:`split_params_for_pipeline`: rebuild the plain
    ``GptLM`` tree (``word_emb``/``pos_emb``/``layer{i}``/``ln_final``/
    ``lm_head``) from a stage-stacked pipeline tree — e.g. to decode from a
    checkpoint written by a ``--pipeline_parallel`` run.  ``n_virtual`` > 1:
    the tree is an interleaved run's ([n_virtual, n_pipe, per, ...] leaves,
    chunk i*n_pipe + s at [i, s]) — flattening the two chunk dims recovers
    the natural chunk-major stack."""
    stages = pp_params["stages"]
    if n_virtual > 1:
        stages = jax.tree.map(
            lambda x: x.reshape((-1,) + tuple(x.shape[2:])), stages)
    flat = jax.tree.map(
        lambda x: x.reshape((num_layers,) + tuple(x.shape[2:])), stages)
    params = dict(pp_params["embed"])
    params.update(pp_params["head"])
    for i in range(num_layers):
        params[f"layer{i}"] = jax.tree.map(lambda x: x[i], flat)
    return params


def make_pipelined_gpt_apply(cfg: GptConfig, mesh, *, n_micro: int,
                             remat: bool = True):
    """``apply(pp_params, tokens) -> logits`` running the decoder blocks as a
    GPipe schedule over the ``pipe`` mesh axis.

    Embedding and LM head run outside the pipeline (replicated over ``pipe``,
    data-sharded like everything else); the homogeneous block stack is the
    pipelined region.  Same math as ``GptLM.__call__`` — an equivalence test
    pins it.
    """
    cfg.refuse_state_layers("make_pipelined_gpt_apply")
    from ..parallel.pipeline import make_pipeline_fn

    block = GptBlock(cfg)

    def stage_fn(stage_params, x):
        # stage_params leaves: [layers_per_stage, ...] — scan the sub-stack.
        def body(h, layer_params):
            return block.apply({"params": layer_params}, h), None
        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    pipe_fwd = make_pipeline_fn(mesh, stage_fn, n_micro=n_micro, remat=remat)
    word = nn.Embed(cfg.vocab_size, cfg.hidden_size)
    pos = nn.Embed(cfg.max_position, cfg.hidden_size)
    ln_final = _layer_norm(cfg)
    lm_head = nn.Dense(cfg.vocab_size)

    def apply(pp_params, tokens):
        S = tokens.shape[1]
        x = word.apply({"params": pp_params["embed"]["word_emb"]}, tokens)
        if cfg.pos_encoding == "learned":
            x = x + pos.apply({"params": pp_params["embed"]["pos_emb"]},
                              jnp.arange(S)[None, :])
        x = x.astype(jnp.dtype(cfg.dtype))
        x = pipe_fwd(pp_params["stages"], x)
        x = ln_final.apply({"params": pp_params["head"]["ln_final"]}, x)
        return lm_head.apply({"params": pp_params["head"]["lm_head"]}, x)

    return apply


def make_interleaved_gpt_apply(cfg: GptConfig):
    """``apply(pp_params, tokens) -> logits`` for the interleaved layout
    ([n_virtual, n_pipe, per, ...] stage leaves): flattens the chunk dims
    back to the natural layer order and scans the block stack — the plain
    (non-pipelined) forward, used for eval/validation where the schedule
    doesn't matter (GSPMD gathers the chunk shards as needed)."""
    cfg.refuse_state_layers("make_interleaved_gpt_apply")
    block = GptBlock(cfg)
    word = nn.Embed(cfg.vocab_size, cfg.hidden_size)
    pos = nn.Embed(cfg.max_position, cfg.hidden_size)
    ln_final = _layer_norm(cfg)
    lm_head = nn.Dense(cfg.vocab_size)

    def apply(pp_params, tokens):
        S = tokens.shape[1]
        x = word.apply({"params": pp_params["embed"]["word_emb"]}, tokens)
        if cfg.pos_encoding == "learned":
            x = x + pos.apply({"params": pp_params["embed"]["pos_emb"]},
                              jnp.arange(S)[None, :])
        x = x.astype(jnp.dtype(cfg.dtype))
        # [v, P, per, ...] -> [v*P*per, ...]: C-order flatten IS the natural
        # layer order (chunk i*P + s at [i, s], layers contiguous per chunk).
        flat = jax.tree.map(
            lambda a: a.reshape((-1,) + tuple(a.shape[3:])),
            pp_params["stages"])

        def body(h, layer_params):
            return block.apply({"params": layer_params}, h), None

        x, _ = jax.lax.scan(body, x, flat)
        x = ln_final.apply({"params": pp_params["head"]["ln_final"]}, x)
        return lm_head.apply({"params": pp_params["head"]["lm_head"]}, x)

    return apply


def make_1f1b_gpt_train_step_builder(cfg: GptConfig, *, n_micro: int,
                                     label_smoothing: float = 0.0,
                                     n_virtual: int = 1):
    """Builder for the 1F1B-scheduled GPT pipeline train step.

    Same math and parameter layout (``{"embed", "stages", "head"}``) as the
    GPipe path (:func:`make_pipelined_gpt_apply`), but training runs the
    hand-rolled one-forward-one-backward schedule
    (:func:`..parallel.pipeline.build_1f1b_pipeline_train_step`): activation
    stash bounded by pipeline depth instead of microbatch count, no AD
    through the schedule.  ``n_virtual`` > 1 selects the interleaved
    (virtual-chunk) schedule instead — stages leaves then carry the
    [n_virtual, n_pipe, ...] layout.  Returns ``builder(mesh) -> step``.
    """
    cfg.refuse_state_layers("make_1f1b_gpt_train_step_builder")
    from ..parallel.pipeline import (build_1f1b_pipeline_train_step,
                                     build_interleaved_1f1b_train_step)

    block = GptBlock(cfg)
    word = nn.Embed(cfg.vocab_size, cfg.hidden_size)
    pos = nn.Embed(cfg.max_position, cfg.hidden_size)
    ln_final = _layer_norm(cfg)
    lm_head = nn.Dense(cfg.vocab_size)

    def stage_fn(stage_params, x):
        def body(h, layer_params):
            return block.apply({"params": layer_params}, h), None
        x, _ = jax.lax.scan(body, x, stage_params)
        return x

    def embed_fn(embed_params, batch):
        tokens = batch["tokens"]
        x = word.apply({"params": embed_params["word_emb"]}, tokens)
        if cfg.pos_encoding == "learned":
            x = x + pos.apply({"params": embed_params["pos_emb"]},
                              jnp.arange(tokens.shape[1])[None, :])
        return x.astype(jnp.dtype(cfg.dtype))

    def loss_head_fn(head_params, y, micro_batch):
        h = ln_final.apply({"params": head_params["ln_final"]}, y)
        logits = lm_head.apply({"params": head_params["lm_head"]}, h)
        loss, acc = lm_loss(logits, micro_batch["tokens"],
                            label_smoothing=label_smoothing)
        return loss, {"accuracy": acc}

    def builder(mesh):
        if n_virtual > 1:
            return build_interleaved_1f1b_train_step(
                mesh, stage_fn, loss_head_fn, n_micro=n_micro,
                n_virtual=n_virtual, embed_fn=embed_fn)
        return build_1f1b_pipeline_train_step(
            mesh, stage_fn, loss_head_fn, n_micro=n_micro,
            embed_fn=embed_fn)

    return builder


def gpt_sharding_rules() -> ShardingRules:
    """Megatron pairing over the ``model`` axis (same layout as BERT's)."""
    return ShardingRules([
        (r"qkv/kernel", P(None, None, "model", None)),
        (r"qkv/bias", P(None, "model", None)),
        (r"q_proj/kernel", P(None, "model", None)),
        (r"q_proj/bias", P("model", None)),
        # kv_proj deliberately REPLICATES under TP: its kv-head axis is
        # usually smaller than the model axis, and at heads/G compression
        # the tensor is tiny — every device holding full K/V is the
        # standard GQA tensor-parallel layout.
        (r"/out/kernel", P("model", None, None)),  # attention proj only
                                                   # (mlp_out matches below)
        (r"mlp_in/kernel", P(None, "model")),
        (r"mlp_in/bias", P("model")),
        (r"mlp_gate/kernel", P(None, "model")),   # column-parallel like mlp_in
        (r"mlp_out/kernel", P("model", None)),
        (r"(word_emb|pos_emb)/embedding", P("model", None)),
        (r"lm_head/kernel", P(None, "model")),
        (r"lm_head/bias", P("model")),
    ])
