"""Training state — parameters + optimizer slots + global_step, resident in TPU HBM.

Replaces the reference's PS-resident ``tf.Variable`` set (N2): ``global_step``
(``distributed.py:65``) and model/optimizer variables live in one pytree whose
placement is governed by :mod:`..parallel.sharding` rules instead of
``replica_device_setter``.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import optax

from ..utils import profiling


@flax.struct.dataclass
class TrainState:
    """Pure-pytree train state (jit/pjit friendly; checkpointable as-is)."""

    params: Any
    opt_state: Any
    global_step: jax.Array  # scalar int32; reference inits it to 1 (distributed.py:65)

    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)

    # Non-trainable model collections (e.g. BatchNorm batch_stats); None for
    # stateless models.
    model_state: Any = None

    # Training-time PRNG state (dropout etc.); None for deterministic models.
    # Split per step by rng-aware train steps; not checkpointed (a resumed
    # run re-seeds — dropout noise need not replay).
    rng: Any = None

    # Exponential moving average of params (None = disabled).  Updated by
    # ema-aware train steps after each optimizer step; evaluation and the
    # final test use the EMA weights when present.  Checkpointed.
    ema_params: Any = None

    @classmethod
    def create(cls, apply_fn: Callable, params: Any,
               tx: optax.GradientTransformation,
               model_state: Any = None, rng: Any = None,
               ema_params: Any = None) -> "TrainState":
        return cls(
            params=params,
            opt_state=tx.init(params),
            # Reference parity: global_step starts at 1 (distributed.py:65).
            global_step=jnp.asarray(1, jnp.int32),
            apply_fn=apply_fn,
            tx=tx,
            model_state=model_state,
            rng=rng,
            ema_params=ema_params,
        )

    def apply_gradients(self, grads: Any) -> "TrainState":
        with profiling.region("optimizer"):
            updates, new_opt_state = self.tx.update(
                grads, self.opt_state, self.params)
            new_params = optax.apply_updates(self.params, updates)
            return self.replace(params=new_params, opt_state=new_opt_state,
                                global_step=self.global_step + 1)


def gradient_descent(learning_rate: float) -> optax.GradientTransformation:
    """The reference optimizer: plain SGD (``distributed.py:89``)."""
    return optax.sgd(learning_rate)
