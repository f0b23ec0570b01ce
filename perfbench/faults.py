"""Break the timed path underneath, for the test that has to see ``correct``
come out false (``tests/perfbench``).  Never reached by a benchmark run: the
worker installs a fault only under ``--break-path``.

- ``frozen_step``: the training step returns its state unchanged (the loss
  and the step counter still move).
- ``dropped_rows``: the step sees only the first half of the batch.
- ``altered_token``: the engine alters every token where it is produced
  (the next id instead of the chosen one).
- ``runner_up_token``: the engine serves the second-best token in place of
  one chosen token in sixteen: near misses that a mean over some hundreds
  of tokens can absorb and the widest gap cannot.
"""

from __future__ import annotations


def install(name: str) -> None:
    if name == "frozen_step":
        from distributed_tensorflow_tpu.training import state as state_lib
        real = state_lib.TrainState.apply_gradients

        def frozen(self, grads):
            new = real(self, grads)
            return new.replace(params=self.params)
        state_lib.TrainState.apply_gradients = frozen
    elif name == "dropped_rows":
        from distributed_tensorflow_tpu.models import gpt as gpt_lib
        real_loss = gpt_lib.lm_loss

        def half(logits, tokens, *a, **kw):
            n = max(1, tokens.shape[0] // 2)
            return real_loss(logits[:n], tokens[:n], *a, **kw)
        gpt_lib.lm_loss = half
    elif name == "altered_token":
        from distributed_tensorflow_tpu.models import gpt as gpt_lib
        real_sample = gpt_lib.sample_logits_dynamic

        def altered(step_logits, *a, **kw):
            out = real_sample(step_logits, *a, **kw)
            return (out + 1) % step_logits.shape[-1]
        gpt_lib.sample_logits_dynamic = altered
    elif name == "runner_up_token":
        import jax.numpy as jnp
        from distributed_tensorflow_tpu.models import gpt as gpt_lib
        real_sample = gpt_lib.sample_logits_dynamic

        def runner_up(step_logits, *a, **kw):
            out = real_sample(step_logits, *a, **kw)
            second = jnp.argsort(-step_logits, axis=-1)[:, 1]
            return jnp.where(out % 16 == 0, second.astype(out.dtype), out)
        gpt_lib.sample_logits_dynamic = runner_up
    else:
        raise SystemExit(f"unknown fault {name!r}")
