"""SPMD determinism checker — the TPU-native answer to race detection.

The reference's async mode *embraces* parameter races (Hogwild updates on the
PS, reference ``distributed.py:89-102``) and ships no sanitizer for them
(SURVEY §5: no TSAN/ASAN config exists).  This framework's design claim is
the opposite: a sync training step is a single jitted SPMD program whose
reductions are deterministic on TPU, so the same config MUST produce
bit-identical trajectories.  This tool *verifies* that claim the way a race
detector verifies lock discipline — run the identical configuration twice
from scratch and compare every step's metrics bitwise.  Any nondeterminism
(an unseeded host RNG leaking into batches, a non-reproducible init, an
accidental dependence on dispatch timing) fails loudly with the first
diverging step.

Usage::

    python -m distributed_tensorflow_tpu.tools.check_determinism \
        --model mnist_mlp --steps 20 --batch_size 64 [--platform cpu]
        [--steps_per_call K] [--seed N]

Exit code 0 = bit-identical replay; 1 = divergence (report printed).
"""

from __future__ import annotations

import argparse
import sys


def _run_trajectory(model: str, steps: int, batch_size: int, seed: int,
                    steps_per_call: int):
    """One from-scratch training run; returns the per-step loss bits."""
    import jax
    import numpy as np

    from ..models import registry
    from ..parallel import mesh as mesh_lib
    from ..parallel import sync as sync_lib
    from ..train import FLAGS  # full flag surface (model/seed/transformer)

    FLAGS.parse([f"--model={model}", f"--batch_size={batch_size}",
                 f"--seed={seed}", f"--train_steps={steps}",
                 "--data_dir=/nonexistent"])
    mesh = mesh_lib.data_parallel_mesh()
    from ..ops.attention import attention_mesh
    with attention_mesh(mesh):
        bundle = registry.build(model, FLAGS, mesh=mesh)
        from ..parallel.sharding import replicate_state
        state = replicate_state(mesh, bundle.state)

        datasets = bundle.load_datasets(FLAGS.data_dir)
        sharding = mesh_lib.batch_sharding(mesh)

        stateful = bundle.stateful_loss_fn is not None
        if stateful:
            if steps_per_call > 1:
                step = sync_lib.build_scanned_stateful_sync_train_step(
                    mesh, bundle.stateful_loss_fn, num_steps=steps_per_call,
                    donate=False)
            else:
                step = sync_lib.build_stateful_sync_train_step(
                    mesh, bundle.stateful_loss_fn, donate=False)
        elif steps_per_call > 1:
            step = sync_lib.build_scanned_sync_train_step(
                mesh, bundle.loss_fn, num_steps=steps_per_call,
                needs_rng=bundle.needs_rng, donate=False)
        else:
            step = sync_lib.build_sync_train_step(
                mesh, bundle.loss_fn, needs_rng=bundle.needs_rng,
                donate=False)

        losses = []
        done = 0
        while done < steps:
            if steps_per_call > 1:
                batch = sync_lib.stack_microbatches(
                    [datasets.train.next_batch(batch_size)
                     for _ in range(steps_per_call)])
                put = mesh_lib.stacked_batch_sharding(mesh)
            else:
                batch = datasets.train.next_batch(batch_size)
                put = sharding
            batch = jax.tree.map(lambda a: jax.device_put(a, put), batch)
            state, metrics = step(state, batch)
            # Bit-exact record: the raw float32 pattern, not a repr round-trip.
            losses.append(np.float32(metrics["loss"]).tobytes())
            done += steps_per_call
    return losses


def check(model: str, steps: int, batch_size: int, seed: int = 0,
          steps_per_call: int = 1) -> tuple[list[int], int]:
    """Run twice, compare bitwise; returns (diverging step indices,
    number of logged steps compared)."""
    first = _run_trajectory(model, steps, batch_size, seed, steps_per_call)
    second = _run_trajectory(model, steps, batch_size, seed, steps_per_call)
    diverged = [i for i, (a, b) in enumerate(zip(first, second)) if a != b]
    return diverged, len(first)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="mnist_mlp")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch_size", type=int, default=64)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--steps_per_call", type=int, default=1)
    parser.add_argument("--platform", default="",
                        help="jax platform override (e.g. cpu)")
    args = parser.parse_args(argv)

    from ..utils.backend import configure_backend
    configure_backend(args.platform)

    diverged, n = check(args.model, args.steps, args.batch_size, args.seed,
                        args.steps_per_call)
    if diverged:
        print(f"FAIL: {args.model} replay diverged at "
              f"{len(diverged)}/{n} logged steps "
              f"(first at step index {diverged[0]}) — nondeterminism in the "
              "init, data pipeline, or step")
        return 1
    print(f"PASS: {args.model} replay bit-identical over {n} logged steps "
          f"(batch_size={args.batch_size}, seed={args.seed}, "
          f"steps_per_call={args.steps_per_call})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
