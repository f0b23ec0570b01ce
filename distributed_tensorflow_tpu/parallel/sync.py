"""Synchronous replica training — the ``SyncReplicasOptimizer`` equivalent (N3).

The reference aggregates R of N worker gradients in PS-side conditional
accumulators, applies once, and gates workers on a token queue (reference
``distributed.py:91-106``, ``:128-131``).  TPU-native, the whole
push/accumulate/apply/pull cycle collapses into a single XLA AllReduce over ICI
inside one jitted step:

- **R == N (default)**: plain GSPMD data parallelism.  The batch is sharded
  over the ``data`` mesh axis, parameters are replicated (or sharded by rules);
  XLA emits the AllReduce for the gradient mean.  The token-queue barrier is
  implicit — SPMD steps are lockstep by construction.
- **R < N stragglers**: AllReduce has no "first R of N" notion, so the
  straggler-drop semantics move to the host layer: the coordination service
  marks slow/dead replicas and the step takes a per-replica 0/1 mask; masked
  gradients are dropped and the mean is renormalized over the live set —
  exactly the reference's stale-gradient-drop behavior, without the queues.
"""

from __future__ import annotations

from functools import partial, wraps
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..ops.pallas.flash_attention import ambient_mesh
from .mesh import DATA_AXIS, num_replicas

# loss_fn signature: (params, batch) -> (scalar_loss, aux_metrics_dict);
# rng-aware variants (needs_rng=True) take (params, batch, rng) instead.
LossFn = Callable[[Any, Any], tuple[jax.Array, dict]]


def build_sync_train_step(mesh: Mesh, loss_fn: LossFn, *, donate: bool = True,
                          needs_rng: bool = False, ema_decay: float = 0.0,
                          log_grad_norm: bool = False):
    """Full-sync (R == N) train step: one jitted fn, gradient AllReduce via GSPMD.

    Returns ``step(state, batch) -> (state, metrics)``.  ``batch`` must be
    sharded along the ``data`` axis (see :func:`..parallel.mesh.data_sharded`);
    parameter placement follows the state's own shardings.

    ``needs_rng=True``: ``loss_fn(params, batch, rng)`` (dropout etc.) —
    the step splits ``state.rng`` each call, so noise differs per step while
    staying identical across replicas (replicated rng ⇒ SPMD-consistent).

    ``ema_decay > 0`` maintains ``state.ema_params`` (exponential moving
    average of the weights) after every optimizer step; eval should then use
    the EMA copy.

    ``log_grad_norm=True`` adds the global (post-AllReduce) gradient L2 norm
    to the metrics as ``grad_norm`` — one extra reduction, observability for
    divergence/clipping decisions.
    """
    return _build_jit_for_mesh(mesh, _grad_and_update(
        loss_fn, needs_rng, ema_decay, log_grad_norm), donate)


def _build_jit_for_mesh(mesh: Mesh, step, donate: bool):
    """``jax.jit(step)`` whose body is traced with ``mesh`` ambient for the
    pallas flash kernel: on a mesh of several devices the op then maps its
    Mosaic call over the batch axes instead of lowering dense attention
    (``ops/pallas/flash_attention.py``).  Nothing else reads the mesh: the
    gradient all-reduce and every placement stay GSPMD's.  A one-device
    mesh has nothing to map over, and its step is traced as it always was
    (the wrapper alone cost the 406M step half a second of tracing)."""
    kwargs = {"donate_argnums": (0,)} if donate else {}
    if mesh.size == 1:
        return jax.jit(step, **kwargs)

    @wraps(step)
    def traced(*args):
        with ambient_mesh(mesh):
            return step(*args)

    return jax.jit(traced, **kwargs)


def _ema_update(decay: float, ema: Any, params: Any) -> Any:
    return jax.tree.map(lambda e, p: decay * e + (1.0 - decay) * p,
                        ema, params)


def _grad_and_update(loss_fn, needs_rng: bool, ema_decay: float = 0.0,
                     log_grad_norm: bool = False):
    """Per-batch gradient + optimizer update, shared by the plain and scanned
    sync builders: one home for the rng/ema update discipline."""

    def update(state, batch):
        if needs_rng:
            new_rng, key = jax.random.split(state.rng)
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, key)
            new_state = state.apply_gradients(grads).replace(rng=new_rng)
        else:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch)
            new_state = state.apply_gradients(grads)
        if ema_decay > 0.0:
            new_state = new_state.replace(ema_params=_ema_update(
                ema_decay, new_state.ema_params, new_state.params))
        metrics = {"loss": loss, "global_step": new_state.global_step, **aux}
        if log_grad_norm:
            metrics["grad_norm"] = _global_norm(grads)
        return new_state, metrics

    return update


def _global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def build_stateful_sync_train_step(mesh: Mesh, loss_fn_with_state, *,
                                   donate: bool = True):
    """Full-sync step for models with non-trainable state (BatchNorm etc.).

    ``loss_fn_with_state(params, model_state, batch) ->
    (loss, (metrics, new_model_state))``.  Under GSPMD jit the batch statistics
    are computed over the *global* batch, i.e. cross-replica-synchronized
    normalization falls out of the sharding — something the reference's PS
    architecture could not express at all.
    """

    def _step(state, batch):
        (loss, (aux, new_model_state)), grads = jax.value_and_grad(
            loss_fn_with_state, has_aux=True)(state.params, state.model_state,
                                              batch)
        new_state = state.apply_gradients(grads).replace(
            model_state=new_model_state)
        metrics = {"loss": loss, "global_step": new_state.global_step, **aux}
        return new_state, metrics

    return _build_jit_for_mesh(mesh, _step, donate)


def build_scanned_sync_train_step(mesh: Mesh, loss_fn: LossFn, *,
                                  num_steps: int, donate: bool = True,
                                  needs_rng: bool = False,
                                  ema_decay: float = 0.0,
                                  log_grad_norm: bool = False):
    """Full-sync step running ``num_steps`` SGD microsteps per dispatch.

    A ``lax.scan`` over K already-staged batches amortizes the per-step host
    dispatch (the cost floor of the reference's feed-dict protocol,
    ``distributed.py:137-145``) across K optimizer steps — one launch, K
    AllReduces fused by XLA, zero host round-trips in between.  Semantically
    identical to K calls of :func:`build_sync_train_step` on the K batches.

    Returns ``step(state, batches) -> (state, metrics)`` where every leaf of
    ``batches`` has a leading ``[num_steps]`` microstep axis (see
    :func:`..parallel.mesh.stacked_batch_sharding` and
    :func:`stack_microbatches`); ``metrics`` are those of the *last*
    microstep — exactly what a per-step print at the chunk boundary shows.
    """
    if num_steps < 1:
        raise ValueError(f"num_steps must be >= 1, got {num_steps}")
    _one = _grad_and_update(loss_fn, needs_rng, ema_decay, log_grad_norm)

    def _step(state, batches):
        state, stacked = jax.lax.scan(_one, state, batches, length=num_steps)
        return state, jax.tree.map(lambda m: m[-1], stacked)

    return _build_jit_for_mesh(mesh, _step, donate)


def build_scanned_stateful_sync_train_step(mesh: Mesh, loss_fn_with_state, *,
                                           num_steps: int, donate: bool = True):
    """Scanned variant of :func:`build_stateful_sync_train_step` (BatchNorm etc.)."""
    if num_steps < 1:
        raise ValueError(f"num_steps must be >= 1, got {num_steps}")

    def _one(state, batch):
        (loss, (aux, new_model_state)), grads = jax.value_and_grad(
            loss_fn_with_state, has_aux=True)(state.params, state.model_state,
                                              batch)
        new_state = state.apply_gradients(grads).replace(
            model_state=new_model_state)
        return new_state, {"loss": loss,
                           "global_step": new_state.global_step, **aux}

    def _step(state, batches):
        state, stacked = jax.lax.scan(_one, state, batches, length=num_steps)
        return state, jax.tree.map(lambda m: m[-1], stacked)

    return _build_jit_for_mesh(mesh, _step, donate)


def build_accumulating_sync_train_step(mesh: Mesh, loss_fn: LossFn, *,
                                       accum_steps: int, donate: bool = True,
                                       needs_rng: bool = False,
                                       ema_decay: float = 0.0,
                                       log_grad_norm: bool = False):
    """Gradient accumulation: K microbatch grads averaged, ONE optimizer step.

    The large-global-batch lever when HBM can't hold the full batch's
    activations: each call consumes a ``[accum_steps, ...]``-stacked batch
    (same layout as the scanned step), runs K forward/backward passes under
    ``lax.scan``, and applies the *mean* gradient once — semantically a
    single step on the concatenated batch (equal microbatch sizes), with
    activation memory of one microbatch.  ``global_step`` advances by 1 per
    call.  Metrics are microbatch means.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def _step(state, batches):
        if needs_rng:
            new_rng, base_key = jax.random.split(state.rng)
            micro_keys = jax.random.split(base_key, accum_steps)
            scan_xs = (batches, micro_keys)
            def micro_loss(p, x):
                batch, key = x
                return loss_fn(p, batch, key)
        else:
            new_rng = None
            scan_xs = (batches,)
            def micro_loss(p, x):
                return loss_fn(p, x[0])

        def accumulate(acc, x):
            (loss, aux), grads = jax.value_and_grad(micro_loss, has_aux=True)(
                state.params, x)
            acc_grads, acc_loss, acc_aux = acc
            acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
            return (acc_grads, acc_loss + loss,
                    jax.tree.map(jnp.add, acc_aux, aux)), None

        zero_grads = jax.tree.map(jnp.zeros_like, state.params)
        aux_shapes = jax.eval_shape(
            lambda p, x: micro_loss(p, x)[1], state.params,
            jax.tree.map(lambda b: b[0], scan_xs))
        zero_aux = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                aux_shapes)
        (grads, loss, aux), _ = jax.lax.scan(
            accumulate, (zero_grads, jnp.zeros(()), zero_aux), scan_xs,
            length=accum_steps)
        inv = 1.0 / accum_steps
        grads = jax.tree.map(lambda g: g * inv, grads)
        grad_norm = _global_norm(grads) if log_grad_norm else None
        new_state = state.apply_gradients(grads)
        if needs_rng:
            new_state = new_state.replace(rng=new_rng)
        if ema_decay > 0.0:
            new_state = new_state.replace(ema_params=_ema_update(
                ema_decay, new_state.ema_params, new_state.params))
        metrics = {"loss": loss * inv,
                   "global_step": new_state.global_step,
                   **jax.tree.map(lambda a: a * inv, aux)}
        if grad_norm is not None:
            metrics["grad_norm"] = grad_norm
        return new_state, metrics

    return _build_jit_for_mesh(mesh, _step, donate)


def stack_microbatches(batches):
    """Stack K host batches (pytrees of arrays) along a new leading axis."""
    import numpy as np
    return jax.tree.map(lambda *xs: np.stack(xs), *batches)


def build_masked_sync_train_step(mesh: Mesh, loss_fn: LossFn):
    """R < N sync step: per-replica gradient masking with renormalized AllReduce.

    Returns ``step(state, batch, replica_mask) -> (state, metrics)`` where
    ``replica_mask`` is a float array of shape ``[num_replicas]`` (1.0 = include
    this replica's gradient, 0.0 = drop it — the reference's stale-gradient
    drop, ``distributed.py:92-99``).  Parameters must be replicated (this is the
    reference's topology: pure data parallelism).  The update is identical on
    every replica because the masked mean is an AllReduce result.
    """
    n = num_replicas(mesh)

    def per_replica(state, local_batch, local_mask):
        # local_mask: [1] — this replica's inclusion bit.
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, local_batch)
        w = local_mask[0]
        live = jax.lax.psum(w, DATA_AXIS)
        live = jnp.maximum(live, 1.0)
        # Weighted AllReduce: dropped replicas contribute zero; renormalize
        # over the live count (SyncReplicasOptimizer averages over R).
        grads = jax.tree.map(lambda g: jax.lax.psum(g * w, DATA_AXIS) / live, grads)
        loss = jax.lax.psum(loss * w, DATA_AXIS) / live
        aux = jax.tree.map(lambda a: jax.lax.psum(a * w, DATA_AXIS) / live, aux)
        new_state = state.apply_gradients(grads)
        metrics = {"loss": loss, "global_step": new_state.global_step, **aux}
        return new_state, metrics

    state_spec = P()      # replicated params/opt-state (DP topology)
    batch_spec = P(DATA_AXIS)
    mask_spec = P(DATA_AXIS)

    mapped = jax.shard_map(
        per_replica, mesh=mesh,
        in_specs=(state_spec, batch_spec, mask_spec),
        out_specs=(state_spec, P()),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, batch, replica_mask):
        return mapped(state, batch, replica_mask)

    return step


def full_mask(mesh: Mesh) -> jax.Array:
    """Mask including every replica (R == N) — the default aggregation set."""
    return jnp.ones((num_replicas(mesh),), jnp.float32)


def replica_mask_from_tasks(alive, num_workers: int, devices_per_task: int,
                            members=None):
    """Per-replica 0/1 float mask from per-TASK liveness bits.

    ``alive`` is the health view (who is answering heartbeats); ``members``
    (optional) is the elastic-membership view (who belongs to the replica
    set this epoch — a LEAVE or explicit evict shrinks it immediately, no
    lease wait).  A task is included only when both agree; each task's bit
    is expanded to its ``devices_per_task`` device replicas.  An all-dead
    view degenerates to all-alive: a step must never divide by zero, and a
    worker that cannot see anyone alive is better off trusting itself (the
    coordinator is probably the thing that is unreachable).
    """
    import numpy as np
    bits = list(alive[:num_workers])
    if members is not None:
        bits = [a and m for a, m in zip(bits, members[:num_workers])]
    mask = np.repeat(np.asarray(bits, np.float32), devices_per_task)
    if mask.sum() < 1:
        mask[:] = 1.0
    return mask


def resolve_replicas_to_aggregate(replicas_to_aggregate: int | None,
                                  num_workers: int) -> int:
    """Reference default: R = num_workers when unset (``distributed.py:92-95``)."""
    return num_workers if replicas_to_aggregate is None else replicas_to_aggregate


def slice_topology(active, slice_size: int) -> list[tuple[int, ...]]:
    """Group the active task ids into slices of ``slice_size`` — the
    topology map of the hierarchical exchange (docs/param_exchange.md,
    "Hierarchical exchange").

    Tasks are sorted and grouped contiguously (pod slices are assigned
    contiguous task ranges by every launcher in this repo's lineage), the
    last slice absorbing the remainder of an uneven split.  The map is a
    pure function of ``(active, slice_size)``: every worker derives the
    identical grouping from the membership epoch's active set, with no
    negotiation — an evicted task simply vanishes from its slice at the
    next epoch and the map re-keys (the PR-5 evicted-owner rule one level
    up).
    """
    if slice_size < 1:
        raise ValueError(f"slice_size must be >= 1, got {slice_size}")
    tasks = sorted(active)
    if not tasks:
        return []
    slices = [tuple(tasks[lo:lo + slice_size])
              for lo in range(0, len(tasks), slice_size)]
    if (len(slices) > 1 and len(slices[-1]) < max(1, slice_size // 2)
            and len(slices[-2]) + len(slices[-1]) <= 32):
        # Runt slice: fold a too-small tail into its neighbor rather than
        # electing an exporter for one or two stragglers — but never past
        # 32 members, the u32 contributor-mask width the exchange levels
        # are built on (a 33-member fold would turn a valid config or an
        # elastic shrink into a per-exchange crash downstream).
        tail = slices.pop()
        slices[-1] = slices[-1] + tail
    return slices


def slice_exporters(slices) -> tuple[int, ...]:
    """Exporter election: the lowest task id of each slice — the one
    member that quantizes the slice-reduced delta and speaks to the other
    slices' exporters over DCN.  Pure function of the topology map, so
    (like shard ownership) every worker agrees without negotiation; the
    global chief (lowest active task) is always slice 0's exporter."""
    return tuple(min(s) for s in slices)


def slice_of_task(slices, task: int) -> int | None:
    """Index of the slice containing ``task`` (None when not a member)."""
    for g, members in enumerate(slices):
        if task in members:
            return g
    return None


def auto_slice_size(num_workers: int, dcn_slices: int = 1) -> int:
    """Slice size derived from the mesh topology: with ``dcn_slices`` ICI
    domains (the ``--dcn_data_parallel`` factor), workers split evenly
    into that many slices; otherwise 1 (every worker its own slice — the
    flat protocol's degenerate case)."""
    if dcn_slices > 1 and num_workers % dcn_slices == 0:
        return max(num_workers // dcn_slices, 1)
    return 1


def build_intra_slice_reduce(mesh: Mesh, axis: str = DATA_AXIS):
    """Jitted intra-slice AllReduce: mean of per-replica delta vectors
    over the ``axis`` mesh axis via ``psum`` — the ICI leg of the
    hierarchical exchange when a slice's members are local mesh replicas
    (no KV traffic, no quantization; ICI/shared-memory is cheap, so the
    int8 codec stays on the inter-slice hop where it pays).

    Returns ``reduce(stacked) -> mean`` where ``stacked`` is ``[k, n]``
    (one flat float32 delta per replica, sharded over ``axis``) and the
    result is the replicated ``[n]`` mean — bit-identical on every
    replica because it is an AllReduce result.
    """
    k = mesh.shape[axis]

    def per_replica(local):  # local: [1, n] — this replica's delta
        return jax.lax.psum(local[0], axis) / k

    mapped = jax.shard_map(per_replica, mesh=mesh,
                           in_specs=P(axis), out_specs=P(),
                           check_vma=False)
    return jax.jit(mapped)


def contiguous_shard_bounds(n: int, k: int) -> list[tuple[int, int]]:
    """Partition ``n`` elements into ``k`` contiguous shards, sizes within 1.

    The cross-replica update-sharding rule (Xu et al., arXiv:2004.13336):
    instead of every replica reducing the full parameter vector, replica
    ``i`` owns shard ``i`` of the flat buffer and reduces only that —
    turning an N-way full mirror into a reduce-scatter.  The first
    ``n % k`` shards carry the extra element, so the map is a pure
    function of ``(n, k)``: every worker derives identical bounds from
    the membership epoch's active count, with no negotiation.
    ``cluster/param_sync.py`` keys its compressed exchange on this.
    """
    if k < 1:
        raise ValueError(f"shard count must be >= 1, got {k}")
    base, extra = divmod(n, k)
    bounds = []
    lo = 0
    for i in range(k):
        hi = lo + base + (1 if i < extra else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds
