"""Device-mesh construction — the TPU-native replacement for cluster device placement.

The reference places compute per-worker and variables on a parameter server via
``tf.train.replica_device_setter`` (reference ``distributed.py:59-64``).  On TPU
there is no PS: every chip holds (a shard of) the parameters in HBM and the mesh
axes define how tensors are laid out.  This module standardizes the axis names
used across the framework:

- ``data``  — data parallelism (batch axis; gradients AllReduce over it)
- ``model`` — tensor parallelism (feature/head axis)
- ``seq``   — sequence/context parallelism (ring attention)
- ``pipe``  — pipeline parallelism (layer stages)
- ``expert``— expert parallelism (MoE)

Axes of size 1 are kept in the mesh so a single sharding-rule set works at any
scale (GSPMD treats size-1 axes as no-ops).
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"

AXIS_ORDER = (DATA_AXIS, SEQ_AXIS, PIPE_AXIS, EXPERT_AXIS, MODEL_AXIS)


def _slice_major(devices, n_groups: int):
    """Order devices so consecutive blocks share a pod slice.

    Grouping key: the TPU runtime's ``slice_index`` when present (real
    multislice), else ``process_index``.  On a real topology (more than one
    key) the group count MUST equal the requested DCN factor — anything else
    would silently route "ICI-only" inner axes over DCN, so it raises
    instead.  Only a synthetic topology (a single key, e.g. the virtual CPU
    mesh) falls back to even positional chunking.
    """
    keyed = {}
    for d in devices:
        key = getattr(d, "slice_index", None)
        if key is None:
            key = getattr(d, "process_index", 0)
        keyed.setdefault(key, []).append(d)
    groups = [keyed[k] for k in sorted(keyed)]
    if len(groups) == 1:
        per = len(devices) // n_groups
        groups = [list(devices[i * per:(i + 1) * per])
                  for i in range(n_groups)]
    elif len(groups) != n_groups or len({len(g) for g in groups}) != 1:
        raise ValueError(
            f"dcn_data={n_groups} does not match the device topology: "
            f"{len(groups)} slice/process groups of sizes "
            f"{[len(g) for g in groups]}; set dcn_data to the slice count "
            "so the inner mesh axes stay on intra-slice ICI")
    return [d for group in groups for d in group]


def create_mesh(
    data: int = -1,
    model: int = 1,
    seq: int = 1,
    pipe: int = 1,
    expert: int = 1,
    devices: Sequence[jax.Device] | None = None,
    dcn_data: int = 1,
) -> Mesh:
    """Build a named mesh over available devices.

    One axis size may be -1 (inferred from the device count).  Axis order puts
    ``model`` innermost so tensor-parallel collectives ride the fastest ICI
    links, and ``data`` outermost so data-parallel AllReduce tolerates the
    slowest links (the scaling-book layout heuristic).

    ``dcn_data > 1`` builds a hybrid multi-slice layout: devices are ordered
    slice-major so the ``data`` axis's OUTER factor of ``dcn_data`` crosses
    slice boundaries (gradient AllReduce pays one DCN hop per slice pair)
    while every other axis — and the inner data factor — stays inside one
    slice on ICI.  Axis names and sharding rules are unchanged; only the
    device order differs.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = {DATA_AXIS: data, SEQ_AXIS: seq, PIPE_AXIS: pipe,
             EXPERT_AXIS: expert, MODEL_AXIS: model}
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError("At most one mesh axis may be -1")
    fixed = math.prod(v for v in sizes.values() if v != -1)
    if unknown:
        if n % fixed != 0:
            raise ValueError(f"{n} devices not divisible by fixed axes product {fixed}")
        sizes[unknown[0]] = n // fixed
    total = math.prod(sizes.values())
    if total != n:
        raise ValueError(f"Mesh of {total} devices but {n} available")
    if dcn_data > 1:
        if sizes[DATA_AXIS] % dcn_data:
            # (data | n already holds, so this is the only divisibility gate.)
            raise ValueError(
                f"data axis {sizes[DATA_AXIS]} not divisible by "
                f"dcn_data={dcn_data} (the DCN factor is the data axis's "
                "outer segment)")
        devices = _slice_major(devices, dcn_data)
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def data_parallel_mesh(num_devices: int | None = None,
                       devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Pure data-parallel mesh — the reference's replica topology (N workers)."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return create_mesh(data=len(devices), devices=devices)


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for tensors replicated on every device (e.g. global_step)."""
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh, *, extra_dims: int = 0) -> NamedSharding:
    """Sharding for batch-major tensors split along the ``data`` (and ``seq``) axes."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * extra_dims)))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for input batches: dim 0 over ``data``, and — when the mesh has
    a non-trivial ``seq`` axis — dim 1 (the sequence dim) over ``seq``."""
    if mesh.shape[SEQ_AXIS] > 1:
        return NamedSharding(mesh, P(DATA_AXIS, SEQ_AXIS))
    return NamedSharding(mesh, P(DATA_AXIS))


def stacked_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a stack of K batches (leading microstep dim unsharded,
    per-batch dims as :func:`batch_sharding`) — the input layout of
    :func:`..parallel.sync.build_scanned_sync_train_step`."""
    if mesh.shape[SEQ_AXIS] > 1:
        return NamedSharding(mesh, P(None, DATA_AXIS, SEQ_AXIS))
    return NamedSharding(mesh, P(None, DATA_AXIS))


def num_replicas(mesh: Mesh) -> int:
    """Number of data-parallel replicas — the reference's ``num_workers`` (``distributed.py:52``)."""
    return mesh.shape[DATA_AXIS]


# ------------------------------------------------- declarative layouts
#
# TF-Replicator's composition principle (PAPERS.md, 1902.00465): ONE
# declarative description of the parallelism layout that a single program
# interprets into any replica/shard topology.  ParallelConfig is that
# description for this framework — train.py and the autotuner
# (tools/autotune.py) both construct their mesh + sharding plan through it
# instead of plumbing individual axis flags, and the tuner's search space
# is literally a list of these values.

_QUANT_ARMS = ("off", "int8")
_ATTENTION_BACKENDS = ("auto", "xla", "pallas", "ring", "ulysses")


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Declarative parallelism layout: axis sizes + step-shape knobs.

    The one value that determines a run's layout end to end:

    - ``data``/``model``/``seq``/``pipe``/``expert`` — the mesh axis
      sizes (:func:`create_mesh` order/semantics; ``data`` may be ``-1``
      to absorb the remaining devices);
    - ``dcn_data`` — the data axis's outer DCN factor on multi-slice
      pods (device order only, see :func:`create_mesh`);
    - ``microbatch`` — gradient-accumulation microbatches per optimizer
      step (1 = plain step);
    - ``quantize`` — ``"off"`` or ``"int8"`` (the int8 matmul training
      arm, ``--gpt_matmul_int8``);
    - ``attention`` — attention backend; ``"auto"`` resolves to
      ``"ring"`` when ``seq > 1`` and ``"xla"`` otherwise;
    - ``fsdp``/``fsdp_min_size`` — ZeRO-3 parameter/optimizer sharding
      over the ``data`` axis.

    A config whose axes are all concrete uses a device *prefix* when the
    host has more devices than the layout needs (the tuner measures
    submeshes of the attached topology this way); ``data=-1`` spans every
    device, which is the CLI default layout.
    """

    data: int = -1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1
    dcn_data: int = 1
    microbatch: int = 1
    quantize: str = "off"
    attention: str = "auto"
    fsdp: bool = False
    fsdp_min_size: int = 2 ** 16

    def __post_init__(self):
        self.validate()

    def validate(self) -> None:
        for name in ("model", "seq", "pipe", "expert", "dcn_data",
                     "microbatch"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"ParallelConfig.{name} must be a "
                                 f"positive int, got {v!r}")
        if not isinstance(self.data, int) or (self.data < 1
                                              and self.data != -1):
            raise ValueError(f"ParallelConfig.data must be a positive int "
                             f"or -1 (infer), got {self.data!r}")
        if self.quantize not in _QUANT_ARMS:
            raise ValueError(f"ParallelConfig.quantize must be one of "
                             f"{_QUANT_ARMS}, got {self.quantize!r}")
        if self.attention not in _ATTENTION_BACKENDS:
            raise ValueError(f"ParallelConfig.attention must be one of "
                             f"{_ATTENTION_BACKENDS}, "
                             f"got {self.attention!r}")
        if self.seq > 1 and self.attention in ("xla", "pallas"):
            raise ValueError(
                f"seq={self.seq} needs a sequence-parallel attention "
                f"backend (ring/ulysses/auto), got {self.attention!r}")

    # ---------------------------------------------------------- shape

    def axis_sizes(self) -> dict[str, int]:
        """Mesh axis name -> size (``data`` may still be -1 here)."""
        return {DATA_AXIS: self.data, SEQ_AXIS: self.seq,
                PIPE_AXIS: self.pipe, EXPERT_AXIS: self.expert,
                MODEL_AXIS: self.model}

    def total_devices(self, n_available: int | None = None) -> int:
        """Devices this layout occupies (resolving ``data=-1`` against
        ``n_available``, which is then required)."""
        fixed = self.model * self.seq * self.pipe * self.expert
        if self.data != -1:
            return fixed * self.data
        if n_available is None:
            raise ValueError("data=-1 needs n_available to resolve")
        if n_available % fixed:
            raise ValueError(f"{n_available} devices not divisible by the "
                             f"fixed axes product {fixed}")
        return n_available

    def resolve(self, n_available: int) -> "ParallelConfig":
        """Concrete copy: ``data=-1`` filled in from ``n_available``."""
        total = self.total_devices(n_available)
        if total > n_available:
            raise ValueError(f"layout needs {total} devices, only "
                             f"{n_available} available")
        fixed = self.model * self.seq * self.pipe * self.expert
        return dataclasses.replace(self, data=total // fixed)

    def resolved_attention(self) -> str:
        """``auto`` resolved against the seq axis (ring when seq > 1)."""
        if self.attention != "auto":
            return self.attention
        return "ring" if self.seq > 1 else "xla"

    def describe(self) -> str:
        """Compact human/bench label, e.g. ``dp4-tp2-mb2-int8``."""
        parts = [f"dp{self.data}"]
        for tag, v in (("tp", self.model), ("sp", self.seq),
                       ("pp", self.pipe), ("ep", self.expert),
                       ("dcn", self.dcn_data)):
            if v > 1:
                parts.append(f"{tag}{v}")
        parts.append(f"mb{self.microbatch}")
        if self.quantize != "off":
            parts.append(self.quantize)
        if self.fsdp:
            parts.append("fsdp")
        return "-".join(parts)

    # ----------------------------------------------------- composition

    def build_mesh(self, devices: Sequence[jax.Device] | None = None
                   ) -> Mesh:
        """Materialize the layout as a named mesh.

        Fully concrete configs take a device *prefix* of the required
        size (a tuner trial's submesh); ``data=-1`` spans all devices.
        """
        if devices is None:
            devices = jax.devices()
        total = self.total_devices(len(devices))
        if total > len(devices):
            raise ValueError(f"layout {self.describe()} needs {total} "
                             f"devices, only {len(devices)} available")
        return create_mesh(data=self.data, model=self.model, seq=self.seq,
                           pipe=self.pipe, expert=self.expert,
                           devices=list(devices)[:total],
                           dcn_data=self.dcn_data)

    def batch_sharding(self, mesh: Mesh, *, stacked: bool = False
                       ) -> NamedSharding:
        """Input-batch sharding for this layout; ``stacked`` for the
        microstep-stacked layouts (microbatch > 1 / steps_per_call)."""
        return stacked_batch_sharding(mesh) if stacked \
            else batch_sharding(mesh)

    def place_state(self, mesh: Mesh, state: Any, rules: Any = None) -> Any:
        """Place a TrainState on ``mesh`` under this layout — the single
        placement dispatch train.py and the tuner share.

        ``rules`` are the model bundle's tensor-parallel ShardingRules
        (or None); they engage only when the mesh has a non-trivial
        ``model``/``expert`` axis, exactly as the trainer's historical
        ad-hoc dispatch did (parity-pinned by tests/test_mesh_config.py).
        """
        from .sharding import fsdp_state, replicate_state, shard_state
        use_rules = rules is not None and (
            mesh.shape[MODEL_AXIS] > 1 or mesh.shape[EXPERT_AXIS] > 1)
        if self.fsdp:
            return fsdp_state(mesh, state, rules if use_rules else None,
                              min_size=self.fsdp_min_size)
        if use_rules:
            return shard_state(mesh, state, rules)
        return replicate_state(mesh, state)

    # ------------------------------------------------- (de)serialization

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ParallelConfig":
        """Strict parse: unknown keys are an error (a typo'd profile key
        must never silently fall back to the default layout)."""
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise ValueError(f"unknown ParallelConfig key(s) {unknown}; "
                             f"known: {sorted(known)}")
        return cls(**d)

    @classmethod
    def from_flags(cls, FLAGS: Any) -> "ParallelConfig":
        """The CLI flag set -> one declarative layout (train.py's path).

        Missing attributes fall back to the defaults so partial flag
        holders (bench harnesses, tests) can reuse the same entry point.
        """
        g = lambda name, default: getattr(FLAGS, name, default)
        return cls(
            data=-1,
            model=g("tensor_parallel", 1),
            seq=g("sequence_parallel", 1),
            pipe=g("pipeline_parallel", 1),
            expert=g("expert_parallel", 1),
            dcn_data=g("dcn_data_parallel", 1),
            microbatch=g("grad_accum_steps", 1),
            quantize="int8" if g("gpt_matmul_int8", False) else "off",
            attention=g("attention_backend", "auto"),
            fsdp=g("fsdp", False),
            fsdp_min_size=g("fsdp_min_size", 2 ** 16),
        )


# ------------------------------------------------------- run profiles
#
# The autotuner's output artifact (docs/autotune.md): one JSON file
# holding the winning ParallelConfig (plus workload identity, serving
# knobs, and the tuning evidence) that ``train.py --profile=<file>``
# consumes to reproduce the tuned layout end to end.

PROFILE_SCHEMA = "dtf_run_profile/v1"


def save_run_profile(path: str, parallel: ParallelConfig | None, *,
                     workload: dict | None = None,
                     serving: dict | None = None,
                     tuning: dict | None = None) -> dict:
    """Write a run profile; returns the payload written."""
    payload: dict[str, Any] = {"schema": PROFILE_SCHEMA}
    if parallel is not None:
        payload["parallel"] = parallel.to_dict()
    if workload:
        payload["workload"] = dict(workload)
    if serving:
        payload["serving"] = dict(serving)
    if tuning:
        payload["tuning"] = dict(tuning)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    import os
    os.replace(tmp, path)
    return payload


def load_run_profile(path: str) -> dict:
    """Read + validate a run profile: schema pinned, the ``parallel``
    section (when present) must parse into a ParallelConfig."""
    with open(path) as fh:
        payload = json.load(fh)
    if not isinstance(payload, dict) \
            or payload.get("schema") != PROFILE_SCHEMA:
        raise ValueError(
            f"{path} is not a {PROFILE_SCHEMA} run profile "
            f"(schema={payload.get('schema') if isinstance(payload, dict) else None!r})")
    if "parallel" in payload:
        # Validation side effect: a malformed layout fails HERE, not as
        # an opaque mesh error mid-startup.
        ParallelConfig.from_dict(payload["parallel"])
    return payload
