"""Shared test helpers: tiny MLP bundles and datasets used across the
step-builder test files, where a traced program holds its pallas calls,
the standalone-TpuServer patch for CLI e2e
tests (no coordination service, no jax.distributed), and the
deterministic test-port allocator shared by the subprocess suites."""

import os
import socket
import threading

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.mlp import (
    MnistMLP, accuracy, cross_entropy_loss)
from distributed_tensorflow_tpu.parallel.sharding import replicate_tree
from distributed_tensorflow_tpu.training.state import (
    TrainState, gradient_descent)


_PORT_LOCK = threading.Lock()
# Partition the scan start by pid so parallel test processes begin in
# disjoint windows (the bind probe below still guards real collisions).
_PORT_NEXT = [21000 + (os.getpid() % 40) * 1000]
_PORTS_HANDED_OUT: set[int] = set()


def free_port() -> int:
    """Retry-free deterministic port allocator for subprocess tests.

    The classic ``bind(("", 0)); close()`` helper has two flake modes
    this kills: it can return the SAME ephemeral port twice in one test
    (the first subprocess hasn't bound yet when the second probe runs),
    and the kernel can hand the closed port to an unrelated process
    before the subprocess binds it.  Here ports come from a sequential
    pid-partitioned scan, each candidate is bind-verified, and a port
    is never handed out twice by this process."""
    with _PORT_LOCK:
        for _ in range(40000):
            port = _PORT_NEXT[0]
            _PORT_NEXT[0] = port + 1 if port + 1 < 61000 else 21000
            if port in _PORTS_HANDED_OUT:
                continue
            try:
                with socket.socket() as s:
                    s.bind(("127.0.0.1", port))
            except OSError:
                continue
            _PORTS_HANDED_OUT.add(port)
            return port
    raise RuntimeError("free_port: port space exhausted")


def primitives(jaxpr, inside=False, out=None):
    """[(primitive name, traced inside a shard_map?)] over a jaxpr and every
    jaxpr its equations hold."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, inside))
        below = inside or eqn.primitive.name == "shard_map"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            primitives(sub, below, out)
    return out


def kernel_placement(fn, *args):
    """(pallas calls traced inside a shard_map, pallas calls outside one)."""
    calls = [inside for name, inside
             in primitives(jax.make_jaxpr(fn)(*args).jaxpr)
             if name == "pallas_call"]
    return sum(calls), len(calls) - sum(calls)


def make_mlp_state(mesh, hidden=8, lr=0.1):
    """Replicated tiny-MLP TrainState + apply_fn on the given mesh."""
    model = MnistMLP(hidden_units=hidden)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 784)))["params"]
    apply_fn = lambda p, x: model.apply({"params": p}, x)
    state = TrainState.create(apply_fn, params, gradient_descent(lr))
    return state.replace(
        params=replicate_tree(mesh, state.params),
        opt_state=replicate_tree(mesh, state.opt_state),
        global_step=replicate_tree(mesh, state.global_step),
    ), apply_fn


def mlp_loss_fn(apply_fn):
    def loss_fn(p, batch):
        x, y = batch
        logits = apply_fn(p, x)
        return cross_entropy_loss(logits, y), {"accuracy": accuracy(logits, y)}
    return loss_fn


def tiny_mlp_datasets():
    from distributed_tensorflow_tpu.data.datasets import (
        DataSet, Datasets, _one_hot, synthetic_classification)
    xs, ys = synthetic_classification(320, 784, 10, seed=0)
    ys = _one_hot(ys, 10)
    return Datasets(train=DataSet(xs[:256], ys[:256], seed=0),
                    validation=DataSet(xs[256:288], ys[256:288], seed=1),
                    test=DataSet(xs[288:], ys[288:], seed=2), synthetic=True)


def launch_train_subprocess(*, job="worker", task=0, ps_port,
                            worker_port=None, worker_ports=None,
                            logdir, train_steps, save_interval_steps=5,
                            extra_flags=(), env_extra=None, devices=2):
    """Launch one real ``train.py`` OS process (the chaos/preemption e2e
    harness): single-process JAX on a small CPU mesh, single-threaded
    eigen so parallel workers don't starve XLA:CPU's collective
    rendezvous.  ``worker_ports`` (list) describes a multi-worker cluster;
    ``worker_port`` keeps the single-worker call sites working.  Returns
    the Popen (stdout+stderr merged, text mode)."""
    import os as _os
    import subprocess
    import sys

    if worker_ports is None:
        worker_ports = [worker_port]
    env = dict(_os.environ)
    env["PYTHONPATH"] = _os.path.dirname(
        _os.path.dirname(_os.path.abspath(__file__)))
    env["DTF_TPU_DISABLE_JAX_DISTRIBUTED"] = "1"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        "--xla_cpu_multi_thread_eigen=false")
    if env_extra:
        env.update(env_extra)
    workers = ",".join(f"localhost:{p}" for p in worker_ports)
    cmd = [
        sys.executable, "-m", "distributed_tensorflow_tpu.train",
        "--platform=cpu", f"--job_name={job}", f"--task_index={task}",
        f"--ps_hosts=localhost:{ps_port}",
        f"--worker_hosts={workers}",
        "--data_dir=/nonexistent", f"--train_steps={train_steps}",
        "--batch_size=32", "--hidden_units=16", "--learning_rate=0.1",
        "--log_every=1", f"--save_interval_steps={save_interval_steps}",
        f"--logdir={logdir}", "--sync_replicas=true", *extra_flags,
    ]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def patch_standalone_server(monkeypatch):
    """Make TpuServer skip the coordination service and jax.distributed —
    single-process CLI e2e runs."""
    from distributed_tensorflow_tpu.cluster.server import TpuServer

    orig = TpuServer.__init__

    def patched(self, cluster, job_name, task_index, **kw):
        kw["coord_service"] = False
        kw["initialize_distributed"] = False
        orig(self, cluster, job_name, task_index, **kw)

    monkeypatch.setattr(TpuServer, "__init__", patched)
