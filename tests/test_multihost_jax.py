"""True multi-controller integration: 2 trainer processes × 4 CPU devices
each form ONE 8-device global mesh via ``jax.distributed`` — the data plane
(gradient AllReduce, eval, orbax checkpointing) runs *across process
boundaries*, unlike test_multiprocess.py which isolates the control plane.

This is the single-machine stand-in for the multi-host TPU pod topology: the
same ``jax.distributed.initialize`` path `TpuServer` takes on real slices
(SURVEY §2b N1: XLA collectives over ICI/DCN replace the PS gRPC data plane).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from helpers import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIMEOUT = 300

def launch_jaxdist(task, ps_port, worker_ports, logdir, train_steps=24,
                   extra=(), devices=4):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    # `devices` local devices per process (4 by default -> 8-device global
    # mesh with 2 workers).  NO DTF_TPU_DISABLE_JAX_DISTRIBUTED: this test
    # wants the real thing.  Single-threaded eigen: N processes already
    # oversubscribe this host's cores.
    env.pop("DTF_TPU_DISABLE_JAX_DISTRIBUTED", None)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        "--xla_cpu_multi_thread_eigen=false")
    workers = ",".join(f"localhost:{p}" for p in worker_ports)
    cmd = [
        sys.executable, "-m", "distributed_tensorflow_tpu.train",
        "--platform=cpu", "--job_name=worker", f"--task_index={task}",
        f"--ps_hosts=localhost:{ps_port}", f"--worker_hosts={workers}",
        "--data_dir=/nonexistent", f"--train_steps={train_steps}",
        "--batch_size=32", "--hidden_units=16", "--learning_rate=0.1",
        "--log_every=4", "--validation_every=8", "--save_interval_steps=8",
        f"--logdir={logdir}", "--sync_replicas=true", *extra,
    ]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def launch_ps(ps_port, worker_ports, logdir):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["DTF_TPU_DISABLE_JAX_DISTRIBUTED"] = "1"  # PS never joins the mesh
    workers = ",".join(f"localhost:{p}" for p in worker_ports)
    cmd = [
        sys.executable, "-m", "distributed_tensorflow_tpu.train",
        "--platform=cpu", "--job_name=ps", "--task_index=0",
        f"--ps_hosts=localhost:{ps_port}", f"--worker_hosts={workers}",
        "--data_dir=/nonexistent", f"--logdir={logdir}",
    ]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def finish(proc, timeout=TIMEOUT):
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"process timed out; output:\n{out}")
    return out


def parse_losses(out: str) -> dict[int, float]:
    losses = {}
    for line in out.splitlines():
        if "traing step" in line and "loss" in line:
            parts = line.split()
            step = int(parts[parts.index("step") + 1])
            loss = float(parts[parts.index("loss") + 1])
            losses[step] = loss
    return losses


@pytest.mark.smoke
def test_two_process_scanned_steps(tmp_path):
    """Chunked dispatch (--steps_per_call) under cross-process collectives:
    the lax.scan body's AllReduces run K times per launch across both
    controllers, lockstep."""
    ps_port = free_port()
    worker_ports = [free_port(), free_port()]
    logdir = str(tmp_path / "logdir")
    ps = launch_ps(ps_port, worker_ports, logdir)
    try:
        extra = ["--steps_per_call=8", "--log_every=8",
                 "--validation_every=0", "--save_interval_steps=1000000"]
        w0 = launch_jaxdist(0, ps_port, worker_ports, logdir,
                            train_steps=32, extra=extra)
        w1 = launch_jaxdist(1, ps_port, worker_ports, logdir,
                            train_steps=32, extra=extra)
        out0, out1 = finish(w0), finish(w1)
        assert w0.returncode == 0, out0
        assert w1.returncode == 0, out1
        l0 = parse_losses(out0)
        assert l0 and l0 == parse_losses(out1)
        # Chunk cadence: logged local steps are multiples of 8.
        assert all(s % 8 == 0 for s in l0), l0
        for out in (out0, out1):
            assert "test accuracy" in out
    finally:
        ps.send_signal(signal.SIGTERM)
        ps.wait(timeout=10)


def test_two_process_async_mode(tmp_path):
    """Async mode NEVER joins the multi-controller mesh, even when the
    launch env would allow it: each worker runs its own single-controller
    program over its local devices and meets its peers only at the
    control-plane exchange (reference ``distributed.py:102,145`` — async
    workers met at the PS, not at each other).

    Lockstep-async over one global mesh is a deadlock by construction —
    the per-process adopt decision depends on racy KV fetch timing, so one
    controller can enter a cross-process device_put the other never joins
    (observed live in round 5).  This test pins the guard: independent
    cadence, both finish, and the later worker averages with the earlier
    one's publications."""
    ps_port = free_port()
    worker_ports = [free_port(), free_port()]
    logdir = str(tmp_path / "logdir")
    ps = launch_ps(ps_port, worker_ports, logdir)
    try:
        extra = ["--sync_replicas=false", "--async_sync_period=4",
                 "--validation_every=0", "--save_interval_steps=1000000"]
        w0 = launch_jaxdist(0, ps_port, worker_ports, logdir,
                            train_steps=160, extra=extra)
        w1 = launch_jaxdist(1, ps_port, worker_ports, logdir,
                            train_steps=160, extra=extra)
        out0, out1 = finish(w0), finish(w1)
        assert w0.returncode == 0, out0
        assert w1.returncode == 0, out1
        # Single-controller per worker: 4 local replicas each -> 40 local
        # steps cross global step 160, at each worker's own cadence.
        l0, l1 = parse_losses(out0), parse_losses(out1)
        assert l0 and sorted(l0) == sorted(l1), (l0, l1)
        assert all(np.isfinite(v) for v in l0.values()), l0
        # No cross-process mesh (that's the sync path's sharded feed)...
        for out in (out0, out1):
            assert "sharded feed" not in out, out
            assert "test accuracy" in out
        # ...but the workers DID meet at the control plane: at least the
        # later-running worker sees the other's publications (exact counts
        # are cadence-dependent; zero on both sides means the exchange is
        # dead).
        assert ("averaged parameters with 1 peer(s)" in out0
                or "averaged parameters with 1 peer(s)" in out1), (out0,
                                                                   out1)
    finally:
        ps.send_signal(signal.SIGTERM)
        ps.wait(timeout=10)


def test_two_process_global_mesh_training(tmp_path):
    ps_port = free_port()
    worker_ports = [free_port(), free_port()]
    logdir = str(tmp_path / "logdir")
    ps = launch_ps(ps_port, worker_ports, logdir)
    try:
        w0 = launch_jaxdist(0, ps_port, worker_ports, logdir)
        w1 = launch_jaxdist(1, ps_port, worker_ports, logdir)
        out0, out1 = finish(w0), finish(w1)
        assert w0.returncode == 0, out0
        assert w1.returncode == 0, out1

        # Lockstep SPMD: both controllers ran the SAME global computation, so
        # per-step losses must be bit-identical across processes.
        l0, l1 = parse_losses(out0), parse_losses(out1)
        assert l0 and l0 == l1, (l0, l1)

        # The overlapped feed is ACTIVE in multi-controller runs (the r1
        # force-disable is gone): staged main-thread puts, not sync feed.
        for out in (out0, out1):
            assert "staged prefetch depth=2" in out, out

        # Training progressed and both report the full-split test accuracy.
        for out in (out0, out1):
            assert "test accuracy" in out
            assert "validation accuracy" in out

        # The sharded feed is active: each of the 2 processes loads only its
        # half of the global batch (assembled via
        # make_array_from_process_local_data), and the run still produced
        # bit-identical cross-process losses above.
        for out in (out0, out1):
            assert "sharded feed — this process loads 16/32" in out, out

        # Collective orbax checkpointing produced a restorable step.
        ckpts = os.path.join(logdir, "mnist_mlp", "checkpoints")
        steps = [int(d) for d in os.listdir(ckpts) if d.isdigit()]
        assert steps and max(steps) >= 24, steps

        # Restart both controllers with a longer horizon: the collective
        # restore path must resume from the shared checkpoint, not step 1.
        w0 = launch_jaxdist(0, ps_port, worker_ports, logdir, train_steps=40)
        w1 = launch_jaxdist(1, ps_port, worker_ports, logdir, train_steps=40)
        out0, out1 = finish(w0), finish(w1)
        assert w0.returncode == 0, out0
        assert w1.returncode == 0, out1
        resumed = parse_losses(out0)
        # Local steps restart, but the global step continues past the
        # restored checkpoint: the first logged global step must be > 24.
        import re
        first_global = int(re.search(r"\(global step:(\d+)\)", out0).group(1))
        assert first_global > 24, out0
        assert resumed and parse_losses(out1) == resumed
    finally:
        ps.send_signal(signal.SIGTERM)
        ps.wait(timeout=10)


@pytest.mark.smoke
def test_four_process_sync_mnist(tmp_path):
    """VERDICT r4 #6: the multi-controller data plane past 2 processes —
    4 trainer processes x 2 devices each form ONE 8-device global mesh;
    gradient AllReduces and the sharded feed cross THREE process
    boundaries, lockstep."""
    ps_port = free_port()
    worker_ports = [free_port() for _ in range(4)]
    logdir = str(tmp_path / "logdir")
    ps = launch_ps(ps_port, worker_ports, logdir)
    try:
        extra = ["--validation_every=0", "--save_interval_steps=1000000"]
        ws = [launch_jaxdist(t, ps_port, worker_ports, logdir,
                             train_steps=16, extra=extra, devices=2)
              for t in range(4)]
        outs = [finish(w, timeout=TIMEOUT * 2) for w in ws]
        for w, out in zip(ws, outs):
            assert w.returncode == 0, out
        # Lockstep SPMD across all four controllers: bit-identical losses.
        losses = [parse_losses(out) for out in outs]
        assert losses[0] and all(l == losses[0] for l in losses[1:]), losses
        for out in outs:
            # Each process feeds its quarter of the global batch.
            assert "sharded feed — this process loads 8/32" in out, out
            assert "test accuracy" in out
    finally:
        ps.send_signal(signal.SIGTERM)
        ps.wait(timeout=10)


def test_two_process_gpt_fsdp_crosses_dcn(tmp_path):
    """VERDICT r4 #6: parallelism COMPOSED with the process boundary — a
    GPT step with FSDP sharding its params over the 8-device data axis
    that spans both controllers, so the FSDP all-gathers (and the
    gradient reduce-scatters) cross the DCN-analog process boundary, not
    just ICI-analog intra-process links."""
    ps_port = free_port()
    worker_ports = [free_port(), free_port()]
    logdir = str(tmp_path / "logdir")
    ps = launch_ps(ps_port, worker_ports, logdir)
    try:
        extra = ["--model=gpt_mini", "--bert_seq_len=16", "--batch_size=16",
                 "--fsdp", "--fsdp_min_size=1024", "--log_sharding",
                 "--validation_every=0", "--save_interval_steps=1000000"]
        w0 = launch_jaxdist(0, ps_port, worker_ports, logdir,
                            train_steps=8, extra=extra)
        w1 = launch_jaxdist(1, ps_port, worker_ports, logdir,
                            train_steps=8, extra=extra)
        out0, out1 = finish(w0, timeout=TIMEOUT * 2), finish(
            w1, timeout=TIMEOUT * 2)
        assert w0.returncode == 0, out0
        assert w1.returncode == 0, out1
        # FSDP really sharded params over the cross-process data axis.
        assert "PartitionSpec('data'" in out0, out0
        # Lockstep losses across the boundary, and training progressed.
        l0, l1 = parse_losses(out0), parse_losses(out1)
        assert l0 and l0 == l1, (l0, l1)
        vals = list(l0.values())
        assert all(np.isfinite(v) for v in vals), l0
        # Global step advanced (the horizon is measured in global steps;
        # the final step's log line lands before the stop check, so the
        # last LOGGED step is earlier than the 8-step horizon).
        import re
        last_global = max(int(m) for m in re.findall(
            r"\(global step:(\d+)\)", out0))
        assert last_global >= 4, out0
    finally:
        ps.send_signal(signal.SIGTERM)
        ps.wait(timeout=10)
