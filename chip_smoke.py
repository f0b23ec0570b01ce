#!/usr/bin/env python3
"""Smoke run of the main path on the chip: the quickest proof that the
system still starts on a TPU v5e.

    python chip_smoke.py              # one chip: four phases
    python chip_smoke.py --chips 4    # one four-chip host: the dp4 phase only

This parent process never imports JAX (nor the package, whose ``__init__``
imports it): a chip belongs to one process at a time, so every phase runs
in children that take the chip, finish and release it, one after another.
Children that need the chip are pinned to it through ``JAX_PLATFORMS``
where the environment does not already say (JAX left to itself carries on
on the CPU when it finds no TPU), and every child written here first
asserts ``jax.devices()[0].platform == "tpu"``.

One chip (the driver runs this):

- ``train_cli``        PS + worker of ``python -m distributed_tensorflow_tpu.train``
                       at the reference's MNIST hyperparameters, then the
                       same worker again resuming from its checkpoint.
- ``train_serve_cli``  ``gpt_mini`` trained through the CLI (BPE corpus, so
                       the C++ tokenizer builds), served by
                       ``python -m distributed_tensorflow_tpu.tools.serve``,
                       queried over plain HTTP.
- ``train_wide``       the widest model the repo builds (406M GPT, L=8
                       H=2048 I=8192 S=1024 bf16, pallas attention) through
                       ``TrainState`` + ``make_optimizer`` +
                       ``build_sync_train_step``; reads the compiled program
                       for Mosaic calls instead of trusting the flag.
- ``serve_wide``       the same config through ``DecodeEngine`` +
                       ``FairScheduler`` + ``ServingServer`` over HTTP.

Four chips (``--chips 4``, run by the builder):

- ``dp4``              the 406M sync step on a one-device mesh and on the
                       four-device mesh, same global batch; placement,
                       all-reduce and loss agreement; what attention program
                       each lowered (the four-device program must hold Mosaic
                       calls: ``flash_attention`` maps its kernel over the
                       mesh's batch axis; the one-device program on a
                       four-chip host still lowers dense, because
                       ``_gspmd_hazard()`` asks ``jax.device_count()``); then
                       ``train.py`` on the four chips.

Every phase prints one JSON line; a failed assertion or child ends the run
non-zero.  On success the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``--rehearse`` runs the same control flow at a tiny size on whatever
backend JAX finds (the CPU, with pallas interpreted), skips the checks only
a chip can meet (Mosaic calls, HBM peak), never prints ``"ok": true`` and
exits 4 when every phase passed.  It is a rehearsal of the script, not a
run of the system.

Child logs land in ``chiprun_out/chip_smoke/`` (the compile cache's hit and
miss counts are read from them).
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(REPO, "distributed_tensorflow_tpu")
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
REHEARSAL_EXIT = 4

#: The widest model the repo builds (bench.py's flagship) and the tiny
#: stand-in a rehearsal uses.  ``pages``/``table`` size the serving pool.
WIDE = dict(hidden_size=2048, num_layers=8, num_heads=16,
            intermediate_size=8192, seq=1024, batch=8,
            pages=384, table=40, prompts=(64, 100, 250, 512), gen=32)
TINY = dict(hidden_size=256, num_layers=2, num_heads=2,
            intermediate_size=512, seq=128, batch=8,
            pages=64, table=8, prompts=(16, 30, 48, 64), gen=8)

#: dp4's stated tolerance: the one- and four-device programs differ in
#: reduction order only (bf16 activations, f32 loss).
DP4_LOSS_RTOL = 2e-2


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def check(cond, what: str, asserted: list | None = None) -> None:
    """An assertion that survives ``python -O`` and names itself on the
    phase line."""
    if not cond:
        raise AssertionError(what)
    if asserted is not None:
        asserted.append(what)


# =====================================================================
# Parent: process management.  No JAX below this line until "Children".
# =====================================================================


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body: dict | None = None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    with urllib.request.urlopen(
            urllib.request.Request(url, data=data), timeout=timeout) as r:
        return json.loads(r.read())


def wait_until(probe, timeout: float, what: str, proc=None):
    """Poll ``probe()`` until it returns non-None; fail fast if ``proc``
    (the child being waited on) has already exited."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(f"{what}: child exited rc={proc.returncode}")
        try:
            out = probe()
        except OSError:
            out = None
        if out is not None:
            return out
        time.sleep(0.25)
    raise TimeoutError(f"{what}: not ready after {timeout:.0f}s")


def read_jsonl(path: str) -> list[dict]:
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def random_prompts(seed: int, vocab: int, lengths) -> list[list[int]]:
    rng = random.Random(seed)
    return [[rng.randrange(vocab) for _ in range(n)] for n in lengths]


def post_generate(base: str, vocab: int, prompt: list[int],
                  gen: int) -> list[int]:
    """One ``POST /generate``; checks the answer is well formed and
    returns the generated tokens."""
    reply = http_json(f"{base}/generate",
                      {"prompt": prompt, "num_tokens": gen}, timeout=600.0)
    toks, n = reply["tokens"], len(prompt)
    check(toks[:n] == prompt and len(toks) == n + gen
          and reply["tokens_out"] == gen,
          f"prompt of {n} echoed and {gen} tokens generated")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
          f"generated tokens in [0, {vocab})")
    return toks[n:]


WELL_FORMED = "answers of the asked length, tokens in range"


class Smoke:
    """The parent's state: where things go and which children are alive."""

    def __init__(self, rehearse: bool, seed: int):
        self.rehearse = rehearse
        self.seed = seed
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")  # outputs only
        shutil.rmtree(OUT_DIR, ignore_errors=True)
        os.makedirs(OUT_DIR)
        self.procs: dict[str, subprocess.Popen] = {}
        self.device: dict = {}

    # ------------------------------------------------------ children

    def env(self) -> dict:
        env = dict(os.environ)
        if not self.rehearse:
            # No --platform anywhere: where the environment leaves the
            # choice to JAX, pin it, so a child that cannot take the chip
            # raises instead of carrying on on the CPU.
            env.setdefault("JAX_PLATFORMS", "tpu,cpu")
        # The compile cache's hits and misses, on the child's stderr.
        env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
        env.setdefault("TPU_LOG_DIR", "disabled")
        return env

    def spawn(self, name: str, cmd: list[str]) -> subprocess.Popen:
        log = open(os.path.join(OUT_DIR, f"{name}.log"), "w")
        proc = subprocess.Popen(cmd, cwd=REPO, env=self.env(), stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        log.close()
        self.procs[name] = proc
        return proc

    def log_text(self, name: str) -> str:
        with open(os.path.join(OUT_DIR, f"{name}.log"),
                  errors="replace") as fh:
            return fh.read()

    def run(self, name: str, cmd: list[str], timeout: float) -> str:
        """Run a child to its end; a non-zero exit fails the run with the
        end of the child's log on stderr.  Returns the log."""
        proc = self.spawn(name, cmd)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        text = self.log_text(name)
        if rc != 0:
            keep = [l for l in text.splitlines()
                    if not l.startswith("DEBUG:")]
            sys.stderr.write(f"--- {name} (rc={rc}) ---\n"
                             + "\n".join(keep[-60:]) + "\n")
            raise RuntimeError(
                f"child {name} " + (f"exited {rc}" if rc is not None else
                                    f"still running after {timeout:.0f}s"))
        return text

    def child(self, name: str, timeout: float) -> dict:
        """Run one of this file's own children; its last stdout line is its
        JSON result."""
        cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
               "--seed", str(self.seed)]
        if self.rehearse:
            cmd.append("--rehearse")
        text = self.run(name, cmd, timeout)
        lines = [l for l in text.splitlines() if l.startswith("{")]
        return json.loads(lines[-1])

    def stop(self, proc: subprocess.Popen, timeout: float = 60.0):
        """SIGTERM and wait: the clean-shutdown path.  Returns the exit
        code, or None when the child had to be killed."""
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            return None

    def cleanup(self) -> None:
        for proc in self.procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        shutil.rmtree(self.work, ignore_errors=True)

    def cache_stats(self, *names: str) -> None:
        """One line per child: persistent-cache hits and misses as JAX's
        compiler logged them (a miss is a compile; only compiles over
        JAX's minimum compile time are then written)."""
        for name in names:
            text = self.log_text(name)
            hits = re.findall(
                r"Persistent compilation cache hit for '([^']+)'", text)
            misses = re.findall(
                r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'", text)
            emit(compile_cache=name, hits=len(hits), misses=len(misses),
                 hit_programs=sorted(set(hits))[:12])

    # -------------------------------------------------------- prepare

    def prepare(self) -> None:
        """Start from no prebuilt native binary: what runs is compiled
        from csrc/ as git would commit it."""
        stale = (glob.glob(os.path.join(PKG, "cluster", "libdtfcoord*.so"))
                 + glob.glob(os.path.join(PKG, "data", "libdtfbpe*.so")))
        for path in stale:
            os.unlink(path)
        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        emit(phase="prepare", removed_native_binaries=len(stale),
             compile_cache_dir=env_dir or os.path.join(REPO, ".jax_cache"),
             compile_cache_placed_by=("JAX_COMPILATION_CACHE_DIR"
                                      if env_dir else "checkout default"),
             rehearsal=self.rehearse)

    def probe(self, want_count: int | None) -> None:
        self.device = self.child("probe", 300.0)
        emit(phase="probe", device=self.device)
        if want_count is not None:
            check(self.device["count"] == want_count,
                  f"this path needs {want_count} devices, JAX reports "
                  f"{self.device['count']}")

    # --------------------------------------------------- train records

    def check_train_records(self, records: list[dict], asserted: list,
                            n_devices: int) -> list[dict]:
        """What every ``train.py`` run's ``--metrics_file`` must show."""
        meta = next(r for r in records if r["kind"] == "run_meta")
        check(meta["device_kind"] == self.device["kind"]
              and meta["n_devices"] == n_devices,
              f"train.py reports {n_devices} x {self.device['kind']}",
              asserted)
        steps = [r for r in records if r["kind"] == "train_step"]
        check(steps and all(math.isfinite(r["loss"]) for r in steps),
              "losses finite", asserted)
        if not self.rehearse:
            check(meta["peak_flops_per_sec"],
                  "device_kind matches a key of the peak table", asserted)
            check(all(r["hbm_peak_bytes"] > 0 for r in steps),
                  "non-zero HBM peak in every train_step record", asserted)
        return steps

    # ---------------------------------------------------------- phases

    def train_cli(self) -> dict:
        asserted: list[str] = []
        logdir = os.path.join(self.work, "mnist")
        ps_port, w_port = free_port(), free_port()
        train = [sys.executable, "-m", "distributed_tensorflow_tpu.train",
                 "--task_index=0", f"--ps_hosts=localhost:{ps_port}",
                 f"--worker_hosts=localhost:{w_port}", f"--logdir={logdir}"]
        ps = self.spawn("train_cli_ps", [*train, "--job_name=ps"])

        def listening():
            with socket.create_connection(("127.0.0.1", ps_port), 1.0):
                return True

        # The PS compiles the coordination service from csrc/ first.
        wait_until(listening, 300.0, "coordination service", ps)

        def worker(name: str, steps: int) -> tuple[str, list[dict]]:
            metrics = os.path.join(OUT_DIR, f"{name}.jsonl")
            out = self.run(name, [
                *train, "--job_name=worker", "--model=mnist_mlp",
                "--data_dir=/nonexistent", "--hidden_units=100",
                "--batch_size=100", "--learning_rate=0.01",
                "--sync_replicas=true", f"--train_steps={steps}",
                "--steps_per_call=10", "--log_every=50",
                "--save_interval_steps=100",
                f"--metrics_file={metrics}"], 600.0)
            check(ps.poll() is None,
                  "PS alive while and after the worker held the chip")
            return out, read_jsonl(metrics)

        out, records = worker("train_cli_worker", 200)
        check("running standalone" not in out
              and any(r["kind"] == "clock_sync"
                      and r.get("source") == "coord_time" for r in records),
              "worker registered with the coordination service", asserted)
        steps = self.check_train_records(records, asserted,
                                         self.device["count"])
        check(steps[-1]["loss"] < steps[0]["loss"], "loss decreased",
              asserted)
        last = steps[-1]["step"]

        out2, records2 = worker("train_cli_resume", 300)
        steps2 = self.check_train_records(records2, [], self.device["count"])
        check(steps2[0]["step"] > last and steps2[-1]["step"] >= 300,
              f"resumed run continues after global step {last}", asserted)
        asserted.append("PS alive while and after the worker held the chip")
        rc = self.stop(ps)
        self.cache_stats("train_cli_worker", "train_cli_resume")
        return dict(asserted=asserted, first_loss=steps[0]["loss"],
                    last_loss=steps2[-1]["loss"],
                    resumed_at_global_step=steps2[0]["step"],
                    hbm_peak_bytes=steps2[-1]["hbm_peak_bytes"],
                    ps_exit_code=rc)

    def write_corpus(self) -> str:
        """A seeded pseudo-text corpus: ``*.txt`` under a data dir."""
        rng = random.Random(self.seed)
        words = ("the quick brown fox jumps over lazy dog tensor mesh chip "
                 "shard replica token page cache").split()
        data_dir = os.path.join(self.work, "corpus")
        os.makedirs(data_dir)
        with open(os.path.join(data_dir, "corpus.txt"), "w") as fh:
            for _ in range(4000):
                fh.write(" ".join(rng.choice(words) for _ in
                                  range(rng.randint(4, 12))) + ".\n")
        return data_dir

    def train_serve_cli(self) -> dict:
        asserted: list[str] = []
        logdir = os.path.join(self.work, "gpt")
        metrics = os.path.join(OUT_DIR, "train_serve_cli_train.jsonl")
        # No PS: the chief hosts the coordination service itself.
        self.run("train_serve_cli_train", [
            sys.executable, "-m", "distributed_tensorflow_tpu.train",
            "--job_name=worker", "--task_index=0", "--ps_hosts=",
            f"--worker_hosts=localhost:{free_port()}",
            "--model=gpt_mini", f"--data_dir={self.write_corpus()}",
            "--gpt_tokenizer=bpe", "--gpt_bpe_vocab=384",
            "--bert_seq_len=128", "--batch_size=16",
            "--learning_rate=0.001", "--sync_replicas=true",
            "--train_steps=40", "--steps_per_call=4", "--log_every=20",
            "--save_interval_steps=20", f"--logdir={logdir}",
            f"--metrics_file={metrics}"], 600.0)
        self.check_train_records(read_jsonl(metrics), asserted,
                                 self.device["count"])
        check(glob.glob(os.path.join(PKG, "cluster", "libdtfcoord.*.so"))
              and glob.glob(os.path.join(PKG, "data", "libdtfbpe.*.so")),
              "both native libraries were compiled from csrc/ during the "
              "run", asserted)

        port = free_port()
        base = f"http://127.0.0.1:{port}"
        serve = self.spawn("train_serve_cli_serve", [
            sys.executable, "-m", "distributed_tensorflow_tpu.tools.serve",
            "--logdir", os.path.join(logdir, "gpt_mini"),
            "--port", str(port), "--max_pages_per_seq", "16"])
        health = wait_until(lambda: http_json(f"{base}/healthz", timeout=5.0),
                            600.0, "tools.serve /healthz", serve)
        check(health["status"] == "ok" and health["model"] == "gpt_mini"
              and health["vocab_size"] == 384,
              "/healthz ok for the trained checkpoint", asserted)
        before = http_json(f"{base}/statz")
        lengths, gen = (3, 17, 40, 90, 150), 16
        for prompt in random_prompts(self.seed, health["vocab_size"],
                                     lengths):
            post_generate(base, health["vocab_size"], prompt, gen)
        asserted.append(WELL_FORMED)
        after = http_json(f"{base}/statz")
        done = sum(t["completed"] for t in after["tenants"].values())
        check(done == len(lengths)
              and after["engine"]["engine_step"]
              > before["engine"]["engine_step"]
              and after["latency"]["serve_ttft_ms"]["count"] == len(lengths),
              "statz counters moved", asserted)
        rc = self.stop(serve)
        check(rc == 0, f"clean shutdown on SIGTERM (exit {rc})", asserted)
        self.cache_stats("train_serve_cli_train", "train_serve_cli_serve")
        return dict(asserted=asserted, requests=len(lengths),
                    prompt_lengths=lengths, generated=gen,
                    engine_steps=after["engine"]["engine_step"])

    def train_wide(self) -> dict:
        out = self.child("train_wide", 900.0)
        self.cache_stats("train_wide")
        return out

    def serve_wide(self) -> dict:
        out = self.child("serve_wide", 900.0)
        self.cache_stats("serve_wide")
        return out

    def dp4(self) -> dict:
        out = self.child("dp4", 1500.0)
        self.cache_stats("dp4")
        asserted = out["asserted"]
        # Then the trainer itself on the four chips.
        metrics = os.path.join(OUT_DIR, "dp4_train_cli.jsonl")
        self.run("dp4_train_cli", [
            sys.executable, "-m", "distributed_tensorflow_tpu.train",
            "--job_name=worker", "--task_index=0", "--ps_hosts=",
            f"--worker_hosts=localhost:{free_port()}",
            "--model=mnist_mlp", "--data_dir=/nonexistent",
            "--hidden_units=100", "--batch_size=100",
            "--learning_rate=0.01", "--sync_replicas=true",
            "--train_steps=200", "--steps_per_call=10", "--log_every=50",
            f"--logdir={os.path.join(self.work, 'mnist4')}",
            f"--metrics_file={metrics}"], 600.0)
        steps = self.check_train_records(read_jsonl(metrics), asserted, 4)
        check(steps[-1]["loss"] < steps[0]["loss"],
              "train.py on four chips: loss decreased", asserted)
        out["train_cli_losses"] = [steps[0]["loss"], steps[-1]["loss"]]
        return out


def parent(args) -> int:
    t_start = time.monotonic()
    smoke = Smoke(args.rehearse, args.seed)
    # A terminated parent still stops its children (the finally below).
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        # The probe first: with no accelerator the run ends here, before
        # it has touched the checkout.
        smoke.probe(4 if args.chips == 4 else None)
        smoke.prepare()
        phases = ([smoke.dp4] if args.chips == 4 else
                  [smoke.train_cli, smoke.train_serve_cli,
                   smoke.train_wide, smoke.serve_wide])
        for phase in phases:
            t0 = time.monotonic()
            result = phase()
            emit(phase=phase.__name__,
                 seconds=round(time.monotonic() - t0, 1), **result)
    finally:
        smoke.cleanup()
    emit(wall_seconds=round(time.monotonic() - t_start, 1))
    if args.rehearse:
        emit(ok=False, rehearsal=True, device=smoke.device)
        return REHEARSAL_EXIT
    emit(ok=True, device=smoke.device)
    return 0


# =====================================================================
# Children: each is one process that takes the chip and releases it.
# =====================================================================


def child_setup(args):
    """First thing in every child written here: the compile cache, then
    the device.  Returns ``(jax, device_dict)``."""
    from distributed_tensorflow_tpu.utils.backend import configure_backend
    configure_backend()
    import jax
    dev = jax.devices()[0]
    if not args.rehearse:
        check(dev.platform == "tpu",
              f"need a TPU, JAX found {dev.platform!r} ({jax.devices()})")
    return jax, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())}


def child_probe(args) -> dict:
    return child_setup(args)[1]


def wide_config(size: dict):
    import dataclasses

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    return dataclasses.replace(
        gpt_lib.mini(), hidden_size=size["hidden_size"],
        num_layers=size["num_layers"], num_heads=size["num_heads"],
        intermediate_size=size["intermediate_size"],
        max_position=size["seq"], dtype="bfloat16",
        attention_backend="pallas")


def n_params(tree) -> int:
    import jax
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def peak_hbm() -> int:
    from distributed_tensorflow_tpu.utils.profiling import (
        device_memory_stats)
    return max(d["peak_bytes_in_use"] for d in device_memory_stats())


def sync_program(jax, size: dict, seed: int, mesh):
    """The 406M sync train step as the trainer builds it, compiled for
    ``mesh``.  Returns ``(compiled, state, batch, text, compile_seconds)``.
    """
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib
    from distributed_tensorflow_tpu.parallel.sharding import replicate_tree
    from distributed_tensorflow_tpu.training.optimizers import make_optimizer
    from distributed_tensorflow_tpu.training.state import TrainState

    cfg = wide_config(size)
    model = gpt_lib.GptLM(cfg)
    tokens = jnp.asarray(gpt_lib.synthetic_lm_batch(
        seed, size["batch"], size["seq"], cfg)["tokens"])
    params = model.init(jax.random.PRNGKey(seed), tokens[:1, :8])["params"]
    apply_fn = lambda p, t: model.apply({"params": p}, t)
    state = TrainState.create(apply_fn, params, make_optimizer("adam", 3e-4))
    state = state.replace(
        params=replicate_tree(mesh, state.params),
        opt_state=replicate_tree(mesh, state.opt_state),
        global_step=replicate_tree(mesh, state.global_step))

    def loss_fn(p, batch):
        loss, acc = gpt_lib.lm_loss(apply_fn(p, batch), batch)
        return loss, {"accuracy": acc}

    step = sync_lib.build_sync_train_step(mesh, loss_fn)
    batch = jax.device_put(tokens, mesh_lib.data_sharded(mesh))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    return (compiled, state, batch, compiled.as_text(),
            time.perf_counter() - t0)


def lowered_attention(device: dict, mosaic_calls: int) -> str:
    """In words, what the compiled step holds for the attention the
    config asked for."""
    if device["platform"] != "tpu":
        return "pallas requested, interpreted (no Mosaic off the chip)"
    return ("pallas requested, Mosaic kernels lowered" if mosaic_calls
            else "pallas requested, dense XLA lowered")


def child_train_wide(args) -> dict:
    jax, device = child_setup(args)
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    size = TINY if args.rehearse else WIDE
    asserted: list[str] = []
    compiled, state, batch, text, compile_s = sync_program(
        jax, size, args.seed, mesh_lib.data_parallel_mesh())
    params = n_params(state.params)
    mosaic = text.count("tpu_custom_call")
    if not args.rehearse:
        check(mosaic > 0, "compiled step contains Mosaic calls "
              "(tpu_custom_call): the kernel is in the program", asserted)
    losses, step_s = [], []
    for _ in range(10):   # Adam's first steps on random weights overshoot
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))  # the fetch ends the step
        step_s.append(time.perf_counter() - t0)
    check(all(math.isfinite(l) for l in losses), "losses finite", asserted)
    check(losses[-1] < losses[0], "loss lower at the last step than at the "
          "first", asserted)
    peak = peak_hbm()
    if not args.rehearse:
        check(peak > 0, "non-zero peak HBM from memory_stats()", asserted)
    return dict(asserted=asserted, device=device, n_params=params,
                mosaic_calls=mosaic,
                attention=lowered_attention(device, mosaic),
                losses=[round(l, 4) for l in losses],
                peak_hbm_bytes=peak,
                smoke_output_not_a_metric=dict(
                    compile_seconds=round(compile_s, 1),
                    step_ms_after_warmup=round(
                        1e3 * sorted(step_s[2:])[len(step_s[2:]) // 2], 1)))


def child_serve_wide(args) -> dict:
    jax, device = child_setup(args)
    import jax.numpy as jnp

    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                           EngineConfig)
    from distributed_tensorflow_tpu.serving.scheduler import FairScheduler
    from distributed_tensorflow_tpu.serving.server import ServingServer
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
    from distributed_tensorflow_tpu.utils.telemetry import Telemetry

    size = TINY if args.rehearse else WIDE
    asserted: list[str] = []
    cfg = wide_config(size)
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    engine = DecodeEngine(
        model, params,
        EngineConfig(num_slots=8, page_size=16, num_pages=size["pages"],
                     max_pages_per_seq=size["table"]),
        telemetry=Telemetry(MetricsLogger(None)))
    server = ServingServer(engine, FairScheduler(), port=0,
                           telemetry=engine.telemetry,
                           meta={"model": "gpt_wide",
                                 "vocab_size": cfg.vocab_size})
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        check(http_json(f"{base}/healthz")["status"] == "ok", "/healthz ok",
              asserted)
        gen, vocab = size["gen"], cfg.vocab_size
        # Warm-up: one request per prompt bucket (a bucket is a prompt's
        # page count) compiles that bucket's prefill; the first also
        # compiles the resident decode step.
        warm = random_prompts(args.seed, vocab, size["prompts"])
        answers = [post_generate(base, vocab, p, gen) for p in warm]
        warm_compiles = len(compiles)
        # The window: the same buckets again (other lengths, other
        # tokens), all at once, so lanes join and leave mid-decode.
        errors: list[BaseException] = []

        def one(prompt):
            try:
                post_generate(base, vocab, prompt, gen)
            except BaseException as e:  # re-raised below, on the main thread
                errors.append(e)

        threads = [threading.Thread(target=one, args=(p,))
                   for p in random_prompts(args.seed + 1, vocab,
                                           [n - 3 for n in size["prompts"]])]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600.0)
        if errors:
            raise errors[0]
        check(not any(t.is_alive() for t in threads),
              "every windowed request returned")
        asserted.append(WELL_FORMED)
        in_window = len(compiles) - warm_compiles
        check(in_window == 0, "zero compilations after warm-up for "
              "repeated prompt buckets", asserted)
        stats = http_json(f"{base}/statz")
        n_req = 2 * len(size["prompts"])
        check(sum(t["completed"] for t in stats["tenants"].values()) == n_req
              and stats["engine"]["engine_step"] > 0
              and stats["latency"]["serve_ttft_ms"]["count"] == n_req,
              "statz counters moved", asserted)
    finally:
        server.shutdown()
    peak = peak_hbm()
    # Not gated: paged engine vs contiguous cache at this width, greedy.
    # bf16 ties make exact equality the wrong gate.
    ref = gpt_lib.generate_cached(
        model, params, jnp.asarray([warm[0]], jnp.int32), gen)
    ref = [int(t) for t in ref[0, len(warm[0]):]]
    agree = sum(1 for a, b in zip(answers[0], ref) if a == b)
    return dict(asserted=asserted, device=device, n_params=n_params(params),
                requests=n_req, prompt_lengths=size["prompts"],
                generated=gen, warmup_compilations=warm_compiles,
                compilations_in_window=in_window,
                prefill_programs=stats["engine"]["compile_cache"][
                    "prefill_programs"],
                engine_steps=stats["engine"]["engine_step"],
                peak_hbm_bytes=peak,
                paged_vs_generate_cached_tokens_agree=f"{agree}/{gen}")


def child_dp4(args) -> dict:
    jax, device = child_setup(args)
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    size = TINY if args.rehearse else WIDE
    asserted: list[str] = []
    check(device["count"] == 4, "four devices visible", asserted)

    programs = {}
    for n in (1, 4):
        mesh = mesh_lib.data_parallel_mesh(num_devices=n)
        compiled, state, batch, text, compile_s = sync_program(
            jax, size, args.seed, mesh)
        mosaic = text.count("tpu_custom_call")
        if n == 4:
            check(all(len(x.sharding.device_set) == 4
                      for x in jax.tree.leaves(state.params)),
                  "every parameter's sharding spans four devices", asserted)
            check(len({s.device for s in batch.addressable_shards}) == 4,
                  "the batch's shards sit on four distinct devices",
                  asserted)
            check("all-reduce" in text,
                  "the four-chip program contains an all-reduce", asserted)
            if not args.rehearse:
                check(mosaic > 0, "the four-device program contains Mosaic "
                      "calls (tpu_custom_call): the flash kernel is mapped "
                      "over the mesh's batch axis, not dropped for dense "
                      "XLA", asserted)
        losses = []
        for _ in range(3):
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))
        programs[f"{n}_device_mesh"] = dict(
            losses=[round(l, 4) for l in losses], mosaic_calls=mosaic,
            attention=lowered_attention(device, mosaic),
            all_reduces=text.count("all-reduce("),
            compile_seconds=round(compile_s, 1))
        del compiled, state, batch, metrics  # free the chip for the next
    l1 = programs["1_device_mesh"]["losses"]
    l4 = programs["4_device_mesh"]["losses"]
    check(all(math.isfinite(x) for x in l1 + l4),
          "losses of both programs finite", asserted)
    check(all(abs(a - b) <= DP4_LOSS_RTOL * abs(a) for a, b in zip(l1, l4)),
          f"per-step losses agree within rtol {DP4_LOSS_RTOL} "
          "(8x1 vs 2x4, bf16)", asserted)
    return dict(asserted=asserted, device=device, programs=programs,
                peak_hbm_bytes=peak_hbm())


CHILDREN = {"probe": child_probe, "train_wide": child_train_wide,
            "serve_wide": child_serve_wide, "dp4": child_dp4}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run the dp4 phase (and no other) on a "
                             "four-chip host")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the random weights, batches and "
                             "prompts")
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on whatever backend JAX finds; "
                             "never prints ok:true (see the module text)")
    parser.add_argument("--child", choices=sorted(CHILDREN),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        emit(**CHILDREN[args.child](args))
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
