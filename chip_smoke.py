#!/usr/bin/env python3
"""Smoke run of the program's command lines on the chip: the quickest proof
that the system still starts on a TPU v5e.

    python chip_smoke.py              # one chip: two phases
    python chip_smoke.py --chips 4    # one four-chip host: train.py on four

This parent process never imports JAX (nor the package, whose ``__init__``
imports it): a chip belongs to one process at a time, so every phase runs
in children that take the chip, finish and release it, one after another.
Children that need the chip are pinned to it through ``JAX_PLATFORMS``
where the environment does not already say (JAX left to itself carries on
on the CPU when it finds no TPU), and the probe asserts
``jax.devices()[0].platform == "tpu"`` before anything else runs.

One chip:

- ``train_cli``        PS + worker of ``python -m distributed_tensorflow_tpu.train``
                       at the reference's MNIST hyperparameters, then the
                       same worker again resuming from its checkpoint.
- ``train_serve_cli``  ``gpt_mini`` trained through the CLI (BPE corpus, so
                       the C++ tokenizer builds), served by
                       ``python -m distributed_tensorflow_tpu.tools.serve``,
                       queried over plain HTTP.

Four chips (``--chips 4``):

- ``train_cli_dp4``    ``train.py`` alone on the four chips: its records
                       name four devices of the probed kind, its losses
                       are finite and fall.

These are the paths no cell of the benchmark runs (the cells build their
models in code: ROADMAP R7).  What this script used to build by hand at an
invented width is held by the cells of ``BENCHMARK.json`` on every PR:

- Mosaic calls in the compiled step, on one chip and on the four-device
  mesh: ``train_mosaic_calls`` in ``train_gpt2m_1chip`` and
  ``train_gpt2m_dp4``; an all-reduce in the four-device program:
  ``train_collective_pct`` there;
- losses of the step against a reference (and, on four chips, against the
  one-device program): the train cells' ``loss_gap_step1..3``,
  ``first_grad_gap`` and ``param_change_gap``;
- a non-zero HBM peak: ``train_hbm_peak_gib`` and ``chat_``/``lp_``/
  ``ohlp_hbm_peak_gib``;
- served tokens against a reference, over HTTP through ``ServingServer``,
  ``FairScheduler`` and ``DecodeEngine``: ``served_logit_gap_mean`` and
  ``_widest`` in the three serving cells; no compile after warm-up:
  ``chat_``/``lp_``/``ohlp_compiles_in_window``.

Every phase prints one JSON line; a failed assertion or child ends the run
non-zero.  On success the LAST line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``--rehearse`` runs the same control flow on whatever backend JAX finds (the
CPU), skips the checks only a chip can meet (the peak table's row, the HBM
peak), never prints ``"ok": true`` and exits 4 when every phase passed.  It
is a rehearsal of the script, not a run of the system.

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
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.join(REPO, "distributed_tensorflow_tpu")
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
REHEARSAL_EXIT = 4

#: ``train.py``'s flags for the reference's MNIST job (``distributed.py``'s
#: hyperparameters, synthetic data), shared by the one- and four-chip legs.
MNIST_FLAGS = ["--model=mnist_mlp", "--data_dir=/nonexistent",
               "--hidden_units=100", "--batch_size=100",
               "--learning_rate=0.01", "--sync_replicas=true",
               "--steps_per_call=10", "--log_every=50"]


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
# Parent: process management.  No JAX below this line until "The probe".
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
        """This file's one child of its own: it takes the chip, says what
        it found on its last stdout line and lets go."""
        cmd = [sys.executable, os.path.abspath(__file__), "--child", "probe"]
        if self.rehearse:
            cmd.append("--rehearse")
        text = self.run("probe", cmd, 300.0)
        self.device = json.loads(
            [l for l in text.splitlines() if l.startswith("{")][-1])
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
                *train, "--job_name=worker", *MNIST_FLAGS,
                f"--train_steps={steps}", "--save_interval_steps=100",
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

    def train_cli_dp4(self) -> dict:
        """The trainer itself on the four chips."""
        asserted: list[str] = []
        metrics = os.path.join(OUT_DIR, "train_cli_dp4.jsonl")
        self.run("train_cli_dp4", [
            sys.executable, "-m", "distributed_tensorflow_tpu.train",
            "--job_name=worker", "--task_index=0", "--ps_hosts=",
            f"--worker_hosts=localhost:{free_port()}",
            *MNIST_FLAGS, "--train_steps=200",
            f"--logdir={os.path.join(self.work, 'mnist4')}",
            f"--metrics_file={metrics}"], 600.0)
        steps = self.check_train_records(read_jsonl(metrics), asserted, 4)
        check(steps[-1]["loss"] < steps[0]["loss"],
              "train.py on four chips: loss decreased", asserted)
        self.cache_stats("train_cli_dp4")
        return dict(asserted=asserted,
                    losses=[steps[0]["loss"], steps[-1]["loss"]],
                    hbm_peak_bytes=steps[-1]["hbm_peak_bytes"])


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
        phases = ([smoke.train_cli_dp4] if args.chips == 4 else
                  [smoke.train_cli, smoke.train_serve_cli])
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
# The probe: one process that takes the chip and releases it.
# =====================================================================


def child_probe(args) -> dict:
    """The compile cache, then the device."""
    from distributed_tensorflow_tpu.utils.backend import configure_backend
    configure_backend()
    import jax
    dev = jax.devices()[0]
    if not args.rehearse:
        check(dev.platform == "tpu",
              f"need a TPU, JAX found {dev.platform!r} ({jax.devices()})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run train.py on the four chips of one "
                             "host (and no other phase)")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the corpus and the prompts")
    parser.add_argument("--rehearse", action="store_true",
                        help="whatever backend JAX finds; never prints "
                             "ok:true (see the module text)")
    parser.add_argument("--child", choices=("probe",),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        emit(**child_probe(args))
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
