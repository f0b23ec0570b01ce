"""The one process of a run that holds the chip.  ``run.py`` starts it,
speaks to it in JSON lines over its stdin and stdout, and never imports JAX
itself.  From the program it takes the system under test
(``run_training_loop`` + ``build_sync_train_step``; ``ServingServer`` +
``FairScheduler`` + ``DecodeEngine``) and nothing that measures: clocks,
step logs, the trace reducer and the check of outputs are the benchmark's.

Lines this process prints on stdout are events, ``{"event": ...}``; the last
one is ``{"event": "result", ...}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
import urllib.request

from perfbench import check, spec, stats, traffic as traffic_lib

CLOCK = time.monotonic          # CLOCK_MONOTONIC: one clock for both processes


def say(event: str, **fields) -> None:
    print(json.dumps({"event": event, **fields}), flush=True)


def note(msg: str) -> None:
    print(f"[worker {CLOCK():.2f}] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ set-up


class Compiles:
    """Compilations (and persistent-cache loads) with the time each ended."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load"}

    def __init__(self, jax):
        self.log: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        kind = self.EVENTS.get(event)
        if kind:
            self.log.append((CLOCK(), kind, float(secs)))

    def between(self, t0, t1, kind=None):
        return [x for x in self.log
                if t0 <= x[0] <= t1 and (kind is None or x[1] == kind)]


def backend(args, chips: int):
    """The compile cache, then the device.  No chip, no run."""
    from distributed_tensorflow_tpu.utils.backend import configure_backend
    configure_backend()
    import jax
    devs = jax.devices()
    if not args.rehearse and devs[0].platform != "tpu":
        raise SystemExit(f"need a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"cell needs {chips} chip(s), JAX found {len(devs)}")
    return jax, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                 "count": len(devs)}


def memory_peak(jax, devices) -> int:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        except Exception:  # noqa: BLE001 — backends without memory_stats
            pass
    return peak


def hashable(value):
    """JSON's lists as tuples, all the way down: a frozen config is hashed."""
    if isinstance(value, list):
        return tuple(hashable(v) for v in value)
    return value


def gpt_config(cell: dict, **over):
    """The program's config from the configuration file's ``model`` (but
    ``norm_eps``, which only the reference reads)."""
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    model = {k: hashable(v) for k, v in cell["config"]["model"].items()
             if k != "norm_eps"}
    unknown = sorted(set(model) - {f.name for f in dataclasses.fields(
        gpt_lib.GptConfig)})
    if unknown:
        raise SystemExit(f"{cell['config_file']}: \"model\" has {unknown}, "
                         f"which the program's GptConfig does not have")
    return gpt_lib.GptConfig(**{**model, **over})


def check_tree(jax, model, params, cfg) -> int:
    """The benchmark lays the leaves out itself; fail loudly where the
    program's own tree has moved on."""
    import jax.numpy as jnp
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    a = {jax.tree_util.keystr(p): x.shape
         for p, x in jax.tree_util.tree_flatten_with_path(want)[0]}
    b = {jax.tree_util.keystr(p): x.shape
         for p, x in jax.tree_util.tree_flatten_with_path(params)[0]}
    if a != b:
        from perfbench import weights
        diff = sorted(set(a.items()) ^ set(b.items()))[:6]
        raise SystemExit(f"{weights.layout_file(cfg)} does not match the "
                         f"program's parameter tree: {diff}")
    return sum(int(x.size) for x in jax.tree.leaves(params))


class Tracer:
    """Profile a short slice into a directory the harness creates, then
    reduce it with the benchmark's own reader."""

    def __init__(self, jax, cell: str):
        self.jax = jax
        self.dir = os.path.join(spec.OUT_DIR, "trace", cell)
        self.t0 = self.t1 = None

    def start(self):
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # Device operations and the harness's annotations only: the Python
        # tracer would slow the host it is measuring and bloat the file.
        options = self.jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        self.jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t0 = CLOCK()

    def stop(self):
        self.t1 = CLOCK()       # before: writing the trace out takes long
        self.jax.profiler.stop_trace()

    def reduce(self) -> dict:
        from perfbench import xplane
        path = xplane.newest_xplane(self.dir)
        if path is None:
            raise SystemExit(f"the profiler wrote no .xplane.pb under "
                             f"{self.dir}")
        out = xplane.reduce(path)
        out["window_s"] = self.t1 - self.t0
        out["t0"], out["t1"] = self.t0, self.t1
        out["file_bytes"] = os.path.getsize(path)
        note(f"trace {path} ({out['file_bytes']} bytes): device planes "
             f"{out['found']['device_planes']} ops lines "
             f"{out['found']['ops_lines']} events {out['device_events']}")
        return out


# ---------------------------------------------------------------- training


class Records:
    """Stands where the program's ``MetricsLogger`` would: keeps its records
    in memory with the harness's clock on each."""

    def __init__(self):
        self.rows: list[dict] = []

    def log(self, step, **fields):
        self.rows.append({"t": CLOCK(), "step": int(step), **fields})

    def kind(self, kind):
        return [r for r in self.rows if r.get("kind") == kind]


class StepClock:
    """Passed to ``run_training_loop`` as its ``shutdown``: the loop asks
    ``requested()`` once after every finished step (the step's scalars have
    been fetched by then), so each call is a step boundary.  It opens the
    window after ``warm`` steps, closes it ``seconds`` later, and in a
    traced run keeps the loop going for ``trace_steps`` whole steps more
    under the profiler."""

    def __init__(self, seconds, warm, tokens_per_step, tracer, trace_steps):
        self.seconds, self.warm = seconds, warm
        self.tokens = tokens_per_step
        self.tracer, self.trace_steps = tracer, trace_steps
        self.bounds = [CLOCK()]
        self.t0 = self.t1 = None
        self.traced = 0

    def requested(self) -> bool:
        now = CLOCK()
        self.bounds.append(now)
        n = len(self.bounds) - 1
        if n == self.warm:
            self.t0, self.t1 = now, now + self.seconds
            return False
        if self.t1 is None or now < self.t1:
            return False
        if self.tracer is None:
            return True
        if self.tracer.t0 is None:
            self.tracer.start()
            self.bounds[-1] = CLOCK()   # the profiler's start is no step's
            return False
        self.traced += 1
        if self.traced >= self.trace_steps:
            self.tracer.stop()
            return True
        return False

    def steps(self) -> list[dict]:
        return [{"t_start": a, "t_end": b, "tokens": self.tokens}
                for a, b in zip(self.bounds, self.bounds[1:])]


class Feed:
    """What ``run_training_loop`` wants of a ``datasets`` object."""

    def __init__(self, stream):
        self.train = stream
        self.validation = self.test = None


def run_train(args, cell: dict) -> dict:
    t_begin = CLOCK()
    chips, cfg, tr = cell["chips"], cell["config"], cell["traffic"]
    jax, device = backend(args, chips)
    import jax.numpy as jnp
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.parallel import mesh as mesh_lib
    from distributed_tensorflow_tpu.parallel import sync as sync_lib
    from distributed_tensorflow_tpu.parallel.sharding import replicate_tree
    from distributed_tensorflow_tpu.training.loop import run_training_loop
    from distributed_tensorflow_tpu.training.optimizers import make_optimizer
    from distributed_tensorflow_tpu.training.state import TrainState
    from distributed_tensorflow_tpu.utils.telemetry import Telemetry
    from perfbench import weights

    compiles = Compiles(jax)
    phases = {"backend_s": CLOCK() - t_begin}
    mesh = mesh_lib.data_parallel_mesh(num_devices=chips)
    devices = list(mesh.devices.flat)
    over = cfg["lower_precision"] if args.control else {}
    gcfg = gpt_config(cell, **over)
    model = gpt_lib.GptLM(gcfg)
    maker = weights.Maker(cfg, sharding=mesh_lib.replicated(mesh))
    rows, seq = tr["batch_per_chip"] * chips, tr["seq_len"]
    stream = traffic_lib.PackedLmStream(tr, gcfg.vocab_size, args.seed)
    lr = tr["learning_rate"]

    def loss_fn(p, batch):
        loss, acc = gpt_lib.lm_loss(model.apply({"params": p}, batch), batch)
        return loss, {"accuracy": acc}

    step = sync_lib.build_sync_train_step(mesh, loss_fn)
    sharding = mesh_lib.data_sharded(mesh)
    compiled = {}

    apply_fn = lambda p, t: model.apply({"params": p}, t)  # noqa: E731
    tx = make_optimizer(tr["optimizer"], lr)

    def fresh_state(seed):
        params = weights.program_tree(seed, maker)
        state = TrainState.create(apply_fn, params, tx)
        return state.replace(
            opt_state=replicate_tree(mesh, state.opt_state),
            global_step=replicate_tree(mesh, state.global_step))

    def train_step(state, batch):
        with jax.profiler.TraceAnnotation("perfbench.train_step"):
            return compiled["step"](state, batch)

    def loop(state, until, records, clock=None, log_every=1):
        """The window's own call and feed, for set-up's steps too."""
        return run_training_loop(
            state=state, train_step=train_step, datasets=Feed(stream),
            batch_size=rows, train_steps=until, mesh=mesh,
            batch_sharding=sharding, validation_every=0,
            log_every=log_every, eval_fn=lambda s, split: 0.0,
            print_fn=lambda line: None, telemetry=Telemetry(records),
            prefetch=tr["prefetch"], shutdown=clock)[0]

    leaf_norms = jax.jit(lambda tree: {
        jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32))))
        for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]})
    change_norms = jax.jit(lambda a, b: {
        jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32))))
        for (p, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                             jax.tree.leaves(b))})

    def names(d):   # "['layer0']['qkv']['kernel']" -> "layer0/qkv/kernel"
        return {k.replace("']['", "/").strip("[]'"): float(v)
                for k, v in jax.device_get(d).items()}

    def param_change(params, seed):
        """The norm of each leaf's change since the seed's weights, with
        those made anew a layer at a time: a whole second copy of the
        parameters beside the optimizer's state would set the process's
        memory peak, which is the program's to set."""
        halves = weights.seed_halves(seed)
        top = weights.nest(maker.top(halves))
        out = names(change_norms({k: params[k] for k in top}, top))
        del top
        for i in range(maker.num_layers):
            was = weights.nest(maker.layer(halves, i))
            out.update({f"layer{i}/{k}": v for k, v in names(
                change_norms(params[f"layer{i}"], was)).items()})
        return out

    def first_steps(seed):
        """One object — the compiled step with its state — driven from the
        seed through ``check_steps`` steps by the window's own call and
        feed.  Returns the state and what the check compares."""
        stream.seed = int(seed)
        state = fresh_state(seed)
        if "step" not in compiled:
            n_params = check_tree(jax, model, state.params, cfg)
            t0 = CLOCK()
            compiled["step"] = step.lower(
                state, jax.ShapeDtypeStruct((rows, seq), jnp.int32,
                                            sharding=sharding)).compile()
            compiled["text"] = compiled["step"].as_text()
            compiled["n_params"] = n_params
            phases["lower_compile_s"] = CLOCK() - t0
        records = Records()
        stream.seek(0)
        state = loop(state, 2, records)
        mu = [x.mu for x in jax.tree.leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
            if hasattr(x, "mu")][0]
        first = {k: v / 0.1 for k, v in names(leaf_norms(mu)).items()}
        stream.seek(1)
        state = loop(state, 1 + tr["check_steps"], records)
        change = param_change(state.params, seed)
        losses = [r["loss"] for r in records.kind("train_step")]
        return state, {"losses": losses, "first_grad_norm": first,
                       "param_change_norm": change}

    def reference(seed):
        ref = _load_reference(cfg)
        stream.seed = int(seed)
        batches = [stream.batch(k, rows) for k in range(tr["check_steps"])]
        return ref.train_steps(cfg, seed, batches, lr)

    if args.calibrate:
        return calibrate_train(args, cell, first_steps, reference, device)

    state, produced = first_steps(args.seed)
    phases["first_steps_s"] = CLOCK() - t_begin - sum(phases.values())
    tracer = Tracer(jax, cell["name"]) if args.trace else None
    clock = StepClock(args.seconds, warm=3, tokens_per_step=rows * seq,
                      tracer=tracer, trace_steps=tr["trace_steps"])
    records = Records()
    stream.seek(tr["check_steps"])
    state = loop(state, 10 ** 9, records, clock, tr["log_every"])
    peak = memory_peak(jax, devices)
    mem = compiled["step"].memory_analysis()
    setup = {"phases": phases, "t_window": clock.t0,
             "compile_s": sum(x[2] for x in compiles.between(0, clock.t0)),
             "compiles": len(compiles.between(0, clock.t0, "compile")),
             "cache_loads": len(compiles.between(0, clock.t0, "cache_load"))}
    out = {
        "kind": "train", "device": {**device, "memory_peak_bytes": peak},
        "window": {"t0": clock.t0, "t1": clock.t1}, "setup": setup,
        "steps": clock.steps(),
        "train_records": [
            {k: r.get(k) for k in ("t", "data_wait_ms", "compute_ms")}
            for r in records.kind("train_step")],
        "counters": {
            "mosaic_calls": compiled["text"].count("tpu_custom_call"),
            "all_reduces": compiled["text"].count("all-reduce("),
            "compiles_in_window": len(compiles.between(
                clock.t0, clock.bounds[-1])),
            "n_params": compiled["n_params"],
            "rows": rows, "seq": seq,
            "memory_analysis": {
                k: int(getattr(mem, k + "_size_in_bytes", 0))
                for k in ("argument", "output", "alias", "temp",
                          "generated_code")} if mem else None},
        "trace": tracer.reduce() if tracer else None,
        "attempted": len(stats.steps_in(clock.steps(), clock.t0, clock.t1)),
        "failed": 0,
    }
    del state
    gc.collect()
    t0 = CLOCK()
    numbers, where = check.train_numbers(produced, reference(args.seed))
    out["check"] = {"numbers": numbers, "where": where,
                    "reference_s": CLOCK() - t0}
    return out


def calibrate_train(args, cell, first_steps, reference, device) -> dict:
    """Readings the limits are set from: the numbers of the check on each
    seed, in one process.  ``--control`` reads the lower precision."""
    rows = []
    for seed in args.calibrate:
        state, produced = first_steps(seed)
        del state
        gc.collect()
        numbers, where = check.train_numbers(produced, reference(seed))
        rows.append({"seed": seed, **numbers, **where})
        note(f"calibrate {json.dumps(rows[-1])}")
    return {"kind": "calibrate", "cell": cell["name"],
            "control": bool(args.control), "device": device, "rows": rows}


def _load_reference(cfg: dict):
    return spec.named_module(cfg, "reference")


# ----------------------------------------------------------------- serving


class EngineLog:
    """The harness's own clock around the engine's two calls.  It replaces
    ``engine.admit`` and ``engine.step`` ON THE INSTANCE by wrappers that
    stamp the time, count the work and place a profiler annotation, and
    call the program's method unchanged.  One record per turn of the engine
    loop: the turn's admissions (whole-prompt prefills) and its decode
    step."""

    def __init__(self, jax, engine):
        self.jax = jax
        self.steps: list[dict] = []
        self.requests: dict[int, dict] = {}
        self._live: list[tuple] = []
        self._admits: list[tuple] = []
        self.tracer = None
        self.trace_after = self.trace_for = None
        self._admit, self._step = engine.admit, engine.step
        engine.admit, engine.step = self.admit, self.step

    def admit(self, request):
        t0 = CLOCK()
        with self.jax.profiler.TraceAnnotation("perfbench.prefill"):
            out = self._admit(request)
        t1 = CLOCK()
        rec = {"t_admit": t0, "t_seated": t1, "t_first": None,
               "t_last": None, "n_out": 0, "prompt_len": len(request.prompt)}
        self.requests[request.seed] = rec
        self._live.append((request, rec))
        self._admits.append((t0, t1, len(request.prompt)))
        return out

    def step(self, queue_depth: int = 0):
        if self.tracer is not None and self.tracer.t1 is None:
            now = CLOCK()
            if self.tracer.t0 is None and now >= self.trace_after:
                self.tracer.start()
            elif self.tracer.t0 is not None \
                    and now >= self.tracer.t0 + self.trace_for:
                self.tracer.stop()
        t0 = CLOCK()
        context = [rec["prompt_len"] + len(req.tokens)
                   for req, rec in self._live]
        with self.jax.profiler.TraceAnnotation("perfbench.decode_step"):
            out = self._step(queue_depth)
        t1 = CLOCK()
        gen, live = 0, []
        for req, rec in self._live:
            n = len(req.tokens)
            if n > rec["n_out"]:
                gen += n - rec["n_out"]
                rec["n_out"], rec["t_last"] = n, t1
                if rec["t_first"] is None:
                    rec["t_first"] = t1
            if req.t_done is None:
                live.append((req, rec))
        self._live = live
        admits, self._admits = self._admits, []
        if context or admits:
            self.steps.append({
                "t_start": admits[0][0] if admits else t0, "t_end": t1,
                "t_decode": t0, "prefill_s": sum(b - a for a, b, _ in admits),
                "admits": [list(a) for a in admits], "context": context,
                "prompt_tokens": sum(p for _, _, p in admits),
                "gen_tokens": gen,
                "tokens": gen + sum(p for _, _, p in admits)})
        return out


def post(port: int, body: dict, timeout: float = 300.0) -> dict:
    req = urllib.request.Request(f"http://127.0.0.1:{port}/generate",
                                 data=json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def run_serve(args, cell: dict) -> dict:
    t_begin = CLOCK()
    cfg, tr = cell["config"], cell["traffic"]
    jax, device = backend(args, cell["chips"])
    from distributed_tensorflow_tpu.models import gpt as gpt_lib
    from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                           EngineConfig)
    from distributed_tensorflow_tpu.serving.scheduler import FairScheduler
    from distributed_tensorflow_tpu.serving.server import ServingServer
    from distributed_tensorflow_tpu.utils.telemetry import Telemetry
    from perfbench import weights

    compiles = Compiles(jax)
    phases = {"backend_s": CLOCK() - t_begin}
    gcfg = gpt_config(cell)
    model = gpt_lib.GptLM(gcfg)
    t0 = CLOCK()
    params = weights.program_tree(args.seed, weights.Maker(cfg))
    n_params = check_tree(jax, model, params, cfg)
    jax.block_until_ready(params)
    phases["weights_s"] = CLOCK() - t0
    over = cfg["lower_precision"] if args.control else {}
    records = Records()
    engine = DecodeEngine(model, params, EngineConfig(**tr["engine"], **over),
                          telemetry=Telemetry(records))
    del params
    log = EngineLog(jax, engine)
    server = ServingServer(engine, FairScheduler(), port=0,
                           telemetry=engine.telemetry,
                           request_timeout_s=tr["request_timeout_s"],
                           meta={"model": cell["config_name"],
                                 "vocab_size": gcfg.vocab_size})
    server.start()
    port = server.port
    t0 = CLOCK()
    for i, plen in enumerate(traffic_lib.serve_buckets(
            tr, tr["engine"]["page_size"])):
        post(port, {"prompt": traffic_lib.prompt_tokens(
            args.seed, 10 ** 6 + i, plen, gcfg.vocab_size),
            "num_tokens": 2, "seed": 2 ** 30 + i})
    phases["warm_up_s"] = CLOCK() - t0
    warm_end = CLOCK()
    say("ready", port=port, vocab_size=gcfg.vocab_size)

    plan = json.loads(sys.stdin.readline())       # {"t0", "t1", "trace_for"}
    tracer = Tracer(jax, cell["name"]) if args.trace else None
    if tracer is not None:
        log.trace_after, log.trace_for = plan["t1"], plan["trace_for"]
        log.tracer = tracer
    done = json.loads(sys.stdin.readline())       # {"samples": [...]}
    if tracer is not None and tracer.t0 is not None and tracer.t1 is None:
        tracer.stop()
    peak = memory_peak(jax, jax.devices()[:1])
    pool = engine.stats()["kv_pool"]
    stats = server.stats()
    server.shutdown()
    t_end = CLOCK()
    setup = {"phases": phases, "t_window": plan["t0"],
             "compile_s": sum(x[2] for x in compiles.between(0, warm_end)),
             "compiles": len(compiles.between(0, warm_end, "compile")),
             "cache_loads": len(compiles.between(0, warm_end, "cache_load"))}
    out = {
        "kind": "serve", "device": {**device, "memory_peak_bytes": peak},
        "window": {"t0": plan["t0"], "t1": plan["t1"]}, "setup": setup,
        "steps": log.steps,
        "requests": {str(k): v for k, v in log.requests.items()},
        "counters": {
            "compiles_in_window": len(compiles.between(plan["t0"],
                                                       plan["t1"])),
            "compiles_after_warm_up": len(compiles.between(warm_end, t_end)),
            "kv_pages_peak": pool["peak_in_use"],
            "kv_pages_total": tr["engine"]["num_pages"],
            "prefill_programs": stats["engine"]["compile_cache"][
                "prefill_programs"],
            "prefill_evictions": stats["engine"]["compile_cache"][
                "evictions"],
            "queue_depth_hwm": stats["queue_depth_hwm"],
            "n_params": n_params},
        "trace": tracer.reduce() if tracer and tracer.t1 else None,
    }
    del engine, server, log, model
    gc.collect()
    t0 = CLOCK()
    gaps = _load_reference(cfg).served_gaps(
        cfg, args.seed, done["samples"], tr["check_pad"])
    every = [float(x) for g in gaps for x in g]
    out["check"] = {
        # Two numbers.  The MEAN gap is steady from seed to seed and is the
        # one the lower precision fails (sound <= 8.0e-4, int8 >= 3.3e-3).
        # The WIDEST gap swings by its nature (sound 0.023-0.065, int8
        # 0.097-0.208: no limit separates those), so it is held against the
        # fault the mean cannot see, one wrong token on a live slot, at
        # three times the sound runs' largest (PERF.md, limits).
        "numbers": {} if not every else {
            "served_logit_gap_mean": sum(every) / len(every),
            "served_logit_gap_widest": max(every)},
        "where": {"tokens_compared": int(sum(len(g) for g in gaps)),
                  "requests_compared": len(gaps),
                  "tokens_off_best": int(sum(int((g > 0).sum())
                                             for g in gaps))},
        "reference_s": CLOCK() - t0}
    return out


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's lower precision (the "
                    "control of the check); never used by a benchmark run")
    ap.add_argument("--calibrate", type=lambda s: [int(x) for x in
                                                   s.split(",")],
                    help="training cells: read the check's numbers on "
                    "these seeds in one process, no window")
    ap.add_argument("--break-path", default="",
                    help="tests only: break the timed path underneath")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, args.rehearse)
    if args.break_path:
        from perfbench import faults
        faults.install(args.break_path)
    run = run_train if cell["traffic"]["kind"] == "train_lm" else run_serve
    say("result", **run(args, cell))
    return 0


if __name__ == "__main__":
    sys.exit(main())
