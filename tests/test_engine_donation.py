"""The engine donates its KV pools (and the recurrent state that rides in
the same tree) to all four of its programs: the decode step, the
speculative step, the per-bucket prefill and the chunk prefill
(serving/engine.py, module docstring).  On the CPU, as on the chip, a
donated array reads ``is_deleted()`` once a program has taken it; JAX
copies instead, silently, while something else holds the buffer, and the
engine's ``pool_steps_in_place`` / ``pool_steps_copied`` say which
happened.  A donated program returns the bits the undonated one returns.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.client import ServeClient
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import (FairScheduler,
                                                          Request)
from distributed_tensorflow_tpu.serving.server import ServingServer
from distributed_tensorflow_tpu.utils.telemetry import Telemetry

BASE = dict(vocab_size=64, hidden_size=32, num_heads=2,
            intermediate_size=64, max_position=64, dtype="float32")
CONFIGS = {
    "dense": dict(num_layers=2),
    "hybrid": dict(
        num_layers=4, pos_encoding="none", norm="rmsnorm",
        activation="swiglu",
        layer_kinds=(gpt_lib.LINEAR_ATTENTION,) * 3 + (
            gpt_lib.FULL_ATTENTION,),
        linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16),
    # Three loop steps over the same two layers: a pool a layer, a run of
    # pages a step.
    "looped": dict(
        num_layers=2, pos_encoding="rope", norm="rmsnorm",
        activation="swiglu", norm_placement="sandwich", loop_steps=3,
        exit_gate=True),
}
#: What makes an engine run each program (a model with recurrent layers
#: or a weight-shared loop is refused ``spec_k`` and ``prefill_chunk``).
PROGRAMS = {"step": {}, "prefill": {}, "spec_step": dict(spec_k=3),
            "chunk_prefill": dict(prefill_chunk=4)}
CASES = [(c, p) for c in CONFIGS for p in PROGRAMS
         if c == "dense" or not PROGRAMS[p]]
PROMPT = list(range(1, 12))


@functools.lru_cache(maxsize=None)
def model_and_params(name):
    model = gpt_lib.GptLM(gpt_lib.GptConfig(**BASE, **CONFIGS[name]))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    return model, params


def engine_of(name, telemetry=None, **kw):
    return DecodeEngine(*model_and_params(name), EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8, **kw),
        telemetry=telemetry)


def request(engine, n=8):
    return Request(PROMPT, n, speculative=bool(engine.config.spec_k))


def serve(engine, req):
    engine.validate(req)
    engine.admit(req)
    while engine.active_slots:
        engine.step()
    return req.tokens


def undonate(engine):
    """The engine's own four programs, jitted again without donation."""
    plain = lambda fn: jax.jit(fn.__wrapped__)  # noqa: E731
    engine._step_fn = plain(engine._step_fn)
    if engine._spec_step_fn is not None:
        engine._spec_step_fn = plain(engine._spec_step_fn)
    for name in ("_prefill_fn", "_chunk_prefill_fn"):
        build = getattr(engine, name)
        setattr(engine, name, functools.lru_cache(maxsize=None)(
            lambda n, build=build: plain(build(n))))
    return engine


def run_program(engine, program):
    """Drive ``engine`` until ``program`` has just been dispatched;
    returns the pool leaves that dispatch was given."""
    req = request(engine)
    engine.validate(req)
    given = jax.tree.leaves(engine.pools)
    engine.admit(req)                  # "prefill": the bucket's program
    if program != "prefill":
        # A chunked engine's first step runs the chunk program, then the
        # decode step on what IT returned; otherwise the step runs alone.
        given = jax.tree.leaves(engine.pools)
        if program == "chunk_prefill":
            engine._advance_prefill()
        else:
            engine.step()
    return given


@pytest.mark.parametrize("config,program", CASES,
                         ids=[f"{c}-{p}" for c, p in CASES])
def test_each_program_consumes_the_pools_it_is_given(config, program):
    records = []
    telemetry = Telemetry()
    orig = telemetry.emit
    telemetry.emit = lambda kind, step=0, **f: (
        records.append((kind, f)), orig(kind, step=step, **f))
    engine = engine_of(config, telemetry, **PROGRAMS[program])
    given = run_program(engine, program)
    assert len(given) == 2 * engine.model.cfg.num_layers
    assert all(leaf.is_deleted() for leaf in given)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree.leaves(engine.pools))
    while engine.active_slots:
        engine.step()
    stats = engine.stats()
    assert stats["pool_steps_copied"] == 0
    assert stats["pool_steps_in_place"] == stats["engine_step"] > 0
    steps = [f for kind, f in records if kind == "serve_step"]
    assert len(steps) == stats["engine_step"]
    assert all(s["pools_in_place"] is True for s in steps)


@pytest.mark.parametrize("config,program", CASES,
                         ids=[f"{c}-{p}" for c, p in CASES])
def test_a_donated_engine_serves_the_undonated_engines_tokens(
        config, program):
    kw = PROGRAMS[program]
    donated, plain = engine_of(config, **kw), undonate(engine_of(config,
                                                                 **kw))
    want = [serve(plain, request(plain)) for _ in range(2)]
    assert [serve(donated, request(donated)) for _ in range(2)] == want
    assert len(want[0]) == 8
    # ... and the pools they leave behind are the same bits.
    for a, b in zip(jax.tree.leaves(donated.pools),
                    jax.tree.leaves(plain.pools)):
        assert a.dtype == b.dtype and np.array_equal(np.array(a),
                                                     np.array(b))
    stats = plain.stats()
    assert stats["pool_steps_in_place"] == 0
    assert stats["pool_steps_copied"] == stats["engine_step"] > 0


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_a_view_of_a_leaf_turns_the_donation_into_a_copy(config):
    """The silent fallback, pinned: while a ``np.asarray`` view holds a
    leaf's buffer JAX copies the pools, warns of nothing, and the engine
    counts the step as copied; with the view gone it donates again.  A
    call counts the step it LANDS: the one dispatched under the view is
    dispatched one call and counted the next (``DecodeEngine.step``)."""
    engine = engine_of(config)
    engine.admit(request(engine))
    engine.step()
    view = np.asarray(engine.pools[0][0])
    engine.step()
    steps = lambda: tuple(engine.stats()[name] for name in (  # noqa: E731
        "pool_steps_in_place", "pool_steps_copied"))
    assert steps() == (2, 0)
    del view
    engine.step()
    assert steps() == (2, 1)
    engine.step()
    assert steps() == (3, 1)


# ------------------------------------------------------ the failure path


def raise_after(fn):
    """``fn`` run to its dispatch (which consumes what it was donated),
    then an error, as from a device that failed under the program."""
    def failing(*args):
        fn(*args)
        raise RuntimeError("the device fell over")
    return failing


def break_program(engine, program):
    """Make ``program`` raise after its dispatch; returns the repair.  The
    two steps are attributes, the two prefills are built by width."""
    name = f"_{program}_fn"
    real = getattr(engine, name)
    setattr(engine, name, raise_after(real) if program.endswith("step")
            else lambda n: raise_after(real(n)))
    return lambda: setattr(engine, name, real)


@pytest.mark.parametrize("config,program", CASES,
                         ids=[f"{c}-{p}" for c, p in CASES])
def test_fail_active_builds_the_pools_anew_after_a_consumed_dispatch(
        config, program):
    kw = PROGRAMS[program]
    sound = engine_of(config, **kw)
    want = serve(sound, request(sound))
    engine = engine_of(config, **kw)
    shapes = [(x.shape, x.dtype) for x in jax.tree.leaves(engine.pools)]
    repair = break_program(engine, program)
    doomed = request(engine)
    with pytest.raises(RuntimeError, match="fell over"):
        serve(engine, doomed)
    assert any(x.is_deleted() for x in jax.tree.leaves(engine.pools))
    failed = engine.fail_active("RuntimeError: the device fell over")
    # (A prefill that raises inside admit() never seated its lane: the
    # server completes that request itself.)
    assert failed == ([] if program == "prefill" else [doomed])
    assert engine.active_slots == 0
    assert engine.allocator.pages_in_use == 0
    leaves = jax.tree.leaves(engine.pools)
    assert [(x.shape, x.dtype) for x in leaves] == shapes
    assert all(not x.is_deleted() and not np.array(x).any()
               for x in leaves)
    repair()
    assert serve(engine, request(engine)) == want
    # Pools that nothing consumed are left as they are.
    before = [id(x) for x in jax.tree.leaves(engine.pools)]
    assert engine.fail_active("nothing was live") == []
    assert [id(x) for x in jax.tree.leaves(engine.pools)] == before


def test_a_server_whose_step_failed_serves_the_next_request():
    """``ServingServer._turn`` answers the exception with ``fail_active``;
    without new pools it would stay up and fail every later request."""
    sound = engine_of("dense")
    want = serve(sound, request(sound))
    engine = engine_of("dense")
    repair = break_program(engine, "step")
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=60.0)
    srv.start()
    try:
        client = ServeClient(f"http://127.0.0.1:{srv.port}")
        with pytest.raises(Exception, match="fell over"):
            client.generate(PROMPT, 8)
        repair()
        assert client.generate(PROMPT, 8)["tokens"] == PROMPT + want
        assert client.stats()["engine"]["pool_steps_copied"] == 0
    finally:
        srv.shutdown()
