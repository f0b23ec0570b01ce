"""``gpt_lib.sample_logits_dynamic`` does no vocabulary-wide work its
inputs do not ask for (models/gpt.py): sorted logits come out of the one
two-operand sort (no ``take_along_axis`` over lanes x vocabulary), and a
call whose rows are all greedy takes an argmax under a ``lax.cond`` and
nothing else.  Both are rewrites of ONE function whose tokens must not
move by a bit: ``reference`` below is the body this file's PR replaced,
kept as the plain form to compare with.
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
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import Telemetry


def reference(step_logits, key, temperature, top_k, top_p):
    """The sampler as it was: argsort, gather, every row through the
    whole sorted-space path, greedy rows picked out at the end."""
    V = step_logits.shape[-1]
    t = jnp.maximum(temperature, 1e-6)[:, None]
    order = jnp.argsort(-step_logits, axis=-1)
    sl = jnp.take_along_axis(step_logits, order, axis=-1) / t
    probs = jax.nn.softmax(sl, axis=-1)
    idx = jnp.arange(V)[None, :]
    keep_k = (top_k[:, None] <= 0) | (idx < top_k[:, None])
    p = top_p[:, None]
    excl = jnp.cumsum(probs, axis=-1) - probs
    keep_p = ~((p > 0.0) & (p < 1.0)) | (excl < p)
    neg = jnp.finfo(sl.dtype).min
    filt = jnp.where(keep_k & keep_p, sl, neg)
    if key.ndim == 1:
        u = jax.vmap(lambda k: jax.random.uniform(
            k, (V,), minval=1e-20, maxval=1.0))(key)
    else:
        u = jax.random.uniform(key, filt.shape, minval=1e-20, maxval=1.0)
    gumbel = -jnp.log(-jnp.log(u))
    samp_sorted = jnp.argmax(filt + gumbel, axis=-1)
    sampled = jnp.take_along_axis(order, samp_sorted[:, None],
                                  axis=-1)[:, 0]
    greedy = jnp.argmax(step_logits, axis=-1)
    return jnp.where(temperature > 0.0, sampled, greedy).astype(jnp.int32)


# One compilation a function: the sampling parameters are traced arrays.
SAMPLERS = {"new": jax.jit(gpt_lib.sample_logits_dynamic),
            "reference": jax.jit(reference)}
SHAPES = {"4x97-f32": ((4, 97), jnp.float32),
          "16x32000-bf16": ((16, 32000), jnp.bfloat16)}


@functools.lru_cache(maxsize=None)
def logits_of(shape_name):
    """Logits with TIES, so that the order among equals is under test:
    bfloat16 has 8 bits of mantissa (32,000 draws hold each value many
    times over), and the float32 rows are rounded to halves."""
    shape, dtype = SHAPES[shape_name]
    x = 3.0 * jax.random.normal(jax.random.key(11), shape, jnp.float32)
    if dtype == jnp.float32:
        x = jnp.round(2.0 * x) / 2.0
    x = x.astype(dtype)
    assert len(np.unique(np.asarray(x[0], np.float32))) < shape[1] // 2
    return x


def temperatures(mode, batch):
    return {"greedy": np.zeros(batch),
            "mixed": np.where(np.arange(batch) % 3 == 1, 0.8, 0.0),
            "sampled": np.linspace(0.5, 1.5, batch)}[mode].astype(
                np.float32)


def keys_of(kind, batch):
    key = jax.random.key(5)
    return key if kind == "scalar" else jax.random.split(key, batch)


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
@pytest.mark.parametrize("key_kind", ["scalar", "per_row"])
@pytest.mark.parametrize("top_p", [0.0, 0.9, 1e-6])
@pytest.mark.parametrize("top_k", [0, 1, 50])
@pytest.mark.parametrize("mode", ["greedy", "mixed", "sampled"])
def test_tokens_bit_equal_to_the_plain_sampler(mode, top_k, top_p,
                                               key_kind, shape_name):
    logits = logits_of(shape_name)
    B = logits.shape[0]
    temp = jnp.asarray(temperatures(mode, B))
    args = (logits, keys_of(key_kind, B), temp,
            jnp.full((B,), top_k, jnp.int32),
            jnp.full((B,), top_p, jnp.float32))
    want = np.asarray(SAMPLERS["reference"](*args))
    got = SAMPLERS["new"](*args)
    assert got.dtype == jnp.int32 and got.shape == (B,)
    np.testing.assert_array_equal(np.asarray(got), want)
    greedy = np.argmax(np.asarray(logits, np.float32), axis=-1)
    rows = np.asarray(temp) <= 0.0
    np.testing.assert_array_equal(want[rows], greedy[rows])
    if mode == "sampled" and top_k != 1 and top_p != 1e-6:
        # The grid does exercise sampling: some row left its argmax.
        assert (want != greedy).any()


def test_rows_of_one_call_differ_in_their_filters():
    """``top_k[b]`` and ``top_p[b]`` are per-row: one call, every filter."""
    logits = logits_of("16x32000-bf16")
    B = logits.shape[0]
    args = (logits, keys_of("per_row", B),
            jnp.asarray(temperatures("mixed", B)).at[0].set(1.0),
            jnp.asarray(np.arange(B) % 3 * 25, jnp.int32),
            jnp.asarray(np.arange(B) % 4 * 0.3, jnp.float32))
    np.testing.assert_array_equal(np.asarray(SAMPLERS["new"](*args)),
                                  np.asarray(SAMPLERS["reference"](*args)))


@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_sort_descending_is_argsort_and_its_gather(shape_name):
    logits = logits_of(shape_name)
    # Signed zeros and infinities keep their bits through two negations.
    logits = logits.at[:, :4].set(
        jnp.asarray([0.0, -0.0, jnp.inf, -jnp.inf], logits.dtype))
    values, order = jax.jit(gpt_lib._sort_descending)(logits)
    want_order = jnp.argsort(-logits, axis=-1)
    want = jnp.take_along_axis(logits, want_order, axis=-1)
    np.testing.assert_array_equal(np.asarray(order), np.asarray(want_order))
    assert values.dtype == logits.dtype
    width = {2: np.uint16, 4: np.uint32}[logits.dtype.itemsize]
    np.testing.assert_array_equal(np.asarray(values).view(width),
                                  np.asarray(want).view(width))


# ------------------------------------------------ what the program holds


def sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            if hasattr(item, "eqns"):
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr


def equations(jaxpr, into_conds=True):
    """Every equation of ``jaxpr`` and of what it calls, depth first."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not into_conds:
            continue
        for sub in sub_jaxprs(eqn):
            yield from equations(sub, into_conds)


def names(eqns):
    return [e.primitive.name for e in eqns]


def sampler_jaxpr(shape_name, key_kind, fn=None):
    logits = logits_of(shape_name)
    B = logits.shape[0]
    return jax.make_jaxpr(fn or gpt_lib.sample_logits_dynamic)(
        logits, keys_of(key_kind, B), jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32)).jaxpr


def wide_gathers(eqns, vocab):
    return [e for e in eqns if e.primitive.name == "gather"
            and any(int(np.prod(v.aval.shape)) >= vocab
                    for v in e.outvars)]


@pytest.mark.parametrize("key_kind", ["scalar", "per_row"])
@pytest.mark.parametrize("shape_name", sorted(SHAPES))
def test_no_gather_over_the_vocabulary_and_one_sort_under_the_cond(
        shape_name, key_kind):
    vocab = SHAPES[shape_name][0][1]
    jaxpr = sampler_jaxpr(shape_name, key_kind)
    everything = list(equations(jaxpr))
    assert wide_gathers(everything, vocab) == []
    assert names(everything).count("sort") == 1
    # Outside the cond: the predicate, and nothing of the vocabulary's.
    outside = names(equations(jaxpr, into_conds=False))
    assert outside.count("cond") == 1
    assert not {"sort", "cumsum", "argmax", "gather", "random_bits",
                "exp", "log"} & set(outside)
    (cond,) = [e for e in everything if e.primitive.name == "cond"]
    greedy_arm, sampled_arm = (
        names(equations(b.jaxpr)) for b in cond.params["branches"])
    assert greedy_arm.count("argmax") == 1
    assert not {"sort", "cumsum", "gather", "random_bits", "exp",
                "log", "cond"} & set(greedy_arm)
    assert sampled_arm.count("sort") == 1
    assert {"cumsum", "random_bits", "argmax", "gather"} <= set(sampled_arm)
    # The sort carries the indices: two operands, one of them the key.
    (sort,) = [e for e in everything if e.primitive.name == "sort"]
    assert len(sort.invars) == 2 and sort.params["num_keys"] == 1
    assert sort.params["is_stable"]


def test_the_walk_finds_the_plain_samplers_gather():
    """The check above is not vacuous: the form it replaced fails it."""
    everything = list(equations(sampler_jaxpr("16x32000-bf16", "per_row",
                                              reference)))
    assert len(wide_gathers(everything, 32000)) == 1
    assert "cond" not in names(everything)


def test_static_samplers_nucleus_takes_its_logits_from_the_sort():
    """``sample_logits`` (the ``generate()`` family's) shares the helper:
    its top-p branch holds one sort and no gather over the vocabulary."""
    logits = logits_of("4x97-f32")
    jaxpr = jax.make_jaxpr(functools.partial(
        gpt_lib.sample_logits, temperature=0.7, top_p=0.9))(
            logits, jax.random.key(0)).jaxpr
    everything = list(equations(jaxpr))
    assert names(everything).count("sort") == 1
    assert wide_gathers(everything, 97) == []


# ------------------------------------------- the engine that runs it


PROMPT = list(range(1, 12))
SAMPLED = dict(temperature=0.8, top_k=16, top_p=0.9, seed=7)
ENGINES = {"step": {}, "spec_step": dict(spec_k=3)}


@functools.lru_cache(maxsize=None)
def model_and_params():
    model = gpt_lib.GptLM(gpt_lib.GptConfig(
        vocab_size=64, hidden_size=32, num_heads=2, intermediate_size=64,
        max_position=64, num_layers=2, dtype="float32"))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    return model, params


def engine_of(telemetry=None, **kw):
    return DecodeEngine(*model_and_params(), EngineConfig(
        num_slots=3, page_size=4, num_pages=32, max_pages_per_seq=8, **kw),
        telemetry=telemetry)


def recording():
    """A telemetry bus, and the records it was handed."""
    records = []
    telemetry = Telemetry()
    orig = telemetry.emit
    telemetry.emit = lambda kind, step=0, **f: (
        records.append((kind, f)), orig(kind, step=step, **f))
    return telemetry, records


def drain(engine):
    while engine.active_slots:
        engine.step()


@pytest.mark.parametrize("program", sorted(ENGINES))
def test_a_greedy_only_run_counts_no_sampled_step(program):
    telemetry, records = recording()
    engine = engine_of(telemetry, **ENGINES[program])
    spec = bool(ENGINES[program])
    engine.admit(Request(PROMPT, 8, speculative=spec))
    engine.admit(Request(PROMPT[:5], 5, speculative=spec))
    drain(engine)
    stats = engine.stats()
    assert stats["sample_steps_sampled"] == 0
    assert stats["sample_steps_greedy"] == stats["engine_step"] > 0
    steps = [f for kind, f in records if kind == "serve_step"]
    assert len(steps) == stats["engine_step"]
    assert all(s["sampled_lanes"] == 0 for s in steps)


def test_one_request_at_temperature_moves_the_counter():
    """``sampled_lanes`` is the seated lanes with temperature > 0 at the
    dispatch: exactly what the sampler's cond is decided on."""
    telemetry, records = recording()
    engine = engine_of(telemetry)
    engine.admit(Request(PROMPT, 9))
    engine.step()
    engine.step()
    hot = Request(PROMPT, 3, **SAMPLED)
    engine.admit(hot)
    drain(engine)
    assert len(hot.tokens) == 3
    stats = engine.stats()
    assert (stats["sample_steps_greedy"],
            stats["sample_steps_sampled"]) == (6, 3)
    lanes = [f["sampled_lanes"] for kind, f in records
             if kind == "serve_step"]
    # (two calls had dispatched three steps when the hot lane was seated)
    assert lanes == [0, 0, 0, 1, 1, 1, 0, 0, 0]
    # A retired lane's temperature is cleared: the next tenant of the
    # slot, greedy, is back on the argmax.
    engine.admit(Request(PROMPT, 2))
    drain(engine)
    assert engine.stats()["sample_steps_sampled"] == 3


def test_the_profilers_retire_event_carries_sampled_lanes(monkeypatch):
    seen = []
    real = profiling.annotate

    def spy(name, **stats):
        seen.append((name, stats))
        return real(name, **stats)
    monkeypatch.setattr(profiling, "annotate", spy)
    engine = engine_of()
    engine.admit(Request(PROMPT, 2, **SAMPLED))
    engine.admit(Request(PROMPT, 2, **SAMPLED))
    engine.admit(Request(PROMPT, 2))
    engine.step()
    retire = [s for n, s in seen if n == "serve.step.retire"]
    # (beside it PR 39's count of the table, three lanes of 4 pages, and
    # PR 40's two clock readings of the stage, whole microseconds)
    assert all(isinstance(retire[0].pop(k), int)
               for k in ("upload_us", "dispatch_us"))
    # (and this PR's three of the hand-over: a first step is a serial one;
    # and PR 45's two: of a lane's 4 reserved pages the prompt reaches 3,
    # which is what a read of held pages only would visit, and on a CPU no
    # layer reads so; and PR 46's one: the three lanes are seated)
    assert retire == [{"pools_in_place": 1, "sampled_lanes": 2,
                       "table_pages": 3 * 8, "table_pages_held": 12,
                       "attn_pages_read": 9, "attn_kernel_layers": 0,
                       "lanes_live": 3,
                       "steps_ahead": 0, "steps_serial": 1,
                       "lane_steps_discarded": 0}]


def with_sampler(engine, fn, monkeypatch):
    """``engine`` with its step programs traced against ``fn``: the engine
    looks the sampler up on the module while tracing (as the benchmark's
    faults rely on), so the first dispatch under the patch binds it."""
    monkeypatch.setattr(gpt_lib, "sample_logits_dynamic", fn)
    warm = Request(PROMPT, 2, speculative=bool(engine.config.spec_k))
    engine.admit(warm)
    drain(engine)
    monkeypatch.undo()
    return engine


def traffic(engine):
    """Greedy alone, sampled joining greedy mid-stream, sampled alone."""
    spec = bool(engine.config.spec_k)
    reqs = [Request(PROMPT, 6, speculative=spec),
            Request(PROMPT, 9, speculative=spec),
            Request(PROMPT[:7], 6, **SAMPLED),
            Request(PROMPT[:4], 5, temperature=1.3, seed=3),
            Request(PROMPT, 4, **SAMPLED)]
    engine.admit(reqs[0])
    drain(engine)
    engine.admit(reqs[1])
    engine.step()
    engine.admit(reqs[2])
    engine.admit(reqs[3])
    drain(engine)
    engine.admit(reqs[4])
    drain(engine)
    return [r.tokens for r in reqs]


@pytest.mark.parametrize("program", sorted(ENGINES))
def test_the_engine_serves_the_plain_samplers_tokens_from_one_program(
        program, monkeypatch):
    plain = with_sampler(engine_of(**ENGINES[program]), reference,
                         monkeypatch)
    engine = engine_of(**ENGINES[program])
    want = traffic(plain)
    assert traffic(engine) == want
    assert [len(t) for t in want] == [6, 9, 6, 5, 4]
    stats = engine.stats()
    assert stats["sample_steps_sampled"] > 0 < stats["sample_steps_greedy"]
    # All of it from the one decode step an engine compiles: the arms
    # are inside the program, not two programs the host picks from.
    for fn in (engine._step_fn, engine._spec_step_fn):
        assert fn is None or fn._cache_size() == 1
    text = engine._step_fn.lower(
        engine._tree, jnp.asarray(engine._tokens),
        jnp.asarray(engine._positions), jnp.asarray(engine._tables),
        engine.pools, jnp.asarray(engine._temp),
        jnp.asarray(engine._top_k), jnp.asarray(engine._top_p),
        jnp.asarray(engine._seeds)).as_text()
    assert text.count("stablehlo.case") == 1


def test_statz_reports_the_sampler_counters_over_http():
    srv = ServingServer(engine_of(), FairScheduler(), port=0,
                        request_timeout_s=60.0)
    srv.start()
    try:
        client = ServeClient(f"http://127.0.0.1:{srv.port}")
        client.generate(PROMPT, 4)
        stats = client.stats()["engine"]
        assert stats["sample_steps_sampled"] == 0
        assert stats["sample_steps_greedy"] == stats["engine_step"] == 4
        client.generate(PROMPT, 3, temperature=0.8, seed=1)
        assert client.stats()["engine"]["sample_steps_sampled"] == 3
    finally:
        srv.shutdown()
