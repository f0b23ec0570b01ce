"""A decoder whose ROUTER reads the block's normed input, before attention,
while its experts read the stream after it (``GptConfig.router_input``
``"mixer_in"``): softmax over the six largest of 64 logits
(``router_score``), ReLU-gated experts (``expert_activation``), grouped-query
heads in groups of SEVEN, full layers without rotation beside sliding-window
layers with it, a period that BEGINS with its full layer.  Against the
benchmark's plain reference (``perfbench/refs/smallthinker-21b-a3b.py``,
loaded by path: one reference, not two) at the rehearsal size of
``perfbench/configs/smallthinker-21b-a3b.json`` (twelve layers: full,
sliding, sliding, sliding, three times; 64 wide, 14 query heads over 2 kv
heads of 16, window 16, 64 experts of 16 at 6 a token) in float32.  Pages of
8 rows: a ring is 3 pages, 24 rows.

Tolerances, with their reasons:

- ``LOGIT_TOL`` 2e-4 on logits of size about 1-5: program and reference are
  float32 throughout and differ in the order of their sums (rows sorted by
  expert against a masked loop over all 64, a ring's rows in ring order
  against the full score matrix under a mask, the widened query of
  ``GptBlock._attend_rows`` against grouped heads); sound readings here are
  2e-6 to 3e-5.  A token whose sixth and seventh router logits lie closer
  than that would choose another expert on one side and read 0.05 or more:
  none of the sequences here has one, and a new seed that finds one has
  found no fault.  bfloat16 anywhere reads 1e-2.
- ``GAP_TOL`` 1e-4 on a served token's logit gap below the reference's best:
  a greedy token IS the reference's best unless two logits lie closer than
  the above.
- Each deliberately WRONG reference (the router fed the post-attention
  stream, SiLU for ReLU, a softmax over all 64 left unrenormalised) must
  read more than ``50 * LOGIT_TOL`` from the program: they read 0.1 to 1.
- Where a test says "bit for bit" it compares the float32 patterns.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.ops import routed_experts as experts_ops
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils.telemetry import Telemetry
from perfbench import spec, weights, worker

CONFIG = os.path.join(spec.HERE, "configs", "smallthinker-21b-a3b.json")
SEED = 2 ** 31 + 50
LOGIT_TOL, GAP_TOL = 2e-4, 1e-4
PAGE, RING = 8, 3          # a window of 16: two pages and one more
SLIDING, FULL = gpt_lib.SLIDING_ATTENTION, gpt_lib.FULL_ATTENTION
PAD = 96


def small(**over):
    """The rehearsal size in float32, ``over`` laid over its ``model``."""
    cfg = spec.load_json(CONFIG)
    cfg = spec.deep_update(cfg, cfg["rehearsal"])
    cfg["model"]["dtype"] = cfg["param_dtype"] = "float32"
    cfg["model"]["attention_backend"] = "xla"
    cfg["model"].update(over)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return small()


@pytest.fixture(scope="module")
def ref(cfg):
    return spec.named_module(cfg, "reference")


def padded_logits(ref, cfg, seed=SEED):
    """The reference's logits for a sequence of up to ``PAD`` tokens,
    through ONE compiled shape: padded (no earlier position sees the
    padding, and a padded token's experts add nothing to another token)."""
    with jax.default_matmul_precision("highest"):
        layers = ref.Layers(cfg, seed)

    def logits(seq):
        toks = np.zeros((PAD,), np.int32)
        toks[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            return np.asarray(layers.head(layers.halves, layers.hidden(
                jnp.asarray(toks))))[:len(seq)]
    return logits


@pytest.fixture(scope="module")
def want_logits(cfg, ref):
    return padded_logits(ref, cfg)


def built(cfg):
    gcfg = worker.gpt_config({"config": cfg, "config_file": CONFIG})
    model = gpt_lib.GptLM(gcfg)
    params = weights.program_tree(SEED, weights.Maker(cfg))
    worker.check_tree(jax, model, params, cfg)
    return model, params


@pytest.fixture(scope="module")
def model_and_params(cfg):
    return built(cfg)


class Rows:
    def __init__(self):
        self.rows = []

    def log(self, step, **fields):
        self.rows.append(fields)


def engine_of(model, params, slots=4, records=None, num_pages=64, **kw):
    return DecodeEngine(model, params, EngineConfig(
        num_slots=slots, page_size=PAGE, num_pages=num_pages,
        max_pages_per_seq=16, **kw),
        telemetry=None if records is None else Telemetry(records))


def tokens_of(n, index=0):
    return np.random.default_rng([SEED, index]).integers(0, 512, n).tolist()


def serve(engine, *requests):
    waiting = list(requests)
    while waiting or engine.active_slots:
        while waiting and engine.can_admit(waiting[0]):
            engine.validate(waiting[0])
            engine.admit(waiting.pop(0))
        engine.step()
    return [r.tokens for r in requests]


def decode_fn(model, params, num_pages=64, routing=False):
    """The engine's decode step without its sampler: every lane's logits
    (with ``routing`` the layers' histograms behind them)."""
    def step(tok, pools, tables, pos, rings, live):
        (logits, pools), sown = model.apply(
            {"params": params}, tok, pools, tables, pos, live, rings,
            method=gpt_lib.GptLM.decode_paged, mutable=["routing"])
        counts = jnp.stack([sown["routing"][f"layer{i}"]["counts"][0]
                            for i in range(model.cfg.num_layers)])
        return (logits, pools, counts) if routing else (logits, pools)
    return jax.jit(step)


def forced(engine, decode, seqs, prompts):
    """Lanes seated by ``engine.admit`` (the engine's own prefill and
    landing, its own tables), then decoded token after token, ALL lanes in
    one batch, with each lane's NEXT token taken from ``seqs`` and not from
    the logits: returns, a lane, the logits at positions ``P - 1 ..
    len(seq) - 2``.  A lane that has run out of tokens rides on as an idle
    row."""
    B = engine.config.num_slots
    sentinel = engine.config.num_pages
    out = [[] for _ in seqs]
    at = [p - 1 for p in prompts]
    slots = []
    for seq, P in zip(seqs, prompts):
        req = Request(seq[:P], len(seq) - P)
        engine.validate(req)
        slots.append(engine.admit(req))
    pools = engine.pools
    while any(a < len(s) - 1 for a, s in zip(at, seqs)):
        tok = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tables = np.full_like(engine._tables, sentinel)
        rings = np.full_like(engine._window_tables,
                             engine.allocator.window_pages)
        riding = [i for i, (a, s) in enumerate(zip(at, seqs))
                  if a < len(s) - 1]
        for i in riding:
            tok[slots[i]], pos[slots[i]] = seqs[i][at[i]], at[i]
            tables[slots[i]] = engine._tables[slots[i]]
            rings[slots[i]] = engine._window_tables[slots[i]]
        logits, pools = decode(
            jnp.asarray(tok), pools, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(rings), jnp.asarray(tables[:, 0] < sentinel))
        for i in riding:
            out[i].append(np.asarray(logits[slots[i]]))
            at[i] += 1
    engine.pools = pools
    return [np.stack(o) for o in out], slots


# ------------------------------------------------------------- the model


def test_call_is_the_references_logits(cfg, ref, want_logits,
                                       model_and_params):
    model, params = model_and_params
    mc = model.cfg
    assert mc.kinds == (FULL, SLIDING, SLIDING, SLIDING) * 3
    assert mc.sparse_layers == (True,) * 12
    assert (mc.router_input, mc.router_score, mc.expert_activation) == (
        "mixer_in", "softmax", "relu")
    assert mc.num_heads // mc.num_kv_heads == 7
    assert "router_bias" not in params["layer0"]
    toks = tokens_of(90)          # five and a half windows
    got = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))[0]
    want = ref.logits(cfg, SEED, toks)
    assert float(np.abs(want - want_logits(toks)).max()) < 1e-5
    assert float(np.abs(want).max()) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


def test_each_new_mechanism_moves_the_logits(want_logits, model_and_params):
    """What the reference is compared WITH has every mechanism switched on:
    the same weights with one of them as every other configuration has it
    read far from it."""
    model, params = model_and_params
    toks = tokens_of(40, 1)
    want = want_logits(toks)
    for off in ({"router_input": "mlp_in"}, {"expert_activation": "silu"},
                {"rope_kinds": ()}, {"sliding_window": 12}):
        other = gpt_lib.GptLM(dataclasses.replace(model.cfg, **off))
        got = other.apply({"params": params},
                          jnp.asarray([toks], jnp.int32))[0]
        assert float(jnp.max(jnp.abs(got - want))) > 50 * LOGIT_TOL, off


def wrong_router_input(ref, monkeypatch):
    """The router fed the POST-attention normed stream, as every other
    configuration's is."""
    sound = ref.experts
    monkeypatch.setattr(ref, "experts",
                        lambda model, p, a, m: sound(model, p, m, m))


def wrong_activation(ref, monkeypatch):
    monkeypatch.setattr(ref, "gated", lambda h, wg, wu, wd: (
        jax.nn.silu(h @ wg) * (h @ wu)) @ wd)


def wrong_softmax(ref, monkeypatch):
    """A softmax over ALL the logits, its six largest taken as they are:
    they do not sum to 1."""
    def route(model, p, a):
        r = jax.nn.softmax(a @ p["router/kernel"], -1)
        top, chosen = jax.lax.top_k(r, model["experts_per_token"])
        return chosen, top
    monkeypatch.setattr(ref, "route", route)


@pytest.mark.parametrize("wrong", [wrong_router_input, wrong_activation,
                                   wrong_softmax],
                         ids=lambda f: f.__name__)
def test_a_wrong_reference_disagrees(cfg, want_logits, model_and_params,
                                     monkeypatch, wrong):
    """The comparison can FAIL: a reference that gets one of the three
    things this configuration adds wrong reads far from the program, on the
    sequence on which the sound one reads within the tolerance."""
    model, params = model_and_params
    toks = tokens_of(40, 2)
    got = np.asarray(model.apply({"params": params},
                                 jnp.asarray([toks], jnp.int32))[0])
    assert float(np.abs(got - want_logits(toks)).max()) < LOGIT_TOL
    broken = spec.named_module(cfg, "reference")     # a module of its own
    wrong(broken, monkeypatch)
    bad = padded_logits(broken, cfg)(toks)
    assert float(np.abs(got - bad).max()) > 50 * LOGIT_TOL


@pytest.mark.parametrize("prompts,lengths", [
    # stays inside the window (11 of 16), ends AT it (the last position
    # attended from is 15) and goes round its ring three times over (a
    # ring is 24 rows); a fourth slot idle
    ((5, 9, 70), (11, 17, 86)),
    # a prompt of one token, one that fills its page bucket, one that ends
    # a token into a page past a whole ring, one inside its first page
    ((1, 16, 25, 3), (20, 30, 60, 7)),
], ids=["inside-at-round", "edges"])
def test_prefill_then_paged_decode_is_the_references_logits(
        want_logits, model_and_params, prompts, lengths):
    """Lanes that stay inside the window beside lanes whose ring goes
    round, in ONE batch, logits compared at every step."""
    model, params = model_and_params
    engine = engine_of(model, params)
    seqs = [tokens_of(n, 10 + n) for n in lengths]
    got, _ = forced(engine, decode_fn(model, params), seqs, prompts)
    for seq, P, mine in zip(seqs, prompts, got):
        want = want_logits(seq)[P - 1:len(seq) - 1]
        assert mine.shape == want.shape
        assert float(np.abs(mine - want).max()) < LOGIT_TOL, (P, len(seq))
    # a window layer's pool holds a ring a slot and no more, whatever the
    # context; a full layer's holds the run
    for kind, (k_pool, v_pool) in zip(model.cfg.kinds, engine.pools):
        pages = 4 * RING + 1 if kind == SLIDING else 64 + 1
        assert k_pool.shape == v_pool.shape == (pages, PAGE, 2 * 16)
    assert engine.allocator.window_peak_in_use <= 4 * RING


def test_groups_of_seven_at_28_over_4(ref):
    """28 query heads over 4 key/value heads of 32 (the published head
    counts), one period: the whole forward, then prefill and paged decode
    through a ring gone round beside a lane inside the window."""
    cfg = small(num_heads=28, kv_heads=4, head_size=32, num_layers=4,
                layer_kinds=[FULL, SLIDING, SLIDING, SLIDING])
    model, params = built(cfg)
    assert params["layer0"]["q_proj"]["kernel"].shape == (64, 28, 32)
    assert params["layer0"]["kv_proj"]["kernel"].shape == (64, 2, 4, 32)
    want_logits = padded_logits(ref, cfg)
    toks = tokens_of(60, 3)
    got = model.apply({"params": params}, jnp.asarray([toks], jnp.int32))[0]
    assert float(jnp.max(jnp.abs(got - want_logits(toks)))) < LOGIT_TOL
    engine = engine_of(model, params, slots=2)
    seqs = [tokens_of(70, 4), tokens_of(14, 5)]
    got, _ = forced(engine, decode_fn(model, params), seqs, (50, 6))
    for seq, P, mine in zip(seqs, (50, 6), got):
        want = want_logits(seq)[P - 1:len(seq) - 1]
        assert float(np.abs(mine - want).max()) < LOGIT_TOL, P


def test_a_dead_lane_is_inert_bit_for_bit(model_and_params):
    """A row that is no sequence is routed NOWHERE by the plan laid down
    ahead of the mixer: whatever token it carries, the live lanes' logits,
    every pool and every layer's histogram are the same bit for bit, and
    the histograms count the live lanes' pairs alone."""
    model, params = model_and_params
    engine = engine_of(model, params, slots=3)
    for i, P in enumerate((70, 9)):      # round its ring; inside the window
        engine.admit(Request(tokens_of(P, 60 + i), 30))
    for _ in range(2):
        engine.step()
    engine.settle()
    assert engine._positions.tolist()[:2] == [72, 11]
    decode = decode_fn(model, params, routing=True)
    live = jnp.asarray(engine._tables[:, 0] < 64)
    assert live.tolist() == [True, True, False]

    def run(dead_token):
        tok = engine._tokens.copy()
        tok[2] = dead_token
        logits, pools, counts = decode(
            jnp.asarray(tok), engine.pools, jnp.asarray(engine._tables),
            jnp.asarray(engine._positions),
            jnp.asarray(engine._window_tables), live)
        return np.asarray(logits), jax.tree.leaves(pools), np.asarray(counts)

    a, pools_a, counts_a = run(0)
    b, pools_b, counts_b = run(377)
    np.testing.assert_array_equal(a[:2].view(np.uint32),
                                  b[:2].view(np.uint32))
    for x, y in zip(pools_a, pools_b):
        np.testing.assert_array_equal(np.asarray(x).view(np.uint32),
                                      np.asarray(y).view(np.uint32))
    np.testing.assert_array_equal(counts_a, counts_b)
    # two live lanes x six experts in each of twelve layers
    assert counts_a.sum(axis=1).tolist() == [2 * 6] * 12
    # and with the lane live its pairs are counted: the mask is what
    # silenced it
    everyone = decode(
        jnp.asarray(engine._tokens), engine.pools,
        jnp.asarray(engine._tables), jnp.asarray(engine._positions),
        jnp.asarray(engine._window_tables), jnp.ones((3,), bool))[2]
    assert np.asarray(everyone).sum(axis=1).tolist() == [3 * 6] * 12


def test_served_tokens_are_the_references_with_more_requests_than_slots(
        cfg, ref, model_and_params):
    model, params = model_and_params
    records = Rows()
    engine = engine_of(model, params, slots=2, records=records)
    requests = [Request(tokens_of(n, 100 + n), k)
                for n, k in ((50, 12), (7, 5), (16, 20), (3, 30), (80, 6))]
    serve(engine, *requests)
    assert [len(r.tokens) for r in requests] == [12, 5, 20, 30, 6]
    gaps = np.concatenate(ref.served_gaps(
        cfg, SEED, [{"prompt": r.prompt, "served": r.tokens}
                    for r in requests], 128))
    assert float(gaps.max()) < GAP_TOL
    pool = engine.stats()["kv_pool"]
    assert pool["pages_in_use"] == pool["window"]["pages_in_use"] == 0
    assert pool["window"]["peak_in_use"] == 2 * RING
    # the queue mixed lanes inside the window with lanes gone round, and
    # the step's record says how many of each
    stats = engine.stats()
    assert 0 < stats["window_lanes_wrapped"] < stats["lanes_live"]
    steps = [r for r in records.rows if r.get("kind") == "serve_step"]
    assert {0, 1} <= {r["window_lanes_wrapped"] for r in steps}
    assert all(r["window_lanes_wrapped"] <= r["lanes_live"] for r in steps)
    moe = stats["moe"]
    assert moe["routed_tokens"] == 6 * 12 * stats["lanes_live"]


def test_the_prefill_span_names_the_layers_routed_ahead(model_and_params):
    from distributed_tensorflow_tpu.utils import tracing
    model, params = model_and_params
    records = Rows()
    tracing.install(tracing.Tracer(Telemetry(records), run_id="ahead"))
    try:
        serve(engine_of(model, params), Request(tokens_of(40, 300), 3))
    finally:
        tracing.clear()
    span = next(r for r in records.rows if r.get("name") == "serve.prefill")
    assert (span["route_ahead_layers"], span["sparse_layers"],
            span["window_layers"], span["ring_pages"],
            span["row_bytes"]) == (12, 12, 9, RING, 3 * 2 * 2 * 16 * 4)


def test_routing_is_balanced_at_the_rehearsal_size(model_and_params):
    """The router's kernel is drawn like any kernel, so over many tokens
    every expert of every layer gets a share: between a quarter of the fair
    one and two and a half times it at this size (64 wide, a column's norm
    swings by an eighth and the top 6 amplify it; readings 0.32 to 1.8),
    far nearer at 2,560 wide, which the chip's counters show."""
    model, params = model_and_params
    toks = jnp.asarray(np.random.default_rng(9).integers(0, 512, (4, 256)))
    _, sown = model.apply({"params": params}, toks, mutable=["routing"])
    fair = 4 * 256 * 6 / 64
    for i in range(12):
        counts = np.asarray(sown["routing"][f"layer{i}"]["counts"][0])
        assert counts.sum() == 4 * 256 * 6
        assert 0.25 * fair < counts.min() and counts.max() < 2.5 * fair, i


# -------------------------------------------------------- route and plan


def test_the_softmax_route_is_numpys():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(40, 64)).astype(np.float32) * 2.0
    chosen, w = jax.jit(lambda x: experts_ops.route(
        x, None, 6, score="softmax"))(logits)
    order = np.argsort(-logits, axis=1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.asarray(chosen), order)
    top = np.take_along_axis(logits.astype(np.float64), order, 1)
    want = np.exp(top - top.max(1, keepdims=True))
    want /= want.sum(1, keepdims=True)
    assert np.abs(np.asarray(w) - want).max() < 1e-6
    assert np.abs(np.asarray(w).sum(1) - 1.0).max() < 1e-6
    # the same as a softmax over all 64 renormalised over the chosen
    full = np.exp(logits - logits.max(1, keepdims=True)).astype(np.float64)
    full /= full.sum(1, keepdims=True)
    picked = np.take_along_axis(full, order, 1)
    assert np.abs(picked / picked.sum(1, keepdims=True) - want).max() < 1e-6


@pytest.mark.parametrize("kw", [{"bias": jnp.zeros((64,))}, {"scale": 2.0}],
                         ids=["bias", "scale"])
def test_the_softmax_route_refuses_the_sigmoid_scores_knobs(kw):
    kw = {"bias": None, "scale": 1.0, **kw}
    with pytest.raises(ValueError, match="sigmoid"):
        experts_ops.route(jnp.zeros((4, 64)), kw["bias"], 6, kw["scale"],
                          "softmax")


# (tokens, width, expert width, experts, a token): the rehearsal shapes of
# the three configurations whose sparse MLP the benchmark already runs, a
# decode step's few lanes and a prefill's many rows (more than a row tile)
EXISTING = {"glm-4.7-flash": (5, 64, 16, 8, 2),
            "trinity-mini": (40, 64, 16, 128, 8),
            "lfm2-24b-a2b": (150, 64, 16, 16, 4)}


def _plain_experts(x, chosen, w, gate, up, down, act):
    want = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, share in zip(chosen[t], w[t]):
            want[t] += share * ((act(x[t] @ gate[e]) * (x[t] @ up[e]))
                                @ down[e])
    return want


@pytest.mark.parametrize("name", sorted(EXISTING))
def test_both_activations_are_a_plain_loop_at_the_existing_shapes(name):
    """``routed_experts`` under either activation against a loop over
    tokens and their experts, with dead rows: a dead row comes back zero
    and is counted for no expert.  The default is SiLU, what the three
    configurations trace."""
    T, H, I, E, K = EXISTING[name]
    rng = np.random.default_rng(E)
    x = rng.normal(size=(T, H)).astype(np.float32)
    gate, up = (rng.normal(size=(E, H, I)).astype(np.float32) * H ** -0.5
                for _ in range(2))
    down = rng.normal(size=(E, I, H)).astype(np.float32) * I ** -0.5
    live = rng.uniform(size=T) < 0.7
    chosen, w = experts_ops.route(
        jnp.asarray(rng.normal(size=(T, E)), jnp.float32), jnp.zeros((E,)),
        K, 1.5)
    chosen, w = np.asarray(chosen), np.asarray(w)
    acts = {"silu": lambda v: v / (1.0 + np.exp(-v)),
            "relu": lambda v: np.maximum(v, 0.0)}
    for activation, act in acts.items():
        want = _plain_experts(x, chosen, w, gate, up, down, act)
        want[~live] = 0.0
        got, counts = jax.jit(
            experts_ops.routed_experts, static_argnames="activation")(
                x, chosen, w, gate, up, down, live, activation=activation)
        # float32 sums of 64 and of 16 terms in another order
        assert np.abs(np.asarray(got) - want).max() < 5e-5
        assert not np.asarray(got)[~live].any()
        assert int(counts.sum()) == int(live.sum()) * K
        if activation == "silu":
            default, _ = jax.jit(experts_ops.routed_experts)(
                x, chosen, w, gate, up, down, live)
            np.testing.assert_array_equal(np.asarray(default),
                                          np.asarray(got))


def test_the_relu_experts_are_a_plain_loops():
    T, H, I, E, K = 24, 32, 20, 8, 2
    rng = np.random.default_rng(1)
    x = rng.normal(size=(T, H)).astype(np.float32)
    gate, up = (rng.normal(size=(E, H, I)).astype(np.float32) * H ** -0.5
                for _ in range(2))
    down = rng.normal(size=(E, I, H)).astype(np.float32) * I ** -0.5
    chosen = np.stack([rng.choice(E, K, replace=False) for _ in range(T)])
    w = rng.uniform(0.2, 1.0, size=(T, K)).astype(np.float32)
    want = _plain_experts(x, chosen, w, gate, up, down,
                          lambda v: np.maximum(v, 0.0))
    got, _ = experts_ops.routed_experts(
        x, chosen.astype(np.int32), w, gate, up, down, activation="relu")
    assert np.abs(want).max() > 0.1
    assert np.abs(np.asarray(got) - want).max() < 2e-5
    silu, _ = experts_ops.routed_experts(x, chosen.astype(np.int32), w, gate,
                                         up, down)
    assert np.abs(np.asarray(silu) - want).max() > 1e-2


# ------------------------------------------------------------ the config


BASE = dict(num_experts=8, experts_per_token=2, expert_intermediate_size=16,
            activation="swiglu", norm="rmsnorm")


@pytest.mark.parametrize("fields,message", [
    ({"router_input": "attention"}, "router_input is one of"),
    ({"router_score": "top1"}, "router_score of"),
    ({"expert_activation": "gelu"}, "expert_activation of"),
    ({"router_score": "softmax", "routed_scaling_factor": 2.0},
     "must be 1.0"),
    ({"expert_activation": "relu", "num_shared_experts": 1},
     "a shared expert's gate is SiLU"),
    ({"num_experts": 0, "experts_per_token": 0, "router_input": "mixer_in"},
     "num_experts is 0"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_the_three_fields_are_validated(fields, message):
    with pytest.raises(ValueError, match=message):
        gpt_lib.GptConfig(**{**BASE, **fields})


def test_the_three_fields_default_to_what_the_other_configurations_have():
    cfg = gpt_lib.GptConfig(**BASE)
    assert (cfg.router_input, cfg.router_score, cfg.expert_activation) == (
        "mlp_in", "sigmoid", "silu")
    assert gpt_lib.pool_geometry(cfg, 8).route_ahead_layers == 0
    ahead = dataclasses.replace(cfg, router_input="mixer_in")
    assert gpt_lib.pool_geometry(ahead, 8).route_ahead_layers == 4
    # a sigmoid-scored block keeps its selection bias, a softmax one has none
    tree = jax.eval_shape(lambda c=cfg: gpt_lib.GptLM(c).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert "router_bias" in tree["layer0"]
    soft = dataclasses.replace(cfg, router_score="softmax")
    tree = jax.eval_shape(lambda: gpt_lib.GptLM(soft).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert "router_bias" not in tree["layer0"]


def test_the_route_is_traced_ahead_of_the_mixer(model_and_params):
    """In the decode step's jaxpr the router's top-k of a layer stands
    before that layer's cache write, the first thing its mixer does after
    its projections; under ``router_input`` ``"mlp_in"`` it stands behind
    it."""
    model, params = model_and_params

    def first_equations(mc):
        m = gpt_lib.GptLM(mc)
        pools = gpt_lib.init_kv_pool(mc, 8, PAGE, num_slots=2)
        jaxpr = jax.make_jaxpr(lambda tok, pools: m.apply(
            {"params": params}, tok, pools, jnp.zeros((2, 4), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool),
            jnp.zeros((2, RING), jnp.int32),
            method=gpt_lib.GptLM.decode_paged))(
                jnp.zeros((2,), jnp.int32), pools)
        names = [e.primitive.name for e in jaxpr.jaxpr.eqns]
        return names.index("top_k"), names.index("scatter")

    top_k, write = first_equations(model.cfg)
    assert top_k < write
    top_k, write = first_equations(dataclasses.replace(
        model.cfg, router_input="mlp_in"))
    assert write < top_k
