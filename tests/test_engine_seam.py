"""What the serving engine takes from the model file, held on toy models of
the seven served forms (grouped-query, hybrid with a recurrent state, latent
rows with routed experts, a weight-shared loop, sliding windows beside full
layers, short convolutions beside full layers, and sliding windows behind a
full layer with the route laid down AHEAD of the mixer; the last four around
routed experts as their configurations are):

- ``gpt_lib.pool_geometry``'s bytes are the bytes of ``init_kv_pool``'s own
  arrays, a token and a slot;
- a landed step's counters reach ``engine.stats()``, the ``serve_step``
  record and the ``serve.step.retire`` event under the names written down
  here from the tree before the counters were one record (PR 49): what
  ``perfbench/`` and ``/statz`` read by name can be neither dropped nor
  renamed without a red test.  (PR 50 added ``window_lanes_wrapped``, the
  seated lanes whose ring has gone round, to the forms with a ring and to
  ``engine.stats()``.)
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import Request
from distributed_tensorflow_tpu.utils import profiling
from distributed_tensorflow_tpu.utils.telemetry import Telemetry

BASE = dict(vocab_size=64, hidden_size=32, num_heads=4,
            intermediate_size=64, max_position=64, dtype="float32")
GATED = dict(norm="rmsnorm", activation="swiglu")
EXPERTS = dict(num_experts=4, experts_per_token=2,
               expert_intermediate_size=16, num_shared_experts=1,
               first_dense_layers=1)
FORMS = {
    "dense": dict(num_layers=2, kv_heads=2, pos_encoding="rope"),
    "hybrid": dict(
        num_layers=4, pos_encoding="none", **GATED,
        layer_kinds=(gpt_lib.LINEAR_ATTENTION,) * 3 + (
            gpt_lib.FULL_ATTENTION,),
        linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16),
    "latent": dict(
        num_layers=2, pos_encoding="none", **GATED, latent_kv_rank=16,
        latent_q_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, **EXPERTS),
    "looped": dict(
        num_layers=2, pos_encoding="rope", **GATED,
        norm_placement="sandwich", loop_steps=3, exit_gate=True),
    "sliding": dict(
        num_layers=3, kv_heads=2, pos_encoding="rope", **GATED,
        layer_kinds=(gpt_lib.SLIDING_ATTENTION,) * 2 + (
            gpt_lib.FULL_ATTENTION,),
        sliding_window=8, rope_kinds=(gpt_lib.SLIDING_ATTENTION,),
        **EXPERTS),
    "conv": dict(
        num_layers=3, kv_heads=2, pos_encoding="rope", **GATED,
        layer_kinds=(gpt_lib.SHORT_CONV, gpt_lib.FULL_ATTENTION,
                     gpt_lib.SHORT_CONV),
        short_conv_kernel_dim=3, **EXPERTS),
    "ahead": dict(
        num_layers=3, kv_heads=2, pos_encoding="rope", **GATED,
        layer_kinds=(gpt_lib.FULL_ATTENTION,) + (
            gpt_lib.SLIDING_ATTENTION,) * 2,
        sliding_window=8, rope_kinds=(gpt_lib.SLIDING_ATTENTION,),
        num_experts=8, experts_per_token=3, expert_intermediate_size=16,
        router_input="mixer_in", router_score="softmax",
        expert_activation="relu"),
}
PAGE, PAGES, SLOTS = 4, 48, 3
PROMPT = [11, 3, 40, 7, 25, 9, 31, 2, 18, 5, 44, 1]

# ------------------------------------------ the names, from the parent tree

STATS = set("""
    active_slots attn_kernel_layers attn_pages_read capacity_tokens
    compile_cache.cap compile_cache.chunk_programs compile_cache.evictions
    compile_cache.prefill_programs engine_step kv_dtype kv_pool.free_pages
    kv_pool.internal_fragmentation kv_pool.num_pages kv_pool.page_size
    kv_pool.pages_in_use kv_pool.peak_in_use kv_pool.row_bytes_per_token
    kv_pool.sequences kv_pool.state_bytes kv_pool.state_bytes_peak
    kv_pool.state_bytes_per_slot kv_pool.state_slots kv_pool.utilization
    kv_pool.window.free_pages kv_pool.window.num_pages
    kv_pool.window.pages_in_use kv_pool.window.peak_in_use
    kv_pool.window.ring_pages kv_pool.window.row_bytes_per_token
    lane_steps_discarded lanes_live loop.exit_step_expected_milli
    loop.loop_steps_run loop.loop_tokens model_step moe.expert_slots
    moe.expert_tokens_max moe.experts_touched moe.routed_tokens num_slots
    pool_steps_copied pool_steps_in_place prefill_chunk prefilling_slots
    quantize sample_steps_greedy sample_steps_sampled spec_k spec_rows
    state_bytes state_slots steps_ahead steps_serial swaps table_pages
    table_pages_held window_attn_pages_read window_lanes_wrapped
    window_table_pages window_table_pages_held""".split())
#: What every form's step counts, under the names of both sinks.
COUNTED = set("""
    attn_kernel_layers attn_pages_read lane_steps_discarded lanes_live
    pools_in_place sampled_lanes steps_ahead steps_serial table_pages
    table_pages_held""".split())
RECORD = COUNTED | set("""
    active_slots admitted dispatch_ms fetch_ms kv_pages_in_use
    kv_pages_total model_step prefill_ms prefill_rows prompt_tokens
    queue_depth retire_ms retired spec_accepted spec_rows stage_ms
    state_bytes state_slots step_ms t_start upload_ms""".split())
EVENT = COUNTED | {"upload_us", "dispatch_us"}
STATE = {"state_slots", "state_bytes"}
ROUTED = {"experts_touched", "expert_slots", "expert_tokens_max",
          "routed_tokens"}
LOOPED = {"loop_steps_run", "loop_tokens", "exit_step_expected_milli"}
WINDOW = {"window_table_pages", "window_table_pages_held",
          "window_attn_pages_read", "window_pages_in_use",
          "window_pages_peak", "window_lanes_wrapped"}
#: Beside those, by form: (on the record and the event, on the event alone).
EXTRA = {"dense": (set(), set()), "hybrid": (set(), STATE),
         "latent": (ROUTED, set()), "looped": (LOOPED, set()),
         "sliding": (ROUTED | WINDOW, set()), "conv": (ROUTED, STATE),
         "ahead": (ROUTED | WINDOW, set())}


def model_of(form):
    return gpt_lib.GptLM(gpt_lib.GptConfig(**BASE, **FORMS[form]))


def flat(tree, prefix=""):
    for key, value in tree.items():
        if isinstance(value, dict):
            yield from flat(value, f"{prefix}{key}.")
        else:
            yield prefix + key


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_geometrys_bytes_are_the_pools_own(form):
    cfg = model_of(form).cfg
    geo = gpt_lib.pool_geometry(cfg, PAGE)
    pools = jax.eval_shape(lambda: gpt_lib.init_kv_pool(
        cfg, PAGES, PAGE, num_slots=SLOTS))
    held = {"pages": 0, "ring": 0, None: 0}
    for kind, entry in zip(cfg.kinds, pools):
        # A page's bytes over its tokens; a slot's row whole.
        held[gpt_lib.KINDS[kind].table] += sum(
            x.size // x.shape[0] * x.dtype.itemsize for x in entry)
    assert geo.row_bytes * PAGE == cfg.loop_steps * held["pages"] > 0
    assert geo.window_row_bytes * PAGE == held["ring"]
    assert geo.state_bytes == held[None]
    assert bool(geo.ring_pages) == bool(held["ring"])
    rings = [x.shape[0] for kind, entry in zip(cfg.kinds, pools)
             for x in entry if gpt_lib.KINDS[kind].table == "ring"]
    assert set(rings) <= {SLOTS * geo.ring_pages + 1}
    assert geo.cache_rows == cfg.loop_steps * sum(
        gpt_lib.KINDS[kind].table is not None for kind in cfg.kinds)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_sink_keeps_the_names_it_had(form, monkeypatch):
    model = model_of(form)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    records, events = [], []
    telemetry = Telemetry()
    emit, annotate = telemetry.emit, profiling.annotate
    telemetry.emit = lambda kind, step=0, **f: (
        records.append((kind, f)), emit(kind, step=step, **f))
    monkeypatch.setattr(profiling, "annotate", lambda name, **stats: (
        events.append((name, stats)), annotate(name, **stats))[1])
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=SLOTS, page_size=PAGE, num_pages=PAGES,
        max_pages_per_seq=8), telemetry=telemetry)
    engine.admit(Request(PROMPT, 5))
    engine.admit(Request(PROMPT[:7], 3, temperature=0.9, top_k=12,
                         top_p=0.95, seed=3))
    while engine.active_slots:
        engine.step()
    both, event_only = EXTRA[form]
    steps = [f for kind, f in records if kind == "serve_step"]
    retires = [s for name, s in events if name == "serve.step.retire"]
    assert len(steps) == len(retires) == 5
    assert set(flat(engine.stats())) == STATS
    assert all(set(f) == RECORD | both for f in steps)
    assert all(set(s) == EVENT | both | event_only for s in retires)
    # An event's stats are whole numbers; the sums are the steps' summed.
    assert all(type(v) is int for s in retires for v in s.values())
    stats = engine.stats()
    sums = {**stats, **stats["moe"], **stats["loop"]}
    for name in (COUNTED | both) - {"pools_in_place", "sampled_lanes",
                                    "window_pages_in_use",
                                    "window_pages_peak"}:
        assert sums[name] == sum(s[name] for s in retires), name
    assert stats["pool_steps_in_place"] + stats["pool_steps_copied"] == 5
    assert stats["sample_steps_greedy"] + stats["sample_steps_sampled"] == 5
