"""The benchmark's own arithmetic, on the CPU: traffic that is the same for
every seed, rates over whole steps, quantiles and token-weighted means, the
peaks table, the operation and byte counts, and the files BENCHMARK.json
names."""

import json
import math
import os
import re

import pytest

from perfbench import check, costs, peaks, spec, stats, traffic

CHAT = spec.load_json(os.path.join(spec.HERE, "traffic", "chat_paced.json"))
LONG = spec.load_json(os.path.join(spec.HERE, "traffic",
                                   "longprompt_closed16.json"))
LM = spec.load_json(os.path.join(spec.HERE, "traffic", "lm_s1024.json"))
SEEDS = [0, 7, 2 ** 31 + 12345, 3000000019]


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_open_schedule_same_multiset_and_due_times_for_any_seed(seed):
    a = traffic.open_schedule(CHAT, 40, SEEDS[0])
    b = traffic.open_schedule(CHAT, 40, seed)
    key = lambda s: sorted((r["prompt_len"], r["num_tokens"])  # noqa: E731
                           for r in s if r["phase"] == "window")
    assert key(a) == key(b)
    assert len(a) == len(b)
    # Due-times are the same comb up to its phase.
    da = [r["due"] for r in a]
    db = [r["due"] for r in b]
    shift = db[0] - da[0]
    assert abs(shift) < 1.0 / CHAT["rate_per_s"]
    assert all(abs((y - x) - shift) < 1e-9 for x, y in zip(da, db))


def test_open_schedule_order_differs_between_seeds():
    a = traffic.open_schedule(CHAT, 40, 1)
    b = traffic.open_schedule(CHAT, 40, 2)
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in b]


def test_chat_strata_are_the_medians_the_issue_names():
    assert traffic.strata(CHAT["prompt"]) == [64, 115, 165, 222, 295, 397,
                                              569, 1018]
    out = traffic.strata(CHAT["output"])
    assert len(out) == 16 and out[0] >= 16 and out[-1] <= 384
    # Eight prompt lengths, eight distinct prefill buckets of 16-token pages:
    # exactly the engine's prefill_cache_cap, so nothing is evicted.
    buckets = traffic.serve_buckets(CHAT, CHAT["engine"]["page_size"])
    assert len(buckets) == 8 == CHAT["engine"]["prefill_cache_cap"]


@pytest.mark.parametrize("seed", SEEDS)
def test_closed_sequence_is_one_order_for_every_seed(seed):
    seq = traffic.closed_sequence(LONG, seed)
    items = [next(seq) for _ in range(48)]
    ref = traffic.closed_sequence(LONG, SEEDS[0])
    assert items == [next(ref) for _ in range(48)]
    prompts = sorted(traffic.strata(LONG["prompt"]))
    pairs = [(r["prompt_len"], r["num_tokens"]) for r in items]
    assert sorted(set(pairs)) == sorted(
        (p, o) for p in prompts for o in traffic.strata(LONG["output"]))
    assert sorted(pairs[:16]) == sorted(pairs[16:32]) == sorted(pairs[32:])
    # Any four neighbours hold every prompt length once (a Latin square),
    # so a window's prompt tokens do not depend on where it starts.
    for k in range(0, 44):
        assert sorted(p for p, _ in pairs[k:k + 4]) == prompts


def test_every_request_fits_the_engine_it_is_sent_to():
    for tr in (CHAT, LONG):
        eng = tr["engine"]
        cap = eng["page_size"] * eng["max_pages_per_seq"]
        worst = max(traffic.strata(tr["prompt"])) + max(
            traffic.strata(tr["output"]))
        assert worst <= cap
        assert worst <= tr["check_pad"]
        # No admission ever waits for pages: every slot can hold the worst.
        assert eng["num_pages"] >= eng["num_slots"] * eng["max_pages_per_seq"]


def test_lm_stream_is_a_function_of_seed_and_batch_index():
    a = traffic.PackedLmStream(LM, 50257, 11)
    b = traffic.PackedLmStream(LM, 50257, 11)
    x0, x1 = a.next_batch(4), a.next_batch(4)
    b.seek(1)
    assert (b.next_batch(4) == x1).all()
    assert (a.batch(0, 4) == x0).all() and not (x0 == x1).all()
    assert x0.shape == (4, 1024) and x0.max() == 50256   # eos closes documents
    rows = {bytes(r) for r in x0}
    assert len(rows) == 4                                   # rows all differ
    c = traffic.PackedLmStream(LM, 50257, 12)
    assert not (c.batch(0, 4) == x0).all()


def _log(durations, tokens=1000):
    t, out = 100.0, []
    for d in durations:
        out.append({"t_start": t, "t_end": t + d, "tokens": tokens})
        t += d
    return out


def test_whole_step_rate_counts_whole_steps_and_falls_with_a_stall():
    steady = _log([0.25] * 40)
    rate = stats.whole_step_rate(steady, 100.1, 109.9)
    assert rate == pytest.approx(4000.0)
    # Steps are not cut at the edges: those that ended inside count whole.
    inside = stats.steps_in(steady, 100.1, 109.9)
    assert inside[0]["t_start"] < 100.1 and len(inside) == 39
    stalled = _log([0.25] * 20 + [1.25] + [0.25] * 19)
    assert stats.whole_step_rate(stalled, 100.1, 110.9) < 0.92 * rate
    assert stats.whole_step_rate(steady, 0.0, 1.0) is None


def test_quantile_and_token_weighted_mean():
    values = list(range(1, 101))
    assert stats.quantile(values, 0.90) == 90
    assert stats.quantile(values, 0.50) == 50
    assert math.isnan(stats.quantile([], 0.5))
    # Two requests: 10 gaps in 1000 ms and 90 gaps in 1800 ms.  Weighted by
    # tokens the mean gap is 28 ms; a mean of the per-request means is 60.
    assert stats.weighted_mean([1000.0, 1800.0], [10, 90]) == pytest.approx(28)
    assert stats.weighted_mean([], []) is None


def test_spread_is_the_interquartile_range_over_the_median():
    assert stats.spread([100, 101, 102, 103, 104, 105]) == pytest.approx(
        (104.25 - 100.75) / 102.5)


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_costs_at_published_widths():
    gpt2 = spec.load_json(os.path.join(spec.HERE, "configs",
                                       "gpt2-medium.json"))
    d = costs.dims(gpt2)
    assert d["block_params"] == 24 * 12 * 1024 * 1024
    step = costs.train_step(gpt2, 8, 1024, 406_000_000)
    per_token = step["flops"] / (8 * 1024)
    assert 2.2e9 < per_token < 2.6e9          # ISSUE: about 2.4 GFLOP a token
    pk = peaks.peaks_for("TPU v5 lite")
    assert costs.least_time(step, pk)["bound"] == "compute"
    mistral = spec.load_json(os.path.join(spec.HERE, "configs",
                                          "mistral-7b.json"))
    dm = costs.dims(mistral)
    assert dm["layer_params"] == 218_103_808
    dec = costs.decode_step(mistral, [500] * 16)
    least = costs.least_time(dec, pk)
    assert least["bound"] == "memory"
    assert 0.009 < least["seconds"] < 0.012   # 7.5 GB of weights at 819 GB/s
    pre = costs.prefill(mistral, 3584)
    assert costs.least_time(pre, pk)["bound"] == "compute"
    assert costs.prefill(mistral, 3584)["flops"] > 3 * costs.prefill(
        mistral, 1024)["flops"]


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 1.0, "c": 1e-9}
    # The all-but-zero leaf is held against the median leaf, so its noise
    # does not decide; a leaf that has not moved reads about 1.
    gap, where = check.worst_leaf_gap({"a": 1.01, "b": 1.0, "c": 5e-9}, ref)
    assert gap == pytest.approx(0.01) and where == "a"
    gap, where = check.worst_leaf_gap({"a": 1.0, "b": 0.0, "c": 1e-9}, ref)
    assert gap == pytest.approx(1.0) and where == "b"
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, ref)


def test_verdict_needs_every_number_under_a_limit_of_its_own():
    ok, compared = check.verdict({"x": 0.1, "y": 0.0}, {"x": 0.2, "y": 0})
    assert ok and compared["x"] == {"value": 0.1, "limit": 0.2}
    assert not check.verdict({"x": 0.3, "y": 0.0}, {"x": 0.2, "y": 0})[0]
    assert not check.verdict({"x": 0.1}, {})[0]          # no limit, no pass
    assert not check.verdict({}, {"x": 1})[0]            # nothing compared
    assert not check.verdict({"x": float("nan")}, {"x": 1})[0]


def test_train_memory_is_the_steps_footprint_where_the_allocator_reads_less():
    from perfbench.metrics import _common
    gib = 2 ** 30
    mem = {"argument": 5 * gib, "output": 5 * gib, "alias": 5 * gib,
           "temp": 9 * gib, "generated_code": 0}
    ctx = {"counters": {"memory_analysis": mem},
           "device": {"memory_peak_bytes": 6 * gib}}
    # The allocator's peak misses a running step's temporaries: the
    # footprint is reported, and it moves with the temporaries (batch,
    # remat, the attention's lowering) while that peak stands still.
    assert _common.train_hbm_peak_gib(ctx) == pytest.approx(14.0)
    mem["temp"] = 4 * gib
    assert _common.train_hbm_peak_gib(ctx) == pytest.approx(9.0)
    # An output that reuses no donated argument is held besides.
    mem["alias"] = 0
    assert _common.train_hbm_peak_gib(ctx) == pytest.approx(14.0)
    # Where the allocator saw more, or the compiler gave no account, the
    # allocator's peak stands; nothing to read returns None, never 0.
    ctx["device"]["memory_peak_bytes"] = 15 * gib
    assert _common.train_hbm_peak_gib(ctx) == pytest.approx(15.0)
    ctx["counters"]["memory_analysis"] = None
    assert _common.train_hbm_peak_gib(ctx) == pytest.approx(15.0)
    ctx["device"]["memory_peak_bytes"] = 0
    assert _common.train_hbm_peak_gib(ctx) is None


@pytest.mark.parametrize("cell", ["serve_mistral7b_chat",
                                  "serve_mistral7b_longprompt"])
def test_serving_limits_hold_the_widest_gap_as_well_as_the_mean(cell):
    for size in ("chip", "rehearsal"):
        limits = spec.load_json(os.path.join(
            spec.HERE, "limits", cell + ".json"))[size]
        assert set(limits) == {"served_logit_gap_mean",
                               "served_logit_gap_widest"}
        # One wrong token among some hundreds moves the mean by its gap
        # over their count; the widest gap sees it whole.
        assert limits["served_logit_gap_widest"] > \
            20 * limits["served_logit_gap_mean"]


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = spec.benchmark()


def test_benchmark_json_names_files_that_exist():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert os.path.exists(os.path.join(spec.ROOT, cfg["reference"]))
        assert all(k in cfg for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            spec.HERE, "traffic", w["traffic"] + ".json"))
        assert w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader_and_cells_that_report_what_it_moves(
        metric):
    assert NAME.match(metric["name"])
    assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert os.path.exists(os.path.join(spec.HERE, "metrics",
                                       metric["name"] + ".py"))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "moves" in metric:
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(moved.get("workloads", cells))
    else:
        assert 0 < metric["bound"] <= 0.1


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    m = spec.cell(cell)["metrics"]
    assert "setup_s" in m["end_to_end"] and len(m["end_to_end"]) >= 2
    assert m["per_layer"]
    assert spec.cell(cell)["limits"], "limits/<cell>.json has no limits for it"
