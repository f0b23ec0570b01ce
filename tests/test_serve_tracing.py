"""Request-level serving tracing + the SLO engine (ISSUE 9;
docs/observability.md, "Serving tracing & SLOs").

Covers the span tree a served request leaves
(queue -> reserve -> prefill -> N decode rounds -> retire under one
``serve.request`` root with correct parent/child ids), the swap-pause
span stamped onto in-flight requests, Perfetto export of a real served
run, SLO window math + multi-window burn-rate triggers, the Prometheus
``/metricz`` exposition, the serving flight recorder, and the per-tenant
counters ``/statz`` gained (429s, abandoned retirements, queue HWM).
"""

from __future__ import annotations

import dataclasses
import json
import re
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import gpt as gpt_lib
from distributed_tensorflow_tpu.serving.client import Overloaded, ServeClient
from distributed_tensorflow_tpu.serving.engine import (DecodeEngine,
                                                       EngineConfig)
from distributed_tensorflow_tpu.serving.scheduler import (FairScheduler,
                                                          Request,
                                                          TenantConfig)
from distributed_tensorflow_tpu.serving.server import ServingServer
from distributed_tensorflow_tpu.serving.slo import (Objective, SloEngine,
                                                    parse_slos)
from distributed_tensorflow_tpu.tools import export_trace, summarize_run
from distributed_tensorflow_tpu.tools import watch_serve
from distributed_tensorflow_tpu.utils import tracing
from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
from distributed_tensorflow_tpu.utils.telemetry import Telemetry


def small_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_position=64, dtype="float32")
    base.update(kw)
    return dataclasses.replace(gpt_lib.mini(), **base)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = small_cfg()
    model = gpt_lib.GptLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    return model, params


class _Capture:
    """Telemetry + installed tracer + record capture, torn down safely."""

    def __init__(self, path=None):
        self.logger = MetricsLogger(path)
        self.telemetry = Telemetry(self.logger)
        self.records: list[tuple[str, int, dict]] = []
        orig = self.telemetry.emit

        def emit(kind, step=0, **fields):
            self.records.append((kind, step, dict(fields)))
            orig(kind, step=step, **fields)

        self.telemetry.emit = emit
        self.tracer = tracing.install(
            tracing.Tracer(self.telemetry, run_id="serve-test"))

    def spans(self, name=None):
        out = [dict(f, step=s) for kind, s, f in self.records
               if kind == "span"]
        if name is not None:
            out = [s for s in out if s["name"] == name]
        return out


@pytest.fixture()
def capture():
    cap = _Capture()
    yield cap
    tracing.clear()
    cap.logger.close()


def drain(engine, sched=None):
    while True:
        if sched is not None:
            while engine.free_slots > 0:
                req = sched.next_request(engine.can_admit)
                if req is None:
                    break
                engine.admit(req)
        if engine.active_slots == 0:
            break
        engine.step(queue_depth=sched.depth() if sched else 0)


# ------------------------------------------------------------ span tree


@pytest.mark.smoke
def test_request_span_tree_complete_over_http(model_and_params, capture):
    """One served request decomposes into queue -> reserve -> prefill ->
    N decode rounds -> retire under a single root, parent/child ids
    consistent, all sharing the request-keyed trace id."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=capture.telemetry)
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=60.0,
                        telemetry=capture.telemetry)
    srv.start()
    try:
        out = ServeClient(f"http://127.0.0.1:{srv.port}").generate(
            [5, 6, 7, 8], 6, tenant="alice")
        assert out["tokens_out"] == 6
    finally:
        srv.shutdown()

    spans = capture.spans()
    roots = [s for s in spans if s["name"] == "serve.request"]
    assert len(roots) == 1
    root = roots[0]
    assert root["parent_id"] == 0
    rid = root["request_id"]
    trace_id = root["trace_id"]
    assert trace_id == f"serve-test/req{rid}"
    mine = [s for s in spans if s.get("trace_id") == trace_id]
    by_name = {}
    for s in mine:
        by_name.setdefault(s["name"], []).append(s)
    # Every lifecycle stage present, exactly once (except decode lanes).
    for name in ("serve.queue", "serve.reserve", "serve.prefill",
                 "serve.retire"):
        assert len(by_name.get(name, [])) == 1, (name, by_name.keys())
        assert by_name[name][0]["parent_id"] == root["span_id"]
        assert by_name[name][0]["request_id"] == rid
    # 6 generated tokens, one per plain decode round -> 6 lane spans,
    # each a child of a serve.decode_round engine span.
    lanes = by_name.get("serve.decode_lane", [])
    assert len(lanes) == 6
    rounds = {s["span_id"]: s for s in spans
              if s["name"] == "serve.decode_round"}
    for lane in lanes:
        assert lane["parent_id"] in rounds
        assert lane["tenant"] == "alice"
    # Root duration covers the children: queue + decode all inside it.
    assert root["dur_ms"] > 0
    assert by_name["serve.queue"][0]["dur_ms"] <= root["dur_ms"]
    # The e2e figure decomposes: queue + prefill + rounds account for
    # (almost) all of the root span — nothing big is untraced.
    accounted = (by_name["serve.queue"][0]["dur_ms"]
                 + by_name["serve.prefill"][0]["dur_ms"]
                 + sum(rounds[lane["parent_id"]]["dur_ms"]
                       for lane in lanes))
    assert accounted <= root["dur_ms"] * 1.5


def test_swap_pause_span_lands_on_in_flight_requests(model_and_params,
                                                     capture):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=capture.telemetry)
    req = Request([5, 6, 7, 8], 8, tenant="alice")
    engine.admit(req)
    engine.step()                       # in flight
    engine.swap_params(params, step=7)
    engine.step()                       # adopts the swap, then decodes
    drain(engine)
    pauses = capture.spans("serve.swap_pause")
    assert len(pauses) == 1
    assert pauses[0]["request_id"] == req.id
    assert pauses[0]["trace_id"] == f"serve-test/req{req.id}"
    assert pauses[0]["parent_id"] == req.span_root
    assert pauses[0]["to_model_step"] == 7
    swaps = capture.spans("serve.swap")
    assert len(swaps) == 1 and swaps[0]["in_flight"] == 1


def test_trace_export_of_served_run_is_perfetto_loadable(
        model_and_params, tmp_path):
    """A real (in-process) served run's stream exports to valid Chrome
    trace-event JSON: request spans present with args, clock offset
    applied to the worker row."""
    model, params = model_and_params
    path = tmp_path / "serve.jsonl"
    logger = MetricsLogger(path)
    telemetry = Telemetry(logger)
    tracing.install(tracing.Tracer(telemetry, run_id="serve-test"))
    try:
        # A serving stream stamps the same clock_sync training workers do
        # (tools/serve.py does this against --coord); offsets must apply.
        telemetry.emit("clock_sync", step=0, offset_ms=250.0, rtt_ms=1.0,
                       t_unix=round(time.time(), 6), source="coord_time")
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
            telemetry=telemetry)
        sched = FairScheduler()
        sched.submit(Request([5, 6, 7], 5, tenant="alice"))
        sched.submit(Request([9, 10], 4, tenant="bob"))
        drain(engine, sched)
    finally:
        tracing.clear()
        logger.close()

    out = tmp_path / "trace.json"
    assert export_trace.main([str(path), "--output", str(out)]) == 0
    trace = json.loads(out.read_text())
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "no span events exported"
    for e in spans:    # Chrome trace-event contract
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float))
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    roots = [e for e in spans if e["name"] == "serve.request"]
    assert len(roots) == 2
    assert all(e["args"].get("request_id") is not None for e in roots)
    assert all(e["args"].get("tenant") in ("alice", "bob")
               for e in roots)
    # The measured clock offset is applied to (and displayed on) the row.
    proc = next(e for e in events if e.get("name") == "process_name")
    assert "clock_offset_ms=+250.000" in proc["args"]["name"]


# ------------------------------------------------------------ SLO engine


def test_slo_parse_grammar_and_errors():
    objs = parse_slos("search:ttft_p95_ms<=50,*:error_rate<=0.01,"
                      "ads:reject_rate<=0.05,x:e2e_p999_ms<=2000")
    assert [o.tenant for o in objs] == ["search", "*", "ads", "x"]
    assert objs[0].metric == "ttft_ms" and objs[0].threshold_ms == 50
    assert objs[0].target == 0.95 and abs(objs[0].budget - 0.05) < 1e-9
    assert objs[3].target == 0.999
    assert objs[0].label == "ttft_p95_ms<=50"
    assert objs[1].label == "error_rate<=0.01"
    assert parse_slos("") == []
    for bad in ("nocolon", "t:ttft_p95<=50", "t:ttft_p95_ms=50",
                "t:bogus_rate<=0.1", ":ttft_p95_ms<=50",
                # 3-digit percentiles are per-mille and ONLY p999 —
                # p100/p500 are typos that must not silently parse.
                "t:ttft_p100_ms<=50", "t:e2e_p500_ms<=100"):
        with pytest.raises(ValueError):
            parse_slos(bad)
    with pytest.raises(ValueError):
        Objective("t", "ttft_ms", 0.95)          # missing threshold
    with pytest.raises(ValueError):
        Objective("t", "error_rate", 0.99, threshold_ms=1.0)


def test_slo_sliding_windows_and_burn_rate_math():
    obj = Objective("t", "ttft_ms", 0.95, threshold_ms=50.0)
    eng = SloEngine([obj], short_window_s=10.0, long_window_s=100.0,
                    burn_threshold=14.4, clock=lambda: 0.0)
    # 19 good + 1 bad at t=0..19 -> bad fraction 5% = burn 1.0 (budget
    # consumed exactly at the allowed rate).
    for i in range(20):
        eng.observe_request("t", ttft_ms=10.0 if i else 100.0,
                            tpot_ms=None, e2e_ms=None, now=float(i))
    e = eng.evaluate(now=19.0)[0]
    assert e["good_long"] == 19 and e["bad_long"] == 1
    assert e["burn_long"] == pytest.approx(1.0)
    assert not e["burning"]
    # Short window sees only t>=9: all good -> burn_short 0.
    assert e["bad_short"] == 0 and e["burn_short"] == 0.0
    # Events age out of the long window too.
    e = eng.evaluate(now=150.0)[0]
    assert e["good_long"] == e["bad_long"] == 0


def test_slo_multi_window_burn_alert_triggers_and_clears():
    obj = Objective("t", "ttft_ms", 0.95, threshold_ms=50.0)
    eng = SloEngine([obj], short_window_s=10.0, long_window_s=50.0,
                    burn_threshold=14.4, clock=lambda: 0.0)
    # Sustained 100% bad: burn = 1/0.05 = 20 >= 14.4 in BOTH windows.
    for i in range(5):
        eng.observe_request("t", ttft_ms=500.0, tpot_ms=None,
                            e2e_ms=None, now=float(i))
    e = eng.evaluate(now=5.0)[0]
    assert e["burn_short"] == pytest.approx(20.0)
    assert e["burning"]
    # The breach scrolls out of the SHORT window -> alert clears (the
    # fast-clear property the short window exists for), long still burns.
    e = eng.evaluate(now=20.0)[0]
    assert e["burn_short"] == 0.0 and e["burn_long"] > 14.4
    assert not e["burning"]
    snap = eng.snapshot(now=20.0)
    assert snap["burning"] == []
    assert snap["ever_burning"] == ["t:ttft_p95_ms<=50"]


def test_slo_generous_budget_still_alerts_at_full_burn():
    """Burn is capped at 1/budget, so an objective with budget >
    1/burn_threshold (e.g. a p50 target) alerts at full-budget burn
    (100% bad) rather than never."""
    obj = Objective("t", "e2e_ms", 0.50, threshold_ms=500.0)  # budget 0.5
    eng = SloEngine([obj], short_window_s=10.0, long_window_s=10.0,
                    burn_threshold=14.4, clock=lambda: 0.0)
    for i in range(4):
        eng.observe_request("t", ttft_ms=None, tpot_ms=None,
                            e2e_ms=9999.0, now=float(i))
    e = eng.evaluate(now=4.0)[0]
    assert e["burn_long"] == pytest.approx(2.0)   # the 1/budget ceiling
    assert e["burn_alert_at"] == pytest.approx(2.0)
    assert e["burning"]
    # Half bad is within a 50% budget: burn 1.0 < alert_at -> quiet.
    eng2 = SloEngine([obj], short_window_s=10.0, long_window_s=10.0,
                     burn_threshold=14.4, clock=lambda: 0.0)
    for i in range(4):
        eng2.observe_request("t", ttft_ms=None, tpot_ms=None,
                             e2e_ms=9999.0 if i % 2 else 1.0,
                             now=float(i))
    e2 = eng2.evaluate(now=4.0)[0]
    assert e2["burn_long"] == pytest.approx(1.0) and not e2["burning"]


def test_slo_error_and_reject_budgets():
    eng = SloEngine(parse_slos("t:error_rate<=0.5,t:reject_rate<=0.5"),
                    short_window_s=10.0, long_window_s=10.0,
                    burn_threshold=1.5, clock=lambda: 0.0)
    eng.observe_request("t", ttft_ms=1.0, tpot_ms=1.0, e2e_ms=1.0,
                        ok=False, now=1.0)
    eng.observe_admission("t", rejected=True, now=1.0)
    eng.observe_admission("t", rejected=False, now=1.0)
    err, rej = eng.evaluate(now=2.0)
    assert err["bad_long"] == 1 and err["burn_long"] == pytest.approx(2.0)
    assert err["burning"]
    assert rej["bad_long"] == 1 and rej["good_long"] == 1
    assert rej["burn_long"] == pytest.approx(1.0) and not rej["burning"]


# ---------------------------------------------------- server integration


@pytest.fixture()
def slo_server(model_and_params, capture):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=capture.telemetry)
    slo = SloEngine(parse_slos("alice:ttft_p95_ms<=0.001,"
                               "*:error_rate<=0.01"),
                    short_window_s=5.0, long_window_s=30.0)
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=60.0,
                        telemetry=capture.telemetry, slo=slo,
                        slo_emit_every_s=0.05)
    srv.start()
    yield srv
    srv.shutdown()


def test_breach_visible_in_statz_metricz_and_stream(slo_server, capture):
    """A deliberately impossible TTFT objective burns after one request,
    visible through every surface: /statz (watch_serve's feed), the
    Prometheus /metricz text, and the kind="slo" telemetry records
    summarize_run gates on."""
    client = ServeClient(f"http://127.0.0.1:{slo_server.port}")
    client.generate([5, 6, 7, 8], 4, tenant="alice")
    deadline = time.time() + 5.0
    stats = None
    while time.time() < deadline:
        stats = client.stats()
        if stats.get("slo", {}).get("burning"):
            break
        time.sleep(0.05)
    assert stats["slo"]["burning"] == ["alice:ttft_p95_ms<=0.001"]
    burning = [o for o in stats["slo"]["objectives"] if o["burning"]]
    assert burning and burning[0]["burn_short"] >= 14.4
    # error_rate objective stays quiet on an ok request.
    quiet = [o for o in stats["slo"]["objectives"]
             if o["objective"] == "error_rate<=0.01"]
    assert quiet and not quiet[0]["burning"]

    with urllib.request.urlopen(
            f"http://127.0.0.1:{slo_server.port}/metricz") as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    assert ('serve_slo_burning{tenant="alice",'
            'objective="ttft_p95_ms<=0.001"} 1') in text

    # Records on the stream (for summarize_run's SLO section).
    deadline = time.time() + 5.0
    while time.time() < deadline:
        slo_recs = [f for kind, _, f in capture.records if kind == "slo"]
        if any(f["burning"] for f in slo_recs):
            break
        time.sleep(0.05)
    assert any(f["burning"] and f["tenant"] == "alice" for f in slo_recs)
    for f in slo_recs:
        missing = [k for k in summarize_run.REQUIRED_SLO_FIELDS
                   if k not in f]
        assert not missing, missing


def test_metricz_exposition_format_parses(slo_server):
    client = ServeClient(f"http://127.0.0.1:{slo_server.port}")
    client.generate([1, 2, 3], 3, tenant="alice")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{slo_server.port}/metricz") as r:
        text = r.read().decode()
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'            # metric name
        r'(\{[a-zA-Z_]+="[^"]*"(,[a-zA-Z_]+="[^"]*")*\})?'  # labels
        r' -?[0-9.e+-]+(\n|$)')                 # value
    names = set()
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert sample.match(line), f"unparseable exposition line: {line!r}"
        names.add(line.split("{")[0].split(" ")[0])
    for expected in ("serve_requests_total", "serve_tokens_out_total",
                     "serve_step_ms", "serve_ttft_ms",
                     "serve_kv_pool_pages", "serve_queue_depth",
                     "serve_model_step", "serve_slo_burn_rate"):
        assert expected in names, (expected, sorted(names))


def test_per_tenant_counters_429_abandoned_queue_hwm(model_and_params,
                                                     capture):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=1, page_size=4, num_pages=16, max_pages_per_seq=4),
        telemetry=capture.telemetry)
    slo = SloEngine(parse_slos("flood:reject_rate<=0.01"),
                    short_window_s=5.0, long_window_s=30.0,
                    burn_threshold=1.5)
    srv = ServingServer(engine,
                        FairScheduler([TenantConfig("flood",
                                                    max_queue=2)]),
                        port=0, request_timeout_s=60.0,
                        telemetry=capture.telemetry,
                        slo=slo, slo_emit_every_s=0.05)
    # Fill the bound BEFORE the loop starts draining, then one more ->
    # 429.  The first queued caller then gives up (abandoned) while
    # still queued; the scheduler drops it at the next pop.
    gone = Request([1, 2], 2, tenant="flood")
    served = Request([1, 2, 3], 2, tenant="flood")
    srv.scheduler.submit(gone)
    srv.scheduler.submit(served)
    with pytest.raises(Exception):
        srv.submit(Request([1, 2], 2, tenant="flood"))
    gone.abandoned = True
    srv.start()
    try:
        assert served.event.wait(30.0), "queued request never completed"
        deadline = time.time() + 5.0
        stats = None
        while time.time() < deadline:
            stats = srv.stats()
            tenant_recs = [f for kind, _, f in capture.records
                           if kind == "serve_tenant"
                           and f["tenant"] == "flood"]
            if (stats["slo"]["burning"] and tenant_recs
                    and tenant_recs[-1]["rejected"] == 1):
                break
            time.sleep(0.05)
    finally:
        srv.shutdown()
    t = stats["tenants"]["flood"]
    assert t["rejected"] == 1
    assert t["abandoned"] == 1          # the dropped queued head
    assert t["queued_hwm"] == 2
    assert stats["queue_depth_hwm"] == 2
    assert stats["counters"]["serve_rejected"] == 1
    assert stats["counters"]["serve_rejected[flood]"] == 1
    # The reject burned its tight budget (multi-surface: also /statz).
    assert stats["slo"]["burning"] == ["flood:reject_rate<=0.01"]
    # serve_tenant records carry the counters onto the stream.
    assert tenant_recs and tenant_recs[-1]["rejected"] == 1
    assert tenant_recs[-1]["abandoned"] == 1
    assert tenant_recs[-1]["queued_hwm"] == 2


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_engine_fatal_dumps_serving_flight_and_releases_callers(
        model_and_params, tmp_path):
    """A BaseException escaping the engine loop leaves
    <metrics_file>.flight (the serving flight recorder) and fails the
    blocked caller instead of hanging it; summarize_run ingests the
    dump."""
    model, params = model_and_params
    path = tmp_path / "serve.jsonl"
    logger = MetricsLogger(path)
    telemetry = Telemetry(logger)
    telemetry.enable_flight_recorder(str(path) + ".flight")
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=telemetry)
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=30.0, telemetry=telemetry)
    # Serve one request cleanly so the ring holds serve_step records.
    srv.start()
    client = ServeClient(f"http://127.0.0.1:{srv.port}")
    client.generate([5, 6, 7], 3, tenant="alice")

    def boom(*a, **k):
        raise SystemExit("injected engine death")

    engine.step = boom
    with pytest.raises(RuntimeError, match="engine loop died"):
        srv.submit(Request([1, 2, 3], 4, tenant="alice"))
    # Dead-engine frontend contract: /healthz flips to 503 (load
    # balancers stop routing), new submissions fail FAST instead of
    # parking request_timeout_s, and nothing is booked as served.
    with pytest.raises(Overloaded):
        client.health()
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="engine loop died"):
        srv.submit(Request([1, 2], 2, tenant="bob"))
    assert time.perf_counter() - t0 < 1.0
    assert "bob" not in srv.scheduler.stats()
    srv.shutdown()
    logger.close()

    flight = tmp_path / "serve.jsonl.flight"
    assert flight.exists()
    recs = [json.loads(line) for line in flight.read_text().splitlines()]
    header = recs[0]
    assert header["kind"] == "flight_header"
    assert "SystemExit" in header["reason"]
    kinds = {r.get("kind") for r in recs[1:]}
    assert "serve_step" in kinds and "serve_request" in kinds
    assert "serve_fatal" in kinds       # the ring names its own killer
    # summarize_run auto-ingests the sibling dump into a flight section.
    summary = summarize_run.build_summary(
        _load_all(summarize_run, str(path)))
    worker = next(iter(summary["workers"].values()))
    assert worker["flight"]["records"] >= 3
    assert "SystemExit" in worker["flight"]["reason"]


def test_scheduler_drain_releases_without_counting_service():
    """The fatal-path drain must not inflate admitted/completed — the
    dead-but-listening server's /statz would otherwise report queued
    requests as served."""
    sched = FairScheduler()
    r1, r2 = Request([1], 1), Request([2], 1, tenant="b")
    sched.submit(r1)
    sched.submit(r2)
    drained = sched.drain()
    assert {r.id for r in drained} == {r1.id, r2.id}
    assert sched.depth() == 0
    assert all(s["admitted"] == 0 and s["completed"] == 0
               for s in sched.stats().values())


def test_summarize_tenant_counters_survive_without_requests(tmp_path):
    """A server that died before any request retired leaves serve_step +
    serve_tenant records and NO serve_request records — the counters
    must still reach the report (the crash case they exist for)."""
    path = tmp_path / "serve.jsonl"
    recs = [{"kind": "serve_step", "step": 1, "wall_time": 1.0,
             "active_slots": 1, "admitted": 1, "retired": 0,
             "queue_depth": 2, "kv_pages_in_use": 1,
             "kv_pages_total": 8, "step_ms": 1.0},
            {"kind": "serve_tenant", "step": 1, "wall_time": 1.1,
             "tenant": "search", "queued": 2, "queued_hwm": 4,
             "rejected": 3, "abandoned": 1, "completed": 0,
             "served_tokens": 0}]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    records, _ = summarize_run.load_records(str(path))
    sv = next(iter(summarize_run.build_summary(
        records)["workers"].values()))["serving"]
    assert sv["tenants"]["search"]["rejected"] == 3
    assert sv["tenants"]["search"]["queued_hwm"] == 4
    assert sv["tenants"]["search"]["abandoned"] == 1


def _load_all(summarize_run_mod, path):
    records, _ = summarize_run_mod.load_records(path)
    import os
    if os.path.exists(path + ".flight"):
        fl, _ = summarize_run_mod.load_records(path + ".flight")
        for r in fl:
            r["_flight"] = True
        records.extend(fl)
    return records


# ----------------------------------------------------------- watch_serve


def test_watch_serve_once_json_and_table(slo_server, capsys):
    client = ServeClient(f"http://127.0.0.1:{slo_server.port}")
    client.generate([5, 6, 7, 8], 4, tenant="alice")
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if client.stats().get("slo", {}).get("burning"):
            break
        time.sleep(0.05)
    url = f"http://127.0.0.1:{slo_server.port}"
    assert watch_serve.main(["--url", url, "--once", "--json"]) == 0
    snapshot = json.loads(capsys.readouterr().out)
    assert snapshot["slo"]["burning"] == ["alice:ttft_p95_ms<=0.001"]
    assert "alice" in snapshot["tenants"]
    assert snapshot["tenants"]["alice"]["queued_hwm"] >= 1
    # The human table renders the same snapshot without raising.
    assert watch_serve.main(["--url", url, "--once"]) == 0
    table = capsys.readouterr().out
    assert "BURNING" in table and "alice" in table
    assert "ttft p50/95/99" in table


def test_watch_serve_unreachable_once_fails(capsys):
    assert watch_serve.main(["--url", "http://127.0.0.1:1",
                             "--once", "--json"]) == 1
    captured = capsys.readouterr()
    # stderr, not stdout: --json stdout is a machine-readable stream.
    assert "unreachable" in captured.err
    assert captured.out == ""


# ------------------------------------------------- summarize_run section


def test_summarize_run_check_gates_slo_records(tmp_path):
    """--check accepts complete slo records and flags stripped ones."""
    good = tmp_path / "good.jsonl"
    base = {"kind": "slo", "step": 1, "wall_time": 1.0, "tenant": "t",
            "objective": "ttft_p95_ms<=50", "metric": "ttft_ms",
            "target": 0.95, "budget": 0.05, "good_short": 1,
            "bad_short": 0, "good_long": 1, "bad_long": 0,
            "burn_short": 0.0, "burn_long": 0.0, "burning": False,
            "window_short_s": 60.0, "window_long_s": 600.0}
    serve_step = {"kind": "serve_step", "step": 1, "wall_time": 1.0,
                  "active_slots": 1, "admitted": 1, "retired": 0,
                  "queue_depth": 0, "kv_pages_in_use": 1,
                  "kv_pages_total": 8, "step_ms": 1.0}
    good.write_text(json.dumps(serve_step) + "\n" + json.dumps(base)
                    + "\n")
    assert summarize_run.main([str(good), "--check"]) == 0
    bad = tmp_path / "bad.jsonl"
    stripped = {k: v for k, v in base.items() if k != "burn_long"}
    bad.write_text(json.dumps(serve_step) + "\n" + json.dumps(stripped)
                   + "\n")
    assert summarize_run.main([str(bad), "--check"]) == 1


def test_summarize_run_slo_section_reports_breach(tmp_path):
    path = tmp_path / "serve.jsonl"
    lines = [{"kind": "serve_step", "step": i, "wall_time": float(i),
              "active_slots": 1, "admitted": 0, "retired": 0,
              "queue_depth": 0, "kv_pages_in_use": 1,
              "kv_pages_total": 8, "step_ms": 1.0} for i in (1, 2)]
    lines.append({"kind": "serve_request", "step": 2, "wall_time": 2.0,
                  "tenant": "alice", "status": "ok", "prompt_tokens": 3,
                  "tokens_out": 4, "queue_ms": 1.0, "ttft_ms": 30.0,
                  "tpot_ms": 2.0, "e2e_ms": 40.0, "model_step": 0})
    for burning in (True, False):
        lines.append({"kind": "slo", "step": 2, "wall_time": 2.5,
                      "tenant": "alice", "objective": "ttft_p95_ms<=1",
                      "metric": "ttft_ms", "target": 0.95,
                      "budget": 0.05, "good_short": 0, "bad_short": 1,
                      "good_long": 0, "bad_long": 1, "burn_short": 20.0,
                      "burn_long": 20.0, "burning": burning,
                      "window_short_s": 5.0, "window_long_s": 30.0})
    lines.append({"kind": "serve_tenant", "step": 2, "wall_time": 2.6,
                  "tenant": "alice", "queued": 0, "queued_hwm": 3,
                  "rejected": 2, "abandoned": 1, "completed": 1,
                  "served_tokens": 4})
    path.write_text("".join(json.dumps(r) + "\n" for r in lines))
    records, errors = summarize_run.load_records(str(path))
    assert not errors
    summary = summarize_run.build_summary(records)
    sv = next(iter(summary["workers"].values()))["serving"]
    assert sv["slo"]["evaluations"] == 2
    # Last record (not burning) is the end state, but the mid-run breach
    # is still named.
    assert sv["slo"]["burning"] == []
    assert sv["slo"]["ever_burning"] == ["alice:ttft_p95_ms<=1"]
    tenant = sv["tenants"]["alice"]
    assert tenant["rejected"] == 2 and tenant["abandoned"] == 1
    assert tenant["queued_hwm"] == 3
    assert tenant["ttft_ms"]["p99"] == 30.0
    # The report renders the section (smoke the formatting).
    out = []
    summarize_run.render_report(summary, print_fn=out.append)
    text = "\n".join(out)
    assert "burned during run" in text and "rejected(429)" in text


def test_chunked_prefill_span_carries_chunk_count(model_and_params,
                                                  capture):
    """ISSUE 11: a chunk-prefilled request's ``serve.prefill`` span
    reports how many chunks the prompt took (and the chunk width); the
    whole-bucket path stamps chunks=1 — the stream distinguishes the
    two prefill disciplines post-hoc."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        prefill_chunk=3), telemetry=capture.telemetry)
    long_req = Request(list(range(1, 14)), 4)   # target 12 -> 4 chunks
    engine.admit(long_req)
    drain(engine)
    spans = capture.spans("serve.prefill")
    assert len(spans) == 1
    span = spans[0]
    assert span["request_id"] == long_req.id
    assert span["chunks"] == 4
    assert span["chunk_tokens"] == 3
    assert span["prompt_tokens"] == 13
    assert span["parent_id"] == long_req.span_root

    # Whole-bucket twin on the same capture: chunks == 1.
    engine2 = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=capture.telemetry)
    req2 = Request(list(range(1, 14)), 4)
    engine2.admit(req2)
    drain(engine2)
    spans = [s for s in capture.spans("serve.prefill")
             if s["request_id"] == req2.id]
    assert len(spans) == 1 and spans[0]["chunks"] == 1


# ------------------------------------- the engine's turn, opened (PR 25)


REGIONS = {"serve.turn": None, "serve.schedule": "serve.turn",
           "serve.admit": "serve.turn", "serve.step": "serve.turn",
           "serve.step.stage": "serve.step",
           "serve.step.fetch": "serve.step",
           "serve.step.retire": "serve.step",
           "serve.complete": "serve.turn"}


def test_serve_step_record_splits_the_step_at_the_regions_boundaries(
        model_and_params, capture):
    """One set of stamps feeds the ``serve_step`` record and the
    ``serve.decode_round`` span; a whole-bucket prefill counts.  A call
    lands one step; the first call staged two (the one it landed and the
    one after, ahead), the second one, the last none."""
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=capture.telemetry)
    engine.admit(Request([5, 6, 7, 8, 9], 3, tenant="alice"))
    engine.admit(Request([9, 10], 3, tenant="bob"))
    walls = []
    while engine.active_slots:
        t0 = time.monotonic()
        engine.step()
        walls.append((t0, time.monotonic()))
    steps = [f for kind, _, f in capture.records if kind == "serve_step"]
    rounds = capture.spans("serve.decode_round")
    assert len(steps) == len(walls) == len(rounds) == 3
    assert [(r["steps_serial"], r["steps_ahead"]) for r in steps] == [
        (1, 0), (0, 1), (0, 1)]
    staged_in = [walls[0], walls[0], walls[1]]
    for rec, span, (t0, t1), (s0, s1) in zip(steps, rounds, walls,
                                             staged_in):
        assert s0 <= rec["t_start"] <= s1
        split = [rec[k] for k in ("stage_ms", "fetch_ms", "retire_ms")]
        assert all(ms >= 0 for ms in split) and rec["stage_ms"] > 0
        # The landing's two regions lie in the call that landed the step,
        # its stage in the call that dispatched it.
        assert rec["fetch_ms"] + rec["retire_ms"] \
            <= (t1 - t0) * 1e3 + 0.002              # each rounded to 1 us
        assert rec["stage_ms"] <= (s1 - s0) * 1e3 + 0.002
        assert [span[k] for k in ("stage_ms", "fetch_ms", "retire_ms")] \
            == split
        assert span["dur_ms"] == rec["step_ms"]
    # The serial step's time runs from its stage's start to its tokens on
    # the host, and holds the next step's stage between its own stage and
    # its fetch; a step dispatched ahead runs from the landing before it,
    # so that the rounds tile the clock.
    first = steps[0]
    assert first["stage_ms"] + steps[1]["stage_ms"] + first["fetch_ms"] \
        <= first["step_ms"] + 0.002
    for before, span in zip(rounds, rounds[1:]):
        assert span["t_unix"] == pytest.approx(
            before["t_unix"] + before["dur_ms"] / 1e3, abs=2e-3)
    # Both admissions, whole-bucket prefills, are on the first record only.
    assert [r["prompt_tokens"] for r in steps] == [7, 0, 0]
    assert [r["admitted"] for r in steps] == [2, 0, 0]
    assert steps[0]["prefill_ms"] > 0 and steps[0]["prefill_rows"] == 0
    assert steps[1]["prefill_ms"] == steps[2]["prefill_ms"] == 0
    assert steps[0]["prefill_ms"] == pytest.approx(
        sum(s["dur_ms"] for s in capture.spans("serve.prefill")), abs=0.002)
    # The lanes still hang under their round, whose id was reserved.
    for lane in capture.spans("serve.decode_lane"):
        assert lane["parent_id"] in {r["span_id"] for r in rounds}


@pytest.mark.parametrize("spec_k", [0, 4], ids=["plain", "speculative"])
def test_the_stage_is_cut_at_the_dispatch_by_two_counters(
        model_and_params, capture, monkeypatch, spec_k):
    """Both arms of the step stamp the clock once between the uploads and
    the call: ``upload_ms + dispatch_ms = stage_ms`` on the record and the
    round's span, and the same in whole microseconds on the profiler's
    retire event, with no region of its own under ``serve.step.stage``."""
    from distributed_tensorflow_tpu.utils import profiling
    seen = []
    real = profiling.annotate

    def spy(name, **stats):
        seen.append((name, stats))
        return real(name, **stats)
    monkeypatch.setattr(profiling, "annotate", spy)
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8,
        spec_k=spec_k), telemetry=capture.telemetry)
    engine.admit(Request([5, 6, 7, 8, 9], 4, speculative=bool(spec_k)))
    drain(engine)
    steps = [f for kind, _, f in capture.records if kind == "serve_step"]
    events = [stats for name, stats in seen if name == "serve.step.retire"]
    rounds = capture.spans("serve.decode_round")
    assert len(steps) == len(events) == len(rounds) >= 1
    assert any(r["spec_rows"] for r in steps) == bool(spec_k)
    for rec, event, span in zip(steps, events, rounds):
        assert rec["upload_ms"] > 0 and rec["dispatch_ms"] > 0
        assert rec["upload_ms"] + rec["dispatch_ms"] == pytest.approx(
            rec["stage_ms"], abs=1e-9)
        assert (event["upload_us"], event["dispatch_us"]) == (
            round(rec["upload_ms"] * 1e3), round(rec["dispatch_ms"] * 1e3))
        assert (span["upload_ms"], span["dispatch_ms"]) == (
            rec["upload_ms"], rec["dispatch_ms"])
    assert {name for name, _ in seen if name.startswith("serve.step")} == {
        "serve.step", "serve.step.stage", "serve.step.fetch",
        "serve.step.retire"}


def test_turn_regions_reach_the_stream_nested_under_the_turn(
        model_and_params, capture):
    model, params = model_and_params
    engine = DecodeEngine(model, params, EngineConfig(
        num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
        telemetry=capture.telemetry)
    srv = ServingServer(engine, FairScheduler(), port=0,
                        request_timeout_s=60.0,
                        telemetry=capture.telemetry)
    srv.start()
    try:
        out = ServeClient(f"http://127.0.0.1:{srv.port}").generate(
            [5, 6, 7, 8], 4, tenant="alice")
        assert out["tokens_out"] == 4
    finally:
        srv.shutdown()
    regions = [s for s in capture.spans() if s.get("source") == "annotate"]
    by_id = {s["span_id"]: s for s in regions}
    assert {s["name"] for s in regions} == set(REGIONS)
    assert not any(s["name"].startswith("perfbench.")
                   for s in capture.spans())
    for s in regions:
        assert s["thread"] == "serve-engine"
        parent = REGIONS[s["name"]]
        if parent is None:
            assert s["parent_id"] == 0
        else:
            assert by_id[s["parent_id"]]["name"] == parent
            outer = by_id[s["parent_id"]]
            assert outer["t_unix"] <= s["t_unix"] + 1e-3
            assert s["dur_ms"] <= outer["dur_ms"] + 1.0
    count = lambda name: sum(s["name"] == name for s in regions)  # noqa: E731
    # Four tokens, four turns, each with its step and the step's three
    # regions; one admission; one completion.
    assert count("serve.turn") == count("serve.step") == 4
    assert count("serve.step.stage") == count("serve.step.fetch") \
        == count("serve.step.retire") == 4
    assert count("serve.admit") == count("serve.complete") == 1
    assert count("serve.schedule") >= 4
    # The after-the-fact spans keep their explicit parents: the round is
    # still an engine root, the request's spans still hang under its root.
    assert all(s["parent_id"] == 0
               for s in capture.spans("serve.decode_round"))
    root = capture.spans("serve.request")[0]
    for name in ("serve.queue", "serve.reserve", "serve.prefill"):
        assert capture.spans(name)[0]["parent_id"] == root["span_id"]


def test_no_tracer_and_no_profiler_emit_nothing(model_and_params):
    """The regions cost an inactive annotation each: no span record with
    telemetry on and no tracer installed, and the engine serves the same
    with no telemetry at all."""
    model, params = model_and_params
    tracing.clear()
    logger = MetricsLogger(None)
    telemetry = Telemetry(logger)
    kinds = []
    orig = telemetry.emit
    telemetry.emit = lambda kind, step=0, **f: (kinds.append(kind),
                                                orig(kind, step=step, **f))
    tokens = []
    for tel in (telemetry, None):
        engine = DecodeEngine(model, params, EngineConfig(
            num_slots=2, page_size=4, num_pages=32, max_pages_per_seq=8),
            telemetry=tel)
        sched = FairScheduler()
        req = Request([5, 6, 7], 5, tenant="alice")
        sched.submit(req)
        drain(engine, sched)
        tokens.append(req.tokens)
    logger.close()
    assert tokens[0] == tokens[1] and len(tokens[0]) == 5
    assert "span" not in kinds
    assert kinds.count("serve_step") == 5
