"""Distributed tracing + crash flight recorder (ISSUE 4): span nesting and
cross-process trace-id stability, the Chrome trace export over multiple
worker streams with clock alignment, host-annotation spans, and the
flight ring's dump paths (chaos kill hook, shutdown signal)."""

import json
import os
import signal
import time

import pytest

from distributed_tensorflow_tpu.tools import export_trace
from distributed_tensorflow_tpu.training.preemption import ShutdownSignal
from distributed_tensorflow_tpu.utils import faults, profiling, tracing
from distributed_tensorflow_tpu.utils.faults import FaultInjector
from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
from distributed_tensorflow_tpu.utils.telemetry import Telemetry


@pytest.fixture(autouse=True)
def clear_tracer():
    yield
    tracing.clear()
    faults.clear()


def read_records(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def make_bus(tmp_path, name="stream.jsonl", worker=0):
    path = tmp_path / name
    logger = MetricsLogger(path, static_fields={"worker": worker})
    return str(path), logger, Telemetry(logger)


# ------------------------------------------------------------ span API


def test_span_nesting_records_parent_ids(tmp_path):
    path, logger, telemetry = make_bus(tmp_path)
    tracer = tracing.Tracer(telemetry, run_id="runA")
    tracer.set_step(3)
    with tracer.span("outer"):
        with tracer.span("inner"):
            time.sleep(0.002)
    logger.close()
    spans = {r["name"]: r for r in read_records(path)
             if r.get("kind") == "span"}
    assert set(spans) == {"outer", "inner"}
    assert spans["outer"]["parent_id"] == 0
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["inner"]["dur_ms"] >= 2.0
    # The outer span covers the inner one on the shared timeline.
    assert spans["outer"]["t_unix"] <= spans["inner"]["t_unix"]
    assert spans["outer"]["dur_ms"] >= spans["inner"]["dur_ms"]
    for rec in spans.values():
        assert rec["step"] == 3
        assert rec["trace_id"] == "runA/3"
        assert rec["thread"] == "MainThread"


def test_trace_id_stable_across_processes():
    """Two tracers (two would-be processes) sharing run id + step produce
    the SAME trace id — the cross-worker correlation key."""
    a = tracing.Tracer(Telemetry(), run_id="job1")
    b = tracing.Tracer(Telemetry(), run_id="job1")
    a.set_step(17)
    b.set_step(17)
    assert a.trace_id() == b.trace_id() == "job1/17"
    b.set_step(18)
    assert a.trace_id() != b.trace_id()


def test_module_level_span_is_noop_without_tracer():
    tracing.clear()
    with tracing.span("nothing"):
        pass
    tracing.emit_span("nothing", time.time(), 1.0)  # must not raise


def test_emit_span_after_the_fact_adopts_thread_stack(tmp_path):
    path, logger, telemetry = make_bus(tmp_path)
    tracer = tracing.install(tracing.Tracer(telemetry, run_id="r"))
    with tracer.span("parent"):
        tracing.emit_span("child", time.time(), 1.5)
    logger.close()
    spans = {r["name"]: r for r in read_records(path)
             if r.get("kind") == "span"}
    assert spans["child"]["parent_id"] == spans["parent"]["span_id"]


def test_annotate_emits_nested_spans_and_the_plain_timer_none(tmp_path):
    path, logger, telemetry = make_bus(tmp_path)
    tracing.install(tracing.Tracer(telemetry, run_id="r"))
    with profiling.annotate("host_region"):
        with profiling.annotate("inner_region"):
            time.sleep(0.001)
        # An after-the-fact span that names no parent adopts the open one.
        tracing.emit_span("after_the_fact", time.time(), 1.0)
    with profiling.Timer() as t:    # times, and emits nothing
        time.sleep(0.001)
    logger.close()
    assert t.elapsed > 0
    spans = {r["name"]: r for r in read_records(path)
             if r.get("kind") == "span"}
    assert set(spans) == {"host_region", "inner_region", "after_the_fact"}
    outer, inner = spans["host_region"], spans["inner_region"]
    assert outer["source"] == inner["source"] == "annotate"
    assert outer["parent_id"] == 0
    assert inner["parent_id"] == outer["span_id"]
    assert spans["after_the_fact"]["parent_id"] == outer["span_id"]
    assert outer["dur_ms"] >= inner["dur_ms"] > 0
    assert outer["t_unix"] <= inner["t_unix"]


def test_annotate_without_tracer_still_works():
    tracing.clear()
    with profiling.annotate("plain"):
        pass  # jax annotation alone; no telemetry involved


# ------------------------------------------------------- trace export


def _write_worker_stream(tmp_path, worker, offset_ms, t0, run_id="job"):
    """A synthetic per-worker stream: one clock_sync + spans for steps
    1..3, with this worker's LOCAL clock shifted by -offset_ms (so after
    the exporter adds offset_ms back, all workers align)."""
    path = tmp_path / f"telemetry.jsonl.task{worker}"
    logger = MetricsLogger(path, static_fields={"worker": worker})
    telemetry = Telemetry(logger)
    telemetry.emit("clock_sync", step=0, offset_ms=offset_ms, rtt_ms=0.5,
                   t_unix=t0 - offset_ms / 1000.0, source="coord_time")
    tracer = tracing.Tracer(telemetry, run_id=run_id)
    for step in (1, 2, 3):
        start = t0 + step * 0.1 - offset_ms / 1000.0
        tracer.emit_span("step", start, 80.0, step=step)
        tracer.emit_span("data_wait", start, 20.0, step=step)
    # Stream-resident recovery records carry NO t_unix (only the logger's
    # wall_time) — the exporter must place them via the clock_sync anchor.
    telemetry.emit("recovery", step=2, action="peer_eviction", task=1)
    logger.close()
    return str(path)


def test_export_merges_two_workers_into_valid_chrome_trace(tmp_path,
                                                           capsys):
    t0 = 1_700_000_000.0
    f0 = _write_worker_stream(tmp_path, 0, offset_ms=0.0, t0=t0)
    f1 = _write_worker_stream(tmp_path, 1, offset_ms=750.0, t0=t0)
    out = str(tmp_path / "trace.json")
    assert export_trace.main([f0, f1, "--output", out]) == 0
    trace = json.load(open(out))
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    spans = [e for e in events if e.get("ph") == "X"]
    # Distinct per-worker rows, correct counts.
    assert {e["pid"] for e in spans} == {0, 1}
    assert len(spans) == 12  # 2 workers x 3 steps x 2 spans
    names = {e["name"] for e in spans}
    assert names == {"step", "data_wait"}
    # Metadata rows name the workers.
    meta = {(e["pid"], e["name"]): e for e in events if e["ph"] == "M"}
    assert "worker0" in meta[(0, "process_name")]["args"]["name"]
    assert "worker1" in meta[(1, "process_name")]["args"]["name"]
    # Clock alignment: worker1's local stamps lag by 750 ms, but after the
    # exporter applies its recorded offset the same step's spans coincide.
    for step in (1, 2, 3):
        ts = {e["pid"]: e["ts"] for e in spans
              if e["name"] == "step" and e["args"]["step"] == step}
        assert abs(ts[0] - ts[1]) < 1000  # < 1 ms in trace microseconds
    # Cross-worker correlation: same step -> same trace_id on both rows.
    ids = {e["args"]["trace_id"] for e in spans
           if e["args"]["step"] == 2}
    assert ids == {"job/2"}
    # Recovery records ride along as instant events.
    assert any(e.get("ph") == "i" and "peer_eviction" in e["name"]
               for e in events)


def test_export_fails_loudly_on_spanless_stream(tmp_path):
    path = tmp_path / "bare.jsonl"
    path.write_text('{"step": 1, "wall_time": 0.1, "loss": 1.0}\n')
    out = str(tmp_path / "trace.json")
    assert export_trace.main([str(path), "--output", out]) == 1
    assert export_trace.main(
        [str(path), "--output", out, "--allow-empty"]) == 0
    events = json.load(open(out))["traceEvents"]
    assert not [e for e in events if e.get("ph") == "X"]


def test_multi_incarnation_stream_uses_per_incarnation_clocks(tmp_path):
    """A crash-restarted worker APPENDS to its stream: two incarnations,
    each with its own clock_sync and a wall_time clock reset to zero.
    Every record must map onto the epoch via ITS incarnation's anchor —
    using the newest anchor for all of them misplaces incarnation-1
    events by the inter-incarnation gap."""
    from distributed_tensorflow_tpu.tools import summarize_run

    def rec(**kw):
        return json.dumps(kw)

    step_fields = dict(loss=1.0, steps_per_sec=2.0, data_wait_ms=1.0,
                       compute_ms=2.0, mfu=None, hbm_bytes_in_use=1,
                       hbm_peak_bytes=1)
    w0 = tmp_path / "t.jsonl.task0"
    w0.write_text("\n".join([
        # Incarnation 1: anchored at epoch 1000, dies after step 5.
        rec(step=0, wall_time=0.0, worker=0, kind="clock_sync",
            offset_ms=0.0, rtt_ms=0.1, t_unix=1000.0),
        rec(step=0, wall_time=0.05, worker=0, kind="recovery",
            action="inc1_marker"),
        rec(step=5, wall_time=1.0, worker=0, kind="train_step",
            **step_fields),
        # Incarnation 2 (restart 100 s later): wall_time clock reset.
        rec(step=0, wall_time=0.0, worker=0, kind="clock_sync",
            offset_ms=0.0, rtt_ms=0.1, t_unix=1100.0),
        rec(step=5, wall_time=2.0, worker=0, kind="train_step",
            **step_fields),
    ]) + "\n")
    w1 = tmp_path / "t.jsonl.task1"
    w1.write_text("\n".join([
        rec(step=0, wall_time=0.0, worker=1, kind="clock_sync",
            offset_ms=0.0, rtt_ms=0.1, t_unix=1000.0),
        rec(step=5, wall_time=3.0, worker=1, kind="train_step",
            **step_fields),
    ]) + "\n")

    records = []
    for path in (w0, w1):
        recs, errs = summarize_run.load_records(str(path))
        assert not errs
        records.extend(recs)
    cw = summarize_run.build_summary(records)["cross_worker"]
    # worker0 first reached step 5 at epoch 1001 (incarnation 1), worker1
    # at 1003 -> skew 2 s.  The buggy last-anchor-for-everything mapping
    # would place worker0's hit at 1101 and report ~98 s.
    assert cw["skew_at_step"] == 5
    assert abs(cw["aligned_step_skew_s"] - 2.0) < 0.01, cw

    # The exporter places incarnation-1's instant marker via its own
    # anchor too: 0.05 s after incarnation-1's start, not 100 s later.
    out = str(tmp_path / "trace.json")
    assert export_trace.main([str(w0), str(w1), "--output", out,
                              "--allow-empty"]) == 0
    events = json.load(open(out))["traceEvents"]
    marker = next(e for e in events if e.get("ph") == "i"
                  and "inc1_marker" in e["name"])
    # No spans in this stream, so ts is absolute epoch microseconds: the
    # marker sits at 1000.05, not shifted to ~1100.05 by the newest
    # incarnation's anchor.
    assert abs(marker["ts"] - 1000.05 * 1e6) < 1e4, marker


# ----------------------------------------------------- flight recorder


def test_flight_ring_is_bounded_and_dump_is_parseable(tmp_path):
    path, logger, telemetry = make_bus(tmp_path)
    telemetry.enable_flight_recorder(path + ".flight")
    for step in range(400):
        telemetry.emit("train_step", step=step, loss=float(step))
    out = telemetry.dump_flight(reason="unit")
    assert out == path + ".flight"
    records = read_records(out)
    header, body = records[0], records[1:]
    assert header["kind"] == "flight_header"
    assert header["reason"] == "unit"
    assert header["worker"] == 0  # stream statics stamped into the dump
    assert len(body) == 256  # constant-memory ring, oldest dropped
    assert body[0]["step"] == 400 - 256
    assert body[-1]["step"] == 399
    logger.close()


def test_dump_preserves_span_start_times(tmp_path):
    """A span record's t_unix is its START — the dump must keep it, not
    overwrite it with the (later) ring emit time, or every span in the
    crash timeline shifts late by its own duration."""
    path, logger, telemetry = make_bus(tmp_path)
    telemetry.enable_flight_recorder(path + ".flight")
    tracer = tracing.Tracer(telemetry, run_id="r")
    start = time.time() - 2.0  # a 2 s region that just finished
    tracer.emit_span("checkpoint_save", start, 2000.0, step=4)
    telemetry.dump_flight(reason="x")
    records = read_records(path + ".flight")
    span = next(r for r in records if r.get("kind") == "span")
    assert abs(span["t_unix"] - start) < 1e-3
    logger.close()


def test_dump_flight_without_arming_is_noop(tmp_path):
    telemetry = Telemetry()
    telemetry.emit("train_step", step=1, loss=1.0)
    assert telemetry.dump_flight(reason="x") is None


def test_kill_at_step_dumps_flight_before_sigkill(tmp_path, monkeypatch):
    path, logger, telemetry = make_bus(tmp_path)
    telemetry.enable_flight_recorder(path + ".flight")
    injector = faults.install(FaultInjector(kill_at_step=12))
    injector.attach_telemetry(telemetry)
    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append(sig))
    for step in range(1, 13):
        telemetry.emit("train_step", step=step, loss=1.0 / step)
        faults.on_step(step)
    assert kills == [signal.SIGKILL]
    records = read_records(path + ".flight")
    assert records[0]["reason"] == "kill_at_step=12"
    # The ring's last record is from the step the worker died on.
    steps = [r["step"] for r in records[1:]
             if r.get("kind") == "train_step"]
    assert steps[-1] == 12
    logger.close()


def test_shutdown_signal_runs_flight_callback_once(tmp_path):
    path, logger, telemetry = make_bus(tmp_path)
    telemetry.enable_flight_recorder(path + ".flight")
    telemetry.emit("train_step", step=7, loss=0.5)
    shutdown = ShutdownSignal()
    calls = []
    shutdown.add_callback(lambda: calls.append(
        telemetry.dump_flight(reason=f"signal:{shutdown.signal_name}")))
    shutdown.trigger()
    shutdown.trigger()  # idempotent: one latch, one dump
    assert calls == [path + ".flight"]
    records = read_records(path + ".flight")
    assert records[0]["reason"] == "signal:trigger"
    assert records[-1]["step"] == 7
    logger.close()


def test_shutdown_callback_exception_is_swallowed():
    shutdown = ShutdownSignal()
    shutdown.add_callback(lambda: 1 / 0)
    shutdown.trigger()  # must not raise
    assert shutdown.requested()


# ------------------------------------------- summarize_run ingestion


def test_summarize_run_ingests_flight_dump(tmp_path, capsys):
    from distributed_tensorflow_tpu.tools import summarize_run

    path, logger, telemetry = make_bus(tmp_path)
    telemetry.enable_flight_recorder(path + ".flight")
    tracer = tracing.Tracer(telemetry, run_id="r")
    for step in range(1, 6):
        telemetry.emit(
            "train_step", step=step, loss=1.0, steps_per_sec=2.0,
            data_wait_ms=1.0, compute_ms=2.0, mfu=None,
            hbm_bytes_in_use=1, hbm_peak_bytes=1)
        tracer.emit_span("step", time.time(), 3.0, step=step)
    telemetry.dump_flight(reason="kill_at_step=5")
    logger.close()

    # --check passes: the flight dump must never fail stream validation.
    assert summarize_run.main([path, "--check"]) == 0
    out = str(tmp_path / "summary.json")
    # Passing the dump explicitly AND having it auto-discovered must not
    # ingest it twice.
    assert summarize_run.main([path, path + ".flight",
                               "--json", out]) == 0
    summary = json.load(open(out))["extra"]
    worker = summary["workers"]["worker0"]
    flight = worker["flight"]
    assert flight["reason"] == "kill_at_step=5"
    assert flight["last_step"] == 5
    assert flight["records"] == 10  # 5 train_step + 5 spans, once each
    # The dump's records are COPIES of stream records: aggregates must
    # not double-count them.
    assert worker["step_records"] == 5
    rendered = capsys.readouterr().out
    assert "flight recorder" in rendered
