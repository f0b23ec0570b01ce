"""The span reducer (``perfbench/spans.py``): the sharing-out of device idle
time among the program's own spans, on hand-made intervals, on a serving
trace recorded on the chip in PR 25 with
``perfbench/tools/record_span_trace.py`` (eleven turns of a tiny engine, a
20 ms pause with the engine thread idle), and on the trace a traced CPU
rehearsal of the chat cell leaves behind."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import spans, spec, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP_TRACE = os.path.join(DATA, "spans", "tiny_serve_tpu_1.xplane.pb")
REGIONS = ["serve.turn", "serve.schedule", "serve.admit", "serve.step",
           "serve.step.stage", "serve.step.fetch", "serve.step.retire",
           "serve.complete"]


# ---------------------------------------------------------- by hand


TURN = [(0, 100, "serve.turn"), (10, 20, "serve.schedule"),
        (30, 90, "serve.step"), (30, 50, "serve.step.stage"),
        (50, 80, "serve.step.fetch"), (80, 90, "serve.step.retire")]


def test_nested_spans_are_cut_into_pieces_named_by_the_innermost():
    assert spans.innermost(TURN) == [
        (0, 10, "serve.turn"), (10, 20, "serve.schedule"),
        (20, 30, "serve.turn"), (30, 50, "serve.step.stage"),
        (50, 80, "serve.step.fetch"), (80, 90, "serve.step.retire"),
        (90, 100, "serve.turn")]
    # A second turn after a pause: nothing covers the pause.  A region's
    # own time shows between its children; a child that runs past its
    # parent (two clock reads apart) is cut at the parent's end.
    later = [(200, 260, "serve.turn"), (210, 250, "serve.step"),
             (215, 225, "serve.step.stage"), (240, 255, "serve.step.fetch")]
    assert spans.innermost(TURN + later)[7:] == [
        (200, 210, "serve.turn"), (210, 215, "serve.step"),
        (215, 225, "serve.step.stage"), (225, 240, "serve.step"),
        (240, 250, "serve.step.fetch"), (250, 260, "serve.turn")]
    assert spans.innermost([]) == []


@pytest.mark.parametrize("holes,want", [
    # One hole over the end of one region and the start of the next is
    # split between them by overlap; a midpoint would give it all to one.
    ([(45, 60)], {"serve.step.stage": 5, "serve.step.fetch": 10}),
    # Nested spans: the step's regions take theirs, the turn what is left.
    ([(25, 95)], {"serve.turn": 10, "serve.step.stage": 20,
                  "serve.step.fetch": 30, "serve.step.retire": 10}),
    # Outside every turn, and half out.
    ([(120, 150)], {spans.OUTSIDE: 30}),
    ([(95, 110)], {"serve.turn": 5, spans.OUTSIDE: 10}),
    ([], {}),
], ids=["two_spans", "nested", "outside", "half_out", "no_holes"])
def test_a_holes_time_goes_to_the_innermost_span_by_overlap(holes, want):
    assert spans.apportion(holes, spans.innermost(TURN)) == want


def test_holes_are_the_gaps_in_the_union_of_operation_intervals():
    ops = [(0, 10), (5, 12), (20, 30), (30, 31), (40, 41)]
    assert spans.holes_of(ops) == [(12, 20), (31, 40)]
    assert spans.holes_of([]) == spans.holes_of([(3, 9)]) == []


# ----------------------------------------------- a trace from the chip


def test_a_trace_without_the_programs_spans_reduces_to_none():
    # PR 24's training trace: device operations and ``perfbench.*``
    # annotations, no ``serve.turn``.  So reads the parent of the PR that
    # placed the spans, and so reads every training cell.
    assert spans.reduce(os.path.join(DATA, "tiny_tpu_1.xplane.pb")) is None


def test_reducer_on_a_recorded_chip_trace():
    red = spans.reduce(CHIP_TRACE)
    then = json.load(open(CHIP_TRACE + ".json"))
    assert os.path.getsize(CHIP_TRACE) < 200_000
    assert red["found"] == then["spans"]["found"]
    assert red["found"]["devices"] == 1
    assert red["found"]["line"].startswith("/host:CPU")
    # The readings taken on the chip when it was recorded still hold.
    assert red["idle_s"] == pytest.approx(then["spans"]["idle_s"])
    assert red["ops_span_s"] == pytest.approx(then["xplane"]["span_s"])
    for name, was in then["spans"]["spans"].items():
        assert red["spans"][name] == pytest.approx(was)
    # Every region of the program is there, once a turn where it must be.
    assert set(red["spans"]) == set(REGIONS)
    turns = red["spans"]["serve.turn"]["count"]
    assert 8 <= turns <= 16
    for name in REGIONS[3:7]:
        assert red["spans"][name]["count"] == turns
    assert red["spans"]["serve.admit"]["count"] == 4
    assert red["spans"]["serve.schedule"]["count"] >= turns
    # The parts are the holes, all of them and nothing else: what
    # ``xplane.reduce`` calls the span less the busy time of the same file.
    whole = xplane.reduce(CHIP_TRACE)
    assert sum(red["idle_s"].values()) == pytest.approx(
        whole["span_s"] - whole["busy_s"], rel=1e-9)
    # The 20 ms pause is under no turn; a tiny engine waits on its host.
    assert 0.015 < red["idle_s"][spans.OUTSIDE] < 0.03
    assert red["idle_s"]["serve.step.stage"] > red["idle_s"][
        "serve.step.retire"]


def test_the_parts_of_a_run_add_up_to_its_idle_share(tmp_path, monkeypatch):
    then = json.load(open(CHIP_TRACE + ".json"))
    cell_dir = tmp_path / "trace" / "a_cell" / "plugins" / "profile" / "t"
    cell_dir.mkdir(parents=True)
    shutil.copy(CHIP_TRACE, cell_dir / "vm.xplane.pb")
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    trace = {"busy_s": then["xplane"]["busy_s"],
             "window_s": then["window_s"]}
    ctx = {"cell": "a_cell", "trace": trace}
    parts = {p: spans.idle_pct(ctx, p) for p in spans.PARTS}
    assert all(v is not None and v >= 0 for v in parts.values())
    red = spans.of_run(ctx)
    rest = sum(red["idle_s"][k] for k in (spans.OUTSIDE, "serve.step"))
    idle_pct = 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    assert sum(parts.values()) + 100.0 * (rest + red["edges_s"]) / trace[
        "window_s"] == pytest.approx(idle_pct)
    assert 0 <= red["edges_s"] < 0.01
    # Nothing to read is None, never 0: a run that traced no device (the
    # CPU rehearsal), a cell with no trace, a program with no spans.
    assert spans.idle_pct({"cell": "a_cell", "trace": None}, "stage") is None
    assert spans.idle_pct({"cell": "a_cell", "trace": {"found": {}}},
                          "stage") is None
    assert spans.idle_pct({"cell": "no_such_cell", "trace": trace},
                          "stage") is None
    shutil.copy(os.path.join(DATA, "tiny_tpu_1.xplane.pb"),
                cell_dir / "vm.xplane.pb")
    os.utime(cell_dir / "vm.xplane.pb")
    spans.reduce.cache_clear()
    assert spans.idle_pct(ctx, "stage") is None


def test_exposed_collective_time_is_what_compute_does_not_hide():
    read = spec.load_module(os.path.join(
        spec.HERE, "metrics", "train_collective_exposed_pct.py")).read
    trace = {"busy_s": 2.0, "collective_s": 0.5, "collective_hidden_s": 0.1}
    assert read({"trace": trace}) == pytest.approx(20.0)
    assert read({"trace": dict(trace, collective_s=0.0)}) is None
    assert read({"trace": None}) is None
    then = json.load(open(os.path.join(DATA, "tiny_tpu_4.xplane.pb.json")))
    assert 0 < read({"trace": then}) <= 100.0 * then["collective_s"] / then[
        "busy_s"]


# ------------------------------------- the program's spans, on the CPU


def shadow_checkout(root):
    """A checkout made of links, with an ``out`` directory of its own: the
    traced rehearsal of the chat cell in ``test_perfbench_run.py`` may run
    beside this one, and each empties the cell's trace directory."""
    (root / "perfbench" / "out").mkdir(parents=True)
    for name in os.listdir(spec.HERE):
        if name != "out":
            os.symlink(os.path.join(spec.HERE, name),
                       root / "perfbench" / name)
    for name in ("BENCHMARK.json", "distributed_tensorflow_tpu"):
        os.symlink(os.path.join(spec.ROOT, name), root / name)
    return root


def inside(inner, outer):
    return any(a <= inner[0] and inner[1] <= b for a, b, _ in outer)


def test_a_traced_rehearsal_leaves_every_region_nested_as_placed(tmp_path):
    root = shadow_checkout(tmp_path / "checkout")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, str(root / "perfbench" / "run.py"), "--workload",
         "serve_mistral7b_chat", "--seed", str(2 ** 31 + 99), "--seconds",
         "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, env=env, cwd=root)
    assert proc.returncode == spec.REHEARSAL_EXIT, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    # No device plane on the CPU: the new metrics are left out, not 0.
    assert not any("_idle_" in k for k in line["metrics"])
    path = xplane.newest_xplane(str(
        root / "perfbench" / "out" / "trace" / "serve_mistral7b_chat"))
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    _, events = spans.program_line(planes)
    by = {}
    for ev in events:
        by.setdefault(ev[2], []).append(ev)
    assert set(by) == set(REGIONS)
    assert not any(name.startswith("perfbench.") for name in by)
    for region in ("serve.step.stage", "serve.step.fetch",
                   "serve.step.retire"):
        assert len(by[region]) == len(by["serve.step"]) > 10
        assert all(inside(ev, by["serve.step"]) for ev in by[region])
    # The profiler starts and stops inside the harness's wrapper of
    # ``engine.step``, in the middle of a turn: the first turn's start and
    # the last turn's end are not in the trace, and what those two turns
    # did inside the slice stands alone.
    first = min(ev[0] for ev in by["serve.turn"])
    last = max(ev[1] for ev in by["serve.turn"])
    for region in ("serve.schedule", "serve.admit", "serve.step",
                   "serve.complete"):
        assert all(inside(ev, by["serve.turn"]) for ev in by[region]
                   if first < ev[0] < last)
    assert sum(not first < ev[0] < last for ev in by["serve.step"]) <= 1
    # The harness's wrappers are on the same line, around the program's.
    harness = {}
    for p in planes:
        for ln in p.lines:
            for ev in ln.events:
                if ev.name.startswith("perfbench."):
                    harness.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    assert all(inside(ev, harness["perfbench.decode_step"])
               for ev in by["serve.step"])
    assert all(inside(ev, harness["perfbench.prefill"])
               for ev in by["serve.admit"])
