"""The region reducer (``perfbench/regions.py``): device time by program and
by the program's own regions, on hand-made intervals and names, on traces
built message by message with TensorFlow's ``xplane_pb2`` and ``hlo_pb2``
(which check the reader's wire format; the harness itself never imports
them), on a serving trace recorded on the chip with
``perfbench/tools/record_region_trace.py`` (a tiny engine whose two layers
run three times inside a ``while``), and on the older recordings, which
hold no name: ``found`` is false there and every reader gives nothing."""

import gzip
import json
import os
import shutil

import pytest

from perfbench import regions, spec, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: Kept gzipped (727,564 bytes as recorded: the three modules' HLO are five
#: sevenths of it); ``chip_trace`` unpacks it for a test.
RECORDED = os.path.join(DATA, "regions", "tiny_looped_tpu_1.xplane.pb")
NAMELESS = os.path.join(DATA, "spans", "tiny_serve_tpu_1.xplane.pb")
BENCH = spec.benchmark()
#: (The looped cell's three are left to a ``benchmark`` PR: its own test
#: file pins the cell's per-layer list, PERF.md §7.)
SERVING = ("chat", "lp", "ohlp", "glm")
PARTS = ("attn", "matmul", "unnamed")
NEW = [m for m in BENCH["per_layer"] if m["name"] in (
    "train_head_loss_pct", "train_optimizer_pct", "train_unnamed_pct",
    "lp_prefill_attn_pct", "ohlp_prefill_scan_pct",
    "chat_stage_upload_ms", "chat_stage_dispatch_ms",
    *(f"{c}_decode_{p}_ms" for c in SERVING for p in PARTS))]


# ---------------------------------------------------------- by hand


def test_the_vocabulary_is_the_programs():
    from distributed_tensorflow_tpu.utils import profiling
    assert regions.vocabulary() == frozenset(profiling.REGIONS)
    assert len(profiling.REGIONS) == len(set(profiling.REGIONS))
    assert regions.UNNAMED not in profiling.REGIONS
    assert regions.COLLECTIVE not in profiling.REGIONS
    assert set(regions.ATTENTION) <= set(profiling.REGIONS)


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/GptLM.decode_paged/layer0.decode_step_paged/layer0._qkv/"
     "attn.qkv/q_proj/dot_general", "attn.qkv"),
    # regions nest and the innermost wins
    ("jit(step)/GptLM.decode_paged/mla.absorb/cache.gather/jit(_take)/gather",
     "cache.gather"),
    ("jit(step)/loop.step/layer1._mlp/mlp/moe.experts/gmm", "moe.experts"),
    # the backward pass wears the forward's name inside JAX's wrappers
    ("jit(train_step)/transpose(jvp(GptLM))/layer3/transpose(jvp(mlp))/"
     "mlp_in/dot_general", "mlp"),
    ("jit(f)/vmap(jvp(loss))/reduce_max", "loss"),
    # Flax's own names are no regions, nor is a name that only holds one
    ("jit(step)/GptLM.decode_paged/layer0._attend_rows/dot_general", None),
    ("jit(step)/head_of_state/mlp_in/add", None),
    ("reduce_window_sum", None),
])
def test_region_of_an_op_name(op_name, want):
    assert regions.region_of(op_name, regions.vocabulary()) == want


def test_self_time_counts_a_rolled_loop_once():
    # a while of 100 with two iterations of a body of 2 x 20 inside it, a
    # fusion beside it, and one operation that runs past its neighbour
    events = [(0, 100, "while"), (5, 25, "a"), (25, 45, "b"),
              (50, 70, "a"), (70, 90, "b"), (110, 130, "f"),
              (125, 140, "g")]
    timed, overlap = regions.self_times(events)
    own: dict = {}
    for _, key, t in timed:
        own[key] = own.get(key, 0) + t
    assert own == {"while": 20, "a": 40, "b": 40, "f": 15, "g": 15}
    assert overlap == 5
    # the self times sum to the union of the intervals
    assert sum(own.values()) == sum(b - a for a, b in xplane._union(
        [(a, b) for a, b, _ in events]))


def test_self_time_of_an_overlap_inside_a_nest_sums_to_the_union():
    events = [(0, 100, "p"), (10, 40, "a"), (30, 60, "b"), (35, 38, "c")]
    timed, overlap = regions.self_times(events)
    assert {key: t for _, key, t in timed} == {"p": 50, "a": 20, "b": 27,
                                               "c": 3}
    assert overlap == 10


@pytest.mark.parametrize("inner,want", [
    (["a/attn.qkv/mul", "a/attn.qkv/add", "a/mlp/dot_general"], "attn.qkv"),
    # of two regions that tie, the one nearest the root (the last)
    (["a/mlp/mul", "", "a/head/dot_general"], "head"),
    (["a/head/mul", "a/mlp/add", "no_region_here"], "mlp"),
    (["", "reduce_window_sum"], None),
    ([], None),
])
def test_a_fusion_takes_the_region_most_of_its_instructions_carry(inner,
                                                                  want):
    assert regions.fused_region(inner, regions.vocabulary()) == want


@pytest.mark.parametrize("line,want", [
    ("%copy.240 = bf16[2048,3,16,128]{3,2,1,0:T(8,128)(2,1)} copy("
     "bf16[2048,3,16,128]{3,1,2,0:T(8,128)(2,1)} %bitcast.7)",
     "copy bf16[2048,3,16,128]"),
    ("%slice-done.10 = bf16[8,128,4096]{2,1,0:T(8,128)(2,1)S(1)} async-done("
     "((bf16[32,128,4096]{2,1,0}), bf16[8,128,4096]{2,1,0}) %slice-start.10)",
     "async-done bf16[8,128,4096]"),
    ("fusion.12", "fusion"),
])
def test_head_of_an_operation(line, want):
    assert regions.head(line) == want


@pytest.mark.parametrize("line,want", [
    ("%fusion.4 = bf16[1856,16,1024]{2,1,0} fusion(bf16[1857,16,1024] %p), "
     "kind=kLoop", "fusion"),
    ("%copy-done.1 = bf16[4096,14336]{1,0} copy-done((bf16[4096,14336]) "
     "%copy-start.1)", "copy-done"),
    ("%while.6 = (s32[], bf16[8,1,2048]) while((s32[], bf16[8,1,2048]) "
     "%tuple.9), condition=%c, body=%b", "while"),
    ("copy.166", "copy"),
])
def test_opcode_of_an_operation(line, want):
    assert regions.opcode(line) == want




# ------------------------------------- built with TensorFlow's messages

STEP, PREFILL = ("jit_step(111)", 111), ("jit_prefill(222)", 222)
#: (module, instruction): (HLO line, op_name, the op_names of the
#: instructions it fuses).  Two programs whose operations are named alike
#: (``%fusion.1`` in both, under another region in each), the step's
#: layers inside a ``while``, a fusion the compiler named nothing whose
#: instructions are the projections', one relayout copy, one all-reduce.
OPS = {
    (STEP, "while.6"): ("%while.6 = (s32[]) while((s32[]) %t)",
                        "jit(step)/loop.step/while", None),
    (STEP, "fusion.1"): ("%fusion.1 = bf16[8,64] fusion(bf16[8,64] %a)",
                         "jit(step)/loop.step/layer0._mlp/mlp/dot_general",
                         ["jit(step)/loop.step/layer0._mlp/mlp/mul"]),
    (STEP, "fusion.2"): ("%fusion.2 = bf16[8,64] fusion(bf16[8,64] %b)",
                         "jit(step)/loop.step/layer0/cache.gather/"
                         "jit(_take)/gather", ["", ""]),
    (STEP, "fusion.5"): ("%fusion.5 = bf16[8,64] fusion(bf16[8,64] %c)", "",
                         ["jit(step)/loop.step/layer0._qkv/attn.qkv/mul",
                          "jit(step)/loop.step/layer0._qkv/attn.qkv/add",
                          "jit(step)/loop.step/layer0._mlp/mlp/mul", ""]),
    (STEP, "copy.3"): ("%copy.3 = bf16[64,8]{0,1} copy(bf16[64,8] %w)", "",
                       None),
    (STEP, "all-reduce.4"): ("%all-reduce.4 = f32[64] all-reduce("
                             "f32[64] %g)", "jit(step)/head/psum", None),
    (PREFILL, "fusion.1"): ("%fusion.1 = bf16[8,64] fusion(bf16[8,64] "
                            "%a), kind=kLoop",
                            "jit(prefill)/layer0/attn.scores/dot_general",
                            [""]),
}


def build_trace(path, *, tf_op=True, hlo=True, program_id=True, planes=1):
    """The operations of ``OPS`` on ``planes`` device planes, plane ``k``'s
    times ``k + 1`` times the first's; names as the ``tf_op`` statistic of
    the metadata and in the modules' ``Hlo Proto`` on ``/host:metadata``,
    in one of them, or nowhere."""
    pb = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    hlo_pb = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    space = pb.XSpace()
    for k in range(planes):
        plane = space.planes.add(name=f"/device:TPU:{k}")
        for i, stat in enumerate(("tf_op", "flops", "program_id"), 7):
            plane.stat_metadata[i].id = i
            plane.stat_metadata[i].name = stat
        ids = {}
        for i, ((module, inst), (line, op_name, _)) in enumerate(
                OPS.items(), 1):
            meta = plane.event_metadata[i]
            meta.id, meta.name, meta.display_name = i, line, inst
            meta.stats.add(metadata_id=8, uint64_value=1)
            if program_id:
                meta.stats.add(metadata_id=9, uint64_value=module[1])
            if tf_op and op_name:
                meta.stats.add(metadata_id=7, str_value=op_name)
            ids[module, inst] = i
        for j, module in enumerate((STEP, PREFILL), 100):
            plane.event_metadata[j].id = j
            plane.event_metadata[j].name = module[0]
            ids[module] = j
        modules = plane.lines.add(id=1, name="XLA Modules", timestamp_ns=1000)
        line = plane.lines.add(id=2, name="XLA Ops", timestamp_ns=1000)

        def put(on, key, start_ns, dur_ns):
            on.events.add(metadata_id=ids[key],
                          offset_ps=start_ns * 1000 * (k + 1),
                          duration_ps=dur_ns * 1000 * (k + 1))

        # two steps: a while of 100 ns around two iterations of (mlp 20,
        # gather 10, the unnamed fusion 5), then a copy of 6 and an
        # all-reduce of 4
        for t in (0, 200):
            put(modules, STEP, t, 120)
            put(line, (STEP, "while.6"), t, 100)
            for it in (10, 50):
                put(line, (STEP, "fusion.1"), t + it, 20)
                put(line, (STEP, "fusion.2"), t + it + 20, 10)
                put(line, (STEP, "fusion.5"), t + it + 30, 5)
            put(line, (STEP, "copy.3"), t + 102, 6)
            put(line, (STEP, "all-reduce.4"), t + 110, 4)
        put(modules, PREFILL, 400, 50)
        put(line, (PREFILL, "fusion.1"), 405, 40)
    host = space.planes.add(name="/host:CPU")
    host.lines.add(id=1, name="python3")
    if hlo:
        meta_plane = space.planes.add(name="/host:metadata")
        meta_plane.stat_metadata[1].id = 1
        meta_plane.stat_metadata[1].name = "Hlo Proto"
        for module in (STEP, PREFILL):
            proto = hlo_pb.HloProto()
            proto.hlo_module.name = module[0].split("(")[0]
            main = proto.hlo_module.computations.add(name="main", id=1)
            for (mod, inst), (_, op_name, fused) in OPS.items():
                if mod != module:
                    continue
                made = main.instructions.add(
                    name=inst, opcode=regions.opcode(OPS[mod, inst][0]))
                made.metadata.op_name = op_name
                if fused is not None:
                    comp = proto.hlo_module.computations.add(
                        name=f"fused_{inst}",
                        id=len(proto.hlo_module.computations) + 1)
                    for n, inner in enumerate(fused):
                        comp.instructions.add(
                            name=f"{inst}.in{n}",
                            opcode="multiply").metadata.op_name = inner
                    made.called_computation_ids.append(comp.id)
            meta = meta_plane.event_metadata[module[1]]
            meta.id, meta.name = module[1], module[0]
            meta.stats.add(metadata_id=1,
                           bytes_value=proto.SerializeToString())
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())
    return str(path)


def want(fused: bool = True) -> dict:
    """What ``build_trace`` holds, in seconds on its first plane; the
    unnamed fusion with the projections where its instructions can be
    read, else with the copy."""
    step = {"loop.step": 2 * 30e-9, "mlp": 2 * 40e-9,
            "cache.gather": 2 * 20e-9, regions.UNNAMED: 2 * 6e-9,
            regions.COLLECTIVE: 2 * 4e-9}
    step["attn.qkv" if fused else regions.UNNAMED] = \
        step.get(regions.UNNAMED, 0) * (not fused) + 2 * 10e-9
    return {"jit_step": {"executions": 2, "regions": step},
            "jit_prefill": {"executions": 1,
                            "regions": {"attn.scores": 40e-9}}}


def check(red: dict, wanted: dict, scale: float = 1.0) -> None:
    assert set(red["programs"]) == set(wanted)
    for name, held in wanted.items():
        got = red["programs"][name]
        assert got["executions"] == held["executions"]
        assert got["regions"] == pytest.approx(
            {k: v * scale for k, v in held["regions"].items()})
        assert got["seconds"] == pytest.approx(
            scale * sum(held["regions"].values()))


@pytest.mark.parametrize("source", ["tf_op", "hlo_proto"])
def test_reduce_joins_every_operation_to_a_program_and_a_region(tmp_path,
                                                                source):
    path = build_trace(tmp_path / "t.xplane.pb", tf_op=source == "tf_op")
    red = regions.reduce(path)
    assert red["found"] and red["devices"] == 1 and red["overlap_s"] == 0
    assert red["source"]["ops_lines"] == ["XLA Ops"]
    assert red["source"][source] == 4 and red["source"]["fused"] == 1
    check(red, want())
    # the two programs' ``fusion.1`` went to two regions, and the loop's
    # 100 ns count once: the total is the union, ``xplane``'s busy time
    assert red["total_s"] == pytest.approx(xplane.reduce(path)["busy_s"])
    assert red["total_s"] == pytest.approx(2 * 110e-9 + 40e-9)
    step = red["programs"]["jit_step"]
    assert step["names"] == ["attn.qkv", "cache.gather", "loop.step", "mlp"]
    assert step["unnamed"] == [["copy bf16[64,8]", 1, pytest.approx(12e-9)]]
    assert red["programs"]["jit_prefill"]["names"] == ["attn.scores"]


def test_a_fused_operation_goes_to_its_instructions_region(tmp_path):
    """``%fusion.5`` carries no name of its own; three of its four
    instructions do, two of them the projections'."""
    path = build_trace(tmp_path / "t.xplane.pb")
    step = regions.reduce(path)["programs"]["jit_step"]
    assert step["regions"]["attn.qkv"] == pytest.approx(2 * 10e-9)
    # without the modules' HLO there is nothing to ask: it stays unnamed
    path = build_trace(tmp_path / "u.xplane.pb", hlo=False)
    red = regions.reduce(path)
    check(red, want(fused=False))
    assert [row[:2] for row in red["programs"]["jit_step"]["unnamed"]] == [
        ["fusion bf16[8,64]", 1], ["copy bf16[64,8]", 1]]


def test_without_program_ids_the_modules_line_names_the_program(tmp_path):
    path = build_trace(tmp_path / "t.xplane.pb", program_id=False)
    check(regions.reduce(path), want())


def test_four_planes_give_the_mean(tmp_path):
    path = build_trace(tmp_path / "t.xplane.pb", planes=4)
    red = regions.reduce(path)
    assert red["devices"] == 4 and len(red["source"]["device_planes"]) == 4
    check(red, want(), scale=(1 + 2 + 3 + 4) / 4)
    assert red["total_s"] == pytest.approx(xplane.reduce(path)["busy_s"])


def test_a_file_with_no_name_of_the_vocabulary_finds_nothing(tmp_path):
    path = build_trace(tmp_path / "t.xplane.pb", tf_op=False, hlo=False)
    red = regions.reduce(path)
    assert red["found"] is False and red["devices"] == 1
    step = red["programs"]["jit_step"]
    assert step["names"] == [] and step["executions"] == 2
    assert set(step["regions"]) == {regions.UNNAMED, regions.COLLECTIVE}
    # nor does a file that is no trace, or is not there, raise
    junk = tmp_path / "junk.xplane.pb"
    junk.write_bytes(b"\x0a\xff\xff\xff\xff\x0f no trace")
    for path in (str(junk), str(tmp_path / "missing.xplane.pb")):
        red = regions.reduce(path)
        assert red["found"] is False and red["programs"] == {}


@pytest.mark.parametrize("trace", [
    NAMELESS, os.path.join(DATA, "tiny_tpu_1.xplane.pb"),
    os.path.join(DATA, "tiny_tpu_4.xplane.pb")],
    ids=["serve_pr25", "train_1", "train_4"])
def test_the_older_recordings_hold_no_name(trace):
    # Their statistics were dropped when they were recorded (``slim``), as
    # a parent's program places no region: both read as nothing found.
    whole = xplane.reduce(trace)
    red = regions.reduce(trace)
    assert whole["busy_s"] > 0 and red["found"] is False
    assert red["devices"] == whole["devices"]
    # (``ProfileData`` gives whole nanoseconds, the file picoseconds: on
    # operations of a microsecond that is a few parts in a thousand)
    assert red["total_s"] == pytest.approx(whole["busy_s"], rel=5e-3)
    assert all(p["names"] == [] for p in red["programs"].values())


def traced_ctx(tmp_path, monkeypatch, trace, cell="a_cell"):
    cell_dir = tmp_path / "trace" / cell / "plugins" / "profile" / "t"
    cell_dir.mkdir(parents=True)
    shutil.copy(trace, cell_dir / "vm.xplane.pb")
    monkeypatch.setattr(spec, "OUT_DIR", str(tmp_path))
    return {"cell": cell, "trace": {
        "busy_s": xplane.reduce(trace)["busy_s"], "window_s": 1.0}}


def read_metric(name, ctx):
    return spec.load_module(os.path.join(
        spec.HERE, "metrics", name + ".py")).read(ctx)


def test_every_new_reader_finds_nothing_on_a_program_without_regions(
        tmp_path, monkeypatch):
    assert len(NEW) == 19
    ctx = traced_ctx(tmp_path, monkeypatch, NAMELESS)
    for metric in NEW:
        assert read_metric(metric["name"], ctx) is None, metric["name"]
    # nor on a run that traced no device (the CPU rehearsal), or nothing
    for metric in NEW:
        assert read_metric(metric["name"], {"cell": "a_cell",
                                            "trace": None}) is None
        assert read_metric(metric["name"], {
            "cell": "a_cell", "trace": {"found": {}}}) is None


def test_the_new_entries_name_the_layers_and_sources_the_issue_gives():
    by_name = {m["name"]: m for m in NEW}
    assert BENCH["per_layer"][-19:] == NEW
    for name, m in by_name.items():
        stage = name.startswith("chat_stage_")
        assert m["source"] == ("program_counter" if stage
                               else "device_trace")
        assert m["layer"] == ("engine step" if stage else "sync step"
                              if name == "train_optimizer_pct" else "kernels")
        assert m["better"] == "lower" and m["unit"] in ("ms", "%")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert {m["moves"] for n, m in by_name.items()
            if n.startswith("chat_")} == {"serve_tpot_mean_ms"}
    assert {m["moves"] for n, m in by_name.items()
            if n.startswith(("lp_", "ohlp_", "glm_"))} == {
                "serve_tokens_per_s"}
    assert {tuple(m["workloads"]) for n, m in by_name.items()
            if n.startswith("train_")} == {
                ("train_gpt2m_1chip", "train_gpt2m_dp4")}
    # a serving cell's three parts, and nothing of another cell's
    cells = {w["name"]: spec.metrics_of(BENCH, w["name"])["per_layer"]
             for w in BENCH["workloads"]}
    for names in cells.values():
        mine = [n for n in names if "_decode_" in n and n.endswith("_ms")
                and n.split("_decode_")[1][:-3] in PARTS]
        assert len(mine) in (0, 3)
        assert len({n.split("_decode_")[0] for n in mine}) <= 1


@pytest.mark.parametrize("cell", SERVING)
def test_readers_on_a_built_trace(tmp_path, monkeypatch, cell):
    path = build_trace(tmp_path / "built.xplane.pb")
    ctx = traced_ctx(tmp_path, monkeypatch, path)
    # a step: 20 ns of gather an execution; 40 of MLP, 10 of projections,
    # 30 of the loop's own and 4 of a collective; 6 of a copy
    parts = [read_metric(f"{cell}_decode_{p}_ms", ctx) for p in PARTS]
    assert parts == pytest.approx([20e-6, 84e-6, 6e-6])
    step = regions.reduce(path)["programs"]["jit_step"]
    assert sum(parts) == pytest.approx(
        1e3 * step["seconds"] / step["executions"])
    if cell == "chat":
        assert read_metric("lp_prefill_attn_pct", ctx) == pytest.approx(100.0)
        assert read_metric("ohlp_prefill_scan_pct", ctx) == pytest.approx(0.0)
        # a training cell's step is the program with most device time
        assert read_metric("train_unnamed_pct", ctx) == pytest.approx(
            100 * 12 / 220)
        assert read_metric("train_head_loss_pct", ctx) == pytest.approx(0.0)
        assert read_metric("train_optimizer_pct", ctx) == pytest.approx(0.0)


# ----------------------------------------------- recorded on the chip


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("recorded") / os.path.basename(RECORDED)
    with gzip.open(RECORDED + ".gz", "rb") as src:
        path.write_bytes(src.read())
    return str(path)


@pytest.fixture(scope="module")
def recorded(chip_trace):
    with open(RECORDED + ".json") as fh:
        return regions.reduce(chip_trace), json.load(fh)


def test_the_recorded_trace_still_reads_as_it_did_on_the_chip(recorded):
    red, then = recorded
    assert os.path.getsize(RECORDED + ".gz") < 300_000
    assert red["found"] and red["source"] == then["regions"]["source"]
    assert red["source"]["device_planes"] == ["/device:TPU:0"]
    assert red["total_s"] == pytest.approx(then["regions"]["total_s"])
    for name, was in then["regions"]["programs"].items():
        got = red["programs"][name]
        assert got["executions"] == was["executions"]
        assert got["regions"] == pytest.approx(was["regions"])
        assert got["names"] == was["names"]
        assert [row[:2] for row in got["unnamed"]] == [
            row[:2] for row in was["unnamed"]]


def test_regions_are_found_by_name_on_the_recorded_trace(recorded):
    red, _ = recorded
    # Two prefill programs (12 and 30 tokens) under one name, and the step.
    assert {"jit_step", "jit_prefill"} <= set(red["programs"])
    assert red["programs"]["jit_prefill"]["executions"] == 3
    step, prefill = (red["programs"][p] for p in ("jit_step", "jit_prefill"))
    assert 8 <= step["executions"] <= 12
    assert {"embed", "attn.qkv", "cache.write", "cache.gather",
            "attn.scores", "attn.out", "mlp", "head", "sample",
            "loop.step"} <= set(step["names"])
    assert set(step["names"]) <= set(step["regions"])
    assert {"cache.gather", "head", "sample"}.isdisjoint(prefill["names"])
    # operations the profiler left no ``tf_op`` on were named all the same
    assert red["source"]["tf_op"] > 0
    assert red["source"]["hlo_proto"] + red["source"]["fused"] > 0


def test_a_fused_operation_of_the_recorded_trace_has_its_instructions_region(
        recorded, chip_trace, tmp_path):
    """With the modules' HLO cut out of the file, the fusions that were
    named by their fused instructions fall back to ``unnamed`` and nothing
    else moves."""
    red, _ = recorded
    assert red["source"]["fused"] > 0
    recorder = spec.load_module(os.path.join(spec.HERE, "tools",
                                             "record_region_trace.py"))
    with open(chip_trace, "rb") as fh:
        whole = fh.read()
    bare = tmp_path / "bare.xplane.pb"
    bare.write_bytes(recorder.cut(whole, {
        1: lambda plane: None if regions._plane_head(plane)[0]
        == "/host:metadata" else True}))
    without = regions.reduce(str(bare))
    assert without["source"]["fused"] == 0 and without["found"]
    assert without["total_s"] == pytest.approx(red["total_s"])
    moved = 0.0
    for name, prog in red["programs"].items():
        was = without["programs"][name]["regions"]
        grew = was.get(regions.UNNAMED, 0.0) - prog["regions"].get(
            regions.UNNAMED, 0.0)
        assert grew >= 0
        moved += grew
    assert moved > 0


def test_the_while_of_a_rolled_loop_is_not_counted_on_top_of_its_body(
        recorded, chip_trace):
    """The layers run inside a ``while``: its events and the body's nest
    on the line, so durations add up to MORE than the busy time, and the
    self times to exactly it."""
    red, then = recorded
    from jax.profiler import ProfileData
    (plane,) = [p for p in ProfileData.from_file(chip_trace).planes
                if xplane._is_device_plane(p.name)]
    _, events = xplane._ops_line(plane)
    whiles = [ev for ev in events if regions.opcode(ev.name) == "while"]
    assert len(whiles) >= red["programs"]["jit_step"]["executions"]
    assert sum(ev.duration_ns for ev in events) * 1e-9 > 1.2 * red["total_s"]
    assert sum(ev.duration_ns for ev in whiles) * 1e-9 > 0.3 * red["total_s"]
    whole = xplane.reduce(chip_trace)
    assert whole["busy_s"] == pytest.approx(then["xplane"]["busy_s"])
    assert red["total_s"] == pytest.approx(whole["busy_s"], rel=1e-3)
    assert red["overlap_s"] == 0


def test_self_times_add_up_to_each_programs_device_time(recorded,
                                                        chip_trace):
    """Read another way (``ProfileData``: whole nanoseconds), the union of
    the operations that start inside a program's ``XLA Modules`` events is
    that program's device time; the self times by region add up to it
    within 1%."""
    red, _ = recorded
    from jax.profiler import ProfileData
    (plane,) = [p for p in ProfileData.from_file(chip_trace).planes
                if xplane._is_device_plane(p.name)]
    _, ops = xplane._ops_line(plane)
    (modules,) = [ln for ln in plane.lines if ln.name == "XLA Modules"]
    spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                    regions.PROGRAM.sub(r"\1", ev.name))
                   for ev in modules.events)
    inside: dict = {}
    for ev in ops:
        for a, b, name in spans:
            if a <= ev.start_ns <= b:
                inside.setdefault(name, []).append(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
                break
    assert set(inside) == set(red["programs"])
    for name, prog in red["programs"].items():
        union = sum(b - a for a, b in xplane._union(inside[name])) * 1e-9
        assert sum(prog["regions"].values()) == pytest.approx(union, rel=0.01)
        assert prog["seconds"] == pytest.approx(union, rel=0.01)


def test_the_three_decode_metrics_add_up_on_the_recorded_trace(
        tmp_path, monkeypatch, recorded, chip_trace):
    red, _ = recorded
    ctx = traced_ctx(tmp_path, monkeypatch, chip_trace)
    step = red["programs"]["jit_step"]
    for cell in SERVING:
        parts = [read_metric(f"{cell}_decode_{p}_ms", ctx) for p in PARTS]
        assert all(p is not None and p > 0 for p in parts)
        assert sum(parts) == pytest.approx(
            1e3 * step["seconds"] / step["executions"], rel=1e-9)
    attn = sum(step["regions"].get(r, 0.0) for r in regions.ATTENTION)
    assert read_metric("glm_decode_attn_ms", ctx) == pytest.approx(
        1e3 * attn / step["executions"])
    assert read_metric("lp_prefill_attn_pct", ctx) == pytest.approx(
        100 * red["programs"]["jit_prefill"]["regions"]["attn.scores"]
        / red["programs"]["jit_prefill"]["seconds"])


def test_like_named_operations_of_two_programs_are_kept_apart(chip_trace):
    """The same instruction name stands in the step and in a prefill (each
    module numbers its own): an operation is looked up in ITS module."""
    all_planes = list(regions.planes(chip_trace))
    modules = {module: set(regions.hlo_module(proto))
               for module, proto in regions._modules_hlo(all_planes).values()}
    step = next(v for k, v in modules.items() if k.startswith("jit_step"))
    prefills = [v for k, v in modules.items() if k.startswith("jit_prefill")]
    assert len(prefills) == 2 and all(step & p for p in prefills)
