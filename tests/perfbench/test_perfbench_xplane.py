"""The trace reducer, on small traces recorded on the chip in PR 24 with
``perfbench/tools/record_trace.py`` (four steps of a tiny jitted program
under the harness's profiler options, a 20 ms pause after the second)."""

import json
import os

import pytest

from perfbench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = sorted(f for f in os.listdir(DATA) if f.endswith(".xplane.pb"))


def test_union_merges_nested_and_overlapping_intervals():
    assert xplane._union([(0, 10), (2, 5), (8, 12), (20, 30)]) == [
        (0, 12), (20, 30)]
    assert xplane._union([]) == []


@pytest.mark.parametrize("name,cls", [
    ("all-reduce.17", "collective"), ("all-reduce-start.2", "collective"),
    ("%all-gather.3", "collective"), ("reduce-scatter.1", "collective"),
    ("collective-permute-done", "collective"),
    ("fusion.123", "fusion"), ("convolution.4", "matmul"),
    ("custom-call.9", "custom_call"), ("copy.5", "data_movement"),
    ("while.1", "other")])
def test_op_classes(name, cls):
    assert xplane.op_class(name) == cls


def test_device_planes_are_found_by_kind():
    assert xplane._is_device_plane("/device:TPU:0")
    assert xplane._is_device_plane("/device:TPU:3")
    assert not xplane._is_device_plane("/host:CPU")
    assert not xplane._is_device_plane("/device:CUSTOM:Megascale Trace")
    assert not xplane._is_device_plane("/host:metadata")


def test_recorded_traces_are_there():
    assert any("tpu_1" in t for t in TRACES), TRACES


@pytest.mark.parametrize("trace", TRACES)
def test_reducer_on_a_recorded_chip_trace(trace):
    red = xplane.reduce(os.path.join(DATA, trace))
    chips = int(trace.split("_")[2].split(".")[0])
    assert red["devices"] == chips
    assert len(red["found"]["device_planes"]) == chips
    assert all(p.startswith("/device:TPU:")
               for p in red["found"]["device_planes"])
    assert red["device_events"] > 0
    # Four tiny steps and a 20 ms pause: the device is busy for a small part
    # of the span, and never for more than the span.
    assert 0 < red["busy_s"] < red["span_s"]
    assert red["span_s"] > 0.02
    # Busy time is a union: no more than the operations' summed durations.
    assert red["busy_s"] <= sum(red["class_s"].values()) + 1e-9
    # The longest idle gap is the pause, and it is named by the harness's
    # annotation that covers it.
    gaps = dict((k, v) for k, v in red["idle_gaps"])
    assert max(gaps, key=gaps.get) == "perfbench.pause"
    assert gaps["perfbench.pause"] > 0.015
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
    if chips > 1:
        assert red["collective_s"] > 0
        assert red["class_s"]["collective"] > 0
    else:
        assert red["collective_s"] == 0
    # The reading taken on the chip when the trace was recorded still holds.
    then = json.load(open(os.path.join(DATA, trace + ".json")))
    assert red["busy_s"] == pytest.approx(then["busy_s"])
    assert red["found"]["ops_lines"] == then["found"]["ops_lines"]
