"""The trace reduction on intervals worked out by hand, and on the small
trace recorded on the chip (``data/trace_sample.json``)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr
from benchmark.spec import CODE_DIR


def synthetic():
    # window 0..1000 ns on one chip.  ops: a 100-300, b 250-400 (overlaps
    # a), all-reduce 400-600 with a fusion 500-550 inside it, c 800-900.
    return {
        "window_ns": [0, 1000],
        "devices": {"0": [["a", 100, 200], ["b", 250, 150],
                          ["all-reduce.1", 400, 200],
                          ["fusion.7", 500, 50], ["c", 800, 100]]},
        "host": [["place_batch", 0, 100], ["dispatch", 600, 150],
                 ["sync", 700, 300], ["inner", 720, 30]]}


def test_busy_is_the_union_not_the_sum():
    t = synthetic()
    ev = t["devices"]["0"]
    assert tr.busy_ns(ev, (0, 1000)) == (400 - 100) + 200 + 100
    assert tr.busy_ns(ev, (200, 450)) == 250          # clipped


def test_idle_gaps_and_their_owners():
    t = synthetic()
    gaps = tr.idle_gaps(t["devices"]["0"], (0, 1000))
    assert gaps == [(0, 100), (600, 800), (900, 1000)]
    owners = tr.attribute_gaps(gaps, t["host"], (0, 1000))
    # 0-100 place_batch; 600-700 dispatch; 700-720 sync; 720-750 inner
    # (started last); 750-800 sync; 900-1000 sync
    assert owners == pytest.approx({
        "place_batch": 100e-9, "dispatch": 100e-9, "inner": 30e-9,
        "sync": (20 + 50 + 100) * 1e-9})
    assert sum(owners.values()) == pytest.approx(400e-9)


def test_exposed_collective_time_leaves_out_the_hidden_part():
    ev = synthetic()["devices"]["0"]
    assert tr.exposed_collective_ns(ev, (0, 1000)) == 200 - 50


def test_matching_seconds_and_reduce():
    t = synthetic()
    s, n = tr.matching_seconds(t["devices"]["0"], (0, 1000), r"^(a|c)$")
    assert (s, n) == (pytest.approx(300e-9), 2)
    red = tr.reduce(t)
    assert red["busy_s"] == pytest.approx(600e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["device_ops"][0] == ["a", pytest.approx(200e-9)]
    assert red["exposed_collective_s"] == pytest.approx(150e-9)
    assert dict(red["idle_gaps"])["sync"] == pytest.approx(170e-9)


def test_recorded_trace_reduces_to_the_numbers_noted_with_it():
    path = os.path.join(str(CODE_DIR), "data", "trace_sample.json")
    doc = json.load(open(path))
    red = tr.reduce(doc["trace"])
    for key, want in doc["expected"].items():
        assert red[key] == pytest.approx(want, rel=1e-9), key
    assert [g[0] for g in red["idle_gaps"]] == \
        [g[0] for g in doc["expected_idle_gaps"]]
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["device_ops"] and red["idle_gaps"]
