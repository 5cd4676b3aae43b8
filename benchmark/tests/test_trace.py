"""The trace reduction on intervals worked by hand, and on the small trace
recorded on the chip (benchmark/data)."""

import os

import pytest

from benchmark import trace as tr

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "data")


def _trace(device_events, host_events=()):
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": tr.OPS_LINE, "events": device_events}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": list(host_events)}]}]}


def test_union_and_busy():
    assert tr.union([(0, 10), (5, 10), (30, 5)]) == [[0, 15], [30, 35]]
    assert tr.busy_ns([["a", 0, 10], ["b", 5, 10], ["c", 30, 5]]) == 20


def test_own_time_takes_the_nested_out():
    # a loop of 100 ns runs two bodies of 30 ns: 40 ns are the loop's own
    ev = sorted([["while", 0, 100], ["body", 10, 30], ["body", 50, 30]],
                key=lambda e: (e[1], -e[2]))
    own = tr.own_time_by_name(ev)
    assert own["while"] == pytest.approx(40e-9)
    assert own["body"] == pytest.approx(60e-9)


def test_idle_gaps_named_by_the_host_span_over_them():
    t = _trace([["a", 100, 100], ["b", 400, 100]],
               [["fit", 0, 1000], ["prepare", 200, 150]])
    gaps = dict(tr.idle_gaps(t, (0, 600)))
    # 0-100 under "fit", 200-400 (middle 300) under the narrower "prepare",
    # 500-600 under "fit"
    assert gaps["prepare"] == pytest.approx(200e-9)
    assert gaps["fit"] == pytest.approx(200e-9)
    busy, window = tr.busy_and_window(t, (0, 600))
    assert busy == pytest.approx(200e-9) and window == pytest.approx(600e-9)


def test_gap_with_no_host_event():
    t = _trace([["a", 100, 100]])
    assert tr.idle_gaps(t, (0, 200)) == [[tr.NO_HOST_EVENT, 100e-9]]


def test_clip_keeps_what_starts_inside():
    t = tr.clip(_trace([["a", 0, 10], ["b", 50, 10], ["c", 99, 10]]), 40, 99)
    assert [e[0] for e in tr.device_ops(t)["/device:TPU:0"]] == ["b"]


def test_recorded_trace_reduces():
    path = os.path.join(DATA, "trace_small.json.gz")
    t = tr.load(path)
    name, events = tr.fullest(t)
    assert name.startswith("/device:TPU:")
    window = [(s, s + d) for n, s, d in tr.host_annotations(t)
              if n == "bench.window"][0]
    busy, seconds = tr.busy_and_window(t, window)
    assert 0 < busy <= seconds
    own = tr.own_time_by_name(events)
    # the parts add up to the whole: own times sum to the busy time
    assert sum(own.values()) == pytest.approx(busy, rel=1e-6)
    b = tr.breakdown(t, window)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = sum(v for _, v in tr.idle_gaps(t, window, top=10 ** 6))
    assert idle == pytest.approx(seconds - busy, rel=1e-6)
