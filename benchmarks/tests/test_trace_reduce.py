"""The trace reduction, on hand-made events and on a small recorded excerpt
of a chip trace (a few milliseconds of the r2d2-fused segment on a v5e,
benchmarks/tests/data/trace_excerpt.json)."""

import json
import os

import numpy as np
import pytest

from benchmarks import trace_reduce as tr

DEV, OPS = "/device:TPU:0", tr.OPS_LINE


def test_nested_events_by_hand():
    events = [
        (DEV, OPS, "%while.1", 0.0, 100.0),
        (DEV, OPS, "%fusion.a", 10.0, 20.0),
        (DEV, OPS, "%all-reduce.b", 40.0, 30.0),
        (DEV, "Steps", "0", 0.0, 100.0),  # other lines are not operations
        ("/host:CPU", "python3", tr.HOST_SPAN, 0.0, 60.0),
    ]
    r = tr.reduce_events(events, 1)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert dict(map(tuple, r["device_ops"])) == pytest.approx(
        {"%while.1": 50e-9, "%fusion.a": 20e-9, "%all-reduce.b": 30e-9})
    gaps = dict(map(tuple, r["idle_gaps"]))
    # (0,10) and (30,40) fall inside the host's dispatch span, (70,100) after
    assert gaps["inside a dispatch (host waits on the device)"] == \
        pytest.approx(20e-9)
    assert gaps["between dispatches (host loop)"] == pytest.approx(30e-9)


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        tr.reduce_events([("/host:CPU", "python3", "x", 0.0, 1.0)], 1)


def test_recorded_excerpt():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_excerpt.json")
    events = [tuple(e) for e in json.load(open(path))]
    r = tr.reduce_events(events, 1)
    ops = [(s, s + d) for p, l, _n, s, d in events if p == DEV and l == OPS]
    assert len(ops) > 1000
    # self times add up to the time covered by any operation ...
    edges = np.unique([t for iv in ops for t in iv])
    mids = 0.5 * (edges[1:] + edges[:-1])
    depth = np.zeros(len(mids), int)
    for s, e in ops:
        depth += (mids > s) & (mids < e)
    covered = float(((depth > 0) * np.diff(edges)).sum())
    assert sum(t for _n, t in r["device_ops"]) == pytest.approx(
        covered / 1e9, rel=1e-9)
    # ... and busy time is where the innermost operation is not a container
    # waiting on nothing: never more than the covered time, never zero
    assert 0.0 < r["busy_s"] <= covered / 1e9
    assert r["busy_s"] < r["window_s"]
