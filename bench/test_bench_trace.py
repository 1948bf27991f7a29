"""Reduction of a profiler trace to busy and idle time, top device
operations and idle gaps named by the host's benchmark span: on hand-
made planes, and on a small trace recorded on a TPU v5e
(``bench/data/record_trace.py``)."""
import os
from types import SimpleNamespace as NS

import pytest

import tracecut

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "v5e_small.xplane.pb")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 1000), _ev("bench.feed", 0, 300),
        _ev("bench.step", 300, 500), _ev("bench.feed", 800, 150),
        _ev("other", 900, 50)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.1", 350, 200),
                                   _ev("dot.2", 500, 250),
                                   _ev("copy.3", 780, 20),
                                   _ev("fusion.1", 960, 100)]),
        NS(name="XLA Modules", events=[_ev("jit_f", 350, 700)])])
    return [host, dev]


def test_merge_and_gaps():
    busy = tracecut.merge([(5, 8), (0, 2), (1, 3), (9, 20)], 0, 10)
    assert busy == [(0, 3), (5, 8), (9, 10)]
    assert tracecut.gaps(busy, 0, 12) == [(3, 5), (8, 9), (10, 12)]


def test_reduce_hand_made_trace():
    r = tracecut.reduce(_planes())
    assert r["window_s"] == pytest.approx(1000e-9)
    # ops cover 350..750, 780..800 and 960..1000 (the last clipped)
    assert r["busy_s"] == pytest.approx(460e-9)
    assert r["device_ops"][0] == ["dot.2", pytest.approx(250e-9)]
    assert r["device_ops"][1] == ["fusion.1", pytest.approx(240e-9)]
    # 0..350 under feed, 800..960 under feed, 750..780 under step
    assert r["idle_gaps"] == [["feed", pytest.approx(350e-9)],
                              ["feed", pytest.approx(160e-9)],
                              ["step", pytest.approx(30e-9)]]


def test_reduce_refuses_trace_without_window_or_device():
    host, dev = _planes()
    with pytest.raises(ValueError):
        tracecut.reduce([dev])
    with pytest.raises(ValueError):
        tracecut.reduce([host])


def test_reduce_recorded_chip_trace():
    """5 steps of a matmul chain, each after a 20 ms host sleep in a
    ``bench.feed`` span (record_trace.py): the device idles about
    100 ms of the window, the longest gaps fall in ``feed``."""
    r = tracecut.reduce_file(TRACE)
    assert 0.1 < r["window_s"] < 2.0
    assert 0 < r["busy_s"] < r["window_s"] - 0.09
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert [n for n, _ in r["idle_gaps"][:5]] == ["feed"] * 5
    assert all(s >= 0.019 for _, s in r["idle_gaps"][:5])
