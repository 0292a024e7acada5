"""The trace reduction, on a synthetic trace with known answers and on a
small trace recorded on a TPU v5e (``record_trace.py``)."""

from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v) for k, v in lines.items()])


def fake():
    # window 0..1000 ns; device busy 100..300 and 250..400 (overlap) and 700..800
    host = plane("/host:CPU", main=[ev("window", 0, 1000), ev("feed.next", 0, 100),
                                    ev("step", 100, 420), ev("ckpt.save", 420, 700),
                                    ev("step", 700, 820)])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("fusion.1", 100, 300), ev("fusion.2", 250, 400),
                                          ev("fusion.1", 700, 800), ev("late", 1200, 1300)])
    other = plane("/device:TPU:0 SparseCore 0", XLA_Ops=[ev("x", 0, 1000)])
    return NS(planes=[host, dev, other])


def test_synthetic_busy_ops_and_gaps():
    r = trace.reduce_profile(fake(), n_devices=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(400e-9)           # 100..400 and 700..800
    assert r["device_ops"] == [["fusion.1", 300e-9], ["fusion.2", 150e-9]]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # gaps 0..100 (feed.next), 400..700 (step to 420, then ckpt.save),
    # 800..1000 (step to 820, then no span)
    assert gaps == pytest.approx({"feed.next": 100e-9, "step": 40e-9, "ckpt.save": 280e-9,
                                  "(no span)": 180e-9})


def test_innermost_span_names_a_gap():
    host = plane("/host:CPU", a=[ev("window", 0, 100), ev("step", 0, 100)],
                 b=[ev("incr.read", 40, 60)])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("op", 0, 40), ev("op", 60, 100)])
    r = trace.reduce_profile(NS(planes=[host, dev]), n_devices=1)
    assert r["idle_gaps"] == [["incr.read", pytest.approx(20e-9)]]


def test_missing_window_or_device_raises():
    host = plane("/host:CPU", main=[ev("step", 0, 10)])
    with pytest.raises(RuntimeError, match="window"):
        trace.reduce_profile(NS(planes=[host]), n_devices=1)
    host = plane("/host:CPU", main=[ev("window", 0, 10)])
    with pytest.raises(RuntimeError, match="TPU planes"):
        trace.reduce_profile(NS(planes=[host]), n_devices=1)


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData

    r = trace.reduce_profile(ProfileData.from_file(DATA), n_devices=1,
                             names={"feed.next", "step"})
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # five 2 ms host sleeps under ``feed.next`` with nothing queued on the chip
    assert gaps["feed.next"] >= 5 * 0.002
