"""bench/trace.py: busy, idle, per-op time, exposed collectives and idle
gaps by host span, on planes worked by hand and on a recorded chip
trace."""
import os
from types import SimpleNamespace as NS

import pytest

import bench_small  # noqa: F401  (puts the repository on sys.path)
from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes(dev_events, host_events, n_dev=1):
    out = [NS(name="/host:CPU", lines=[NS(name="main", events=host_events)])]
    for d in range(n_dev):
        out.append(NS(name=f"/device:TPU:{d}",
                      lines=[NS(name="XLA Modules", events=[]),
                             NS(name="XLA Ops", events=dev_events[d])]))
    return out


def test_merge_and_subtract_by_hand():
    assert trace._merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace._subtract([(0, 10)], [(2, 3), (5, 8)]) == 6
    assert trace._subtract([(0, 4), (6, 10)], [(3, 7)]) == 6
    assert trace._subtract([(0, 4)], []) == 4


def test_summary_worked_by_hand():
    # window 0..100 ns; ops cover 10..30 and 25..40 (busy 30 ns) and a
    # collective 60..80 overlapped by compute 70..75 (exposed 15 ns)
    dev = [[ev("fusion.1", 10, 20), ev("kernel_fwd", 25, 15),
            ev("all-reduce.3", 60, 20), ev("fusion.2", 70, 5),
            ev("outside", 150, 10)]]
    host = [ev("bench.window", 0, 100), ev("bench.job", 0, 45),
            ev("bench.ckpt", 45, 55), ev("Execute", 82, 10),
            ev("Transfer", 80, 15)]
    s = trace.summarize_planes(planes(dev, host), devices=1)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((30 + 20) * 1e-9)
    assert s.op_seconds("kernel_fwd") == pytest.approx(15e-9)
    assert "outside" not in s.op_s
    assert s.collective_exposed_s == pytest.approx(15e-9)
    # gaps: 0..10 under bench.job; 40..60 mostly and 80..100 wholly
    # under bench.ckpt
    # and the 80..100 gap to the program's host event that covers it most
    gaps = dict(s.idle_gaps)
    assert gaps["bench.job"] == pytest.approx(10e-9)
    assert gaps["bench.ckpt"] == pytest.approx(20e-9)
    assert gaps["bench.ckpt: Transfer"] == pytest.approx(20e-9)
    b = s.breakdown()
    assert b["device_ops"][0][0] in ("fusion.1", "all-reduce.3")
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_busy_is_averaged_over_devices():
    dev = [[ev("a", 0, 50)], [ev("a", 0, 10)], [ev("a", 0, 99)]]
    host = [ev("bench.window", 0, 100)]
    s = trace.summarize_planes(planes(dev, host, n_dev=3), devices=2)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(30e-9)
    assert s.op_s["a"] == pytest.approx(30e-9)


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize_planes(planes([[ev("a", 0, 5)]], []), 1)
    with pytest.raises(ValueError):
        trace.summarize_planes(planes([], [ev("bench.window", 0, 5)],
                                      n_dev=0), 1)


def test_recorded_chip_trace():
    """A traced minwise-hash window recorded on one TPU v5 lite chip
    (one 790 M-nonzero pass): busy, the encode kernel's time and the idle
    gaps put down to what the host was doing."""
    s = trace.summarize(os.path.join(DATA, "minwise-hash.xplane.pb"), 1)
    assert s.devices == 1
    assert s.window_s == pytest.approx(32.5038, abs=1e-3)
    assert s.busy_s == pytest.approx(7.9338, abs=1e-3)
    assert s.collective_exposed_s is None       # no collective ran
    kernel = s.op_seconds(r"(minhash|oph)_pack_pallas")
    assert kernel == pytest.approx(7.9337, abs=1e-3)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "minhash_pack_pallas.1"
    name, secs = b["idle_gaps"][0]
    assert name == "bench.hash_pass: shard_args"
    assert secs == pytest.approx(22.824, abs=1e-2)
    # idle gaps and busy time tile the window
    idle = sum(v for _, v in s.idle_gaps)
    assert idle + s.busy_s == pytest.approx(s.window_s, rel=1e-6)
