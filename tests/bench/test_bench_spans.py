"""bench/spans.py: the device's idle time put down to the innermost
program span of the training loop, on planes worked by hand (a loop
line and a producer line, nested and edge-straddling spans), and the
seven training metrics read from it."""
import os
from types import SimpleNamespace as NS

import pytest

import bench_small  # noqa: F401  (puts the repository on sys.path)
from bench import harness, spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def planes(dev, lines):
    out = [NS(name="/host:CPU", lines=[NS(name="python", events=events)
                                        for events in lines])]
    out.append(NS(name="/device:TPU:0",
                  lines=[NS(name="XLA Ops", events=dev)]))
    return out


# window 0..1000 ns; the device runs 100..200, 300..330, 350..400 and
# 700..900, so it is idle 0..100, 200..300, 330..350, 400..700 and
# 900..1000 (620 ns)
DEV = [ev("fusion.1", 100, 100), ev("fwd", 300, 30), ev("bwd", 350, 50),
       ev("bwd", 700, 200)]
LOOP = [
    ev("bench.window", 0, 1000), ev("bench.train_job", 0, 1000),
    ev("repro.train.step", -100, 50, step_num=99),    # before the window
    ev("repro.train.job_setup", 0, 150),
    ev("repro.train.step", 160, 90, step_num=0),
    ev("repro.prefetch.wait", 250, 30),
    ev("repro.train.step", 280, 40, step_num=1),
    ev("repro.train.hits_drain", 320, 30, n=2),
    ev("repro.ckpt.save", 350, 250),
    ev("repro.ckpt.read_device", 360, 60, n=10),
    ev("repro.ckpt.write", 420, 160),
    ev("repro.ckpt.publish", 600, 80),
    ev("repro.ckpt.read_device", 600, 10, n=1),
    ev("repro.ckpt.read_device", 610, 10, n=2),
    ev("repro.ckpt.write", 620, 55),
    ev("PjitFunction(step)", 630, 5),                  # not a program span
    ev("repro.train.job_end", 950, 40),
]
PRODUCER = [ev("repro.prefetch.read", 240, 30, epoch=0, shard=3),
            ev("repro.prefetch.put", 270, 30, n=32),
            ev("repro.prefetch.queue_full", 500, 200)]

# idle ns under each innermost loop span, worked by hand, gap by gap
BY_HAND = {
    "repro.train.job_setup": 100,                     # 0..100
    "repro.train.step": 50 + 20,                      # 200..250, 280..300
    "repro.prefetch.wait": 30,                        # 250..280
    "repro.train.hits_drain": 20,                     # 330..350
    "repro.ckpt.read_device": 20 + 10 + 10,           # 400..420, 600..620
    "repro.ckpt.write": 160 + 55,                     # 420..580, 620..675
    "repro.ckpt.save": 20,                            # 580..600
    "repro.ckpt.publish": 5,                          # 675..680
    "(none)": 20 + 50 + 10,                           # 680..700, 900..950,
    #                                                   990..1000
    "repro.train.job_end": 40,                        # 950..990
}


def test_idle_by_span_to_the_nanosecond():
    s = spans.summarize_planes(planes(DEV, [LOOP, PRODUCER]))
    assert s.window_ns == 1000 and s.idle_ns == 620
    assert s.idle_by_span == BY_HAND
    assert sum(s.idle_by_span.values()) == s.idle_ns
    # the wait's idle overlaps the producer's read 20 ns, its put 10 ns
    assert s.wait_causes == {"repro.prefetch.read": 30}
    assert s.steps == 2 and s.device_reads == 2 + 10 + 1 + 2
    assert s.span_ns["repro.ckpt.write"] == (2, 215)
    assert s.span_ns["repro.prefetch.queue_full"] == (1, 200)
    assert s.span_ns["repro.train.step"] == (2, 130)
    assert s.idle_spans()[0] == ["repro.ckpt.write", pytest.approx(215e-9)]
    assert ["repro.prefetch.wait: repro.prefetch.read",
            pytest.approx(30e-9)] in s.idle_spans()
    assert len(s.idle_spans()) == len(BY_HAND)


def test_metrics_by_hand_partition_device_idle():
    p = planes(DEV, [LOOP, PRODUCER])
    got = spans.metrics(spans.summarize_planes(p))
    assert got == pytest.approx({
        "train.idle_input": 3.0, "train.idle_dispatch": 7.0,
        "train.idle_drain": 2.0, "train.idle_ckpt": 28.0,
        "train.idle_job_edges": 14.0, "train.idle_unattributed": 8.0,
        "train.device_reads_per_step": 7.5})
    idle = harness.metric_reader("train.device_idle")(
        {"trace": trace.summarize_planes(p, devices=1), "counters": {}})
    assert sum(v for k, v in got.items()
               if k != spans.READS_PER_STEP) == pytest.approx(idle)


def test_inline_reads_count_as_input():
    """Prefetch off: the loop reads and puts inside its own wait, and
    the innermost span there is the read or the put."""
    loop = [ev("bench.window", 0, 100),
            ev("repro.prefetch.wait", 0, 60),
            ev("repro.prefetch.read", 5, 30, epoch=0, shard=0),
            ev("repro.prefetch.put", 35, 20, n=8),
            ev("repro.train.step", 60, 40, step_num=0)]
    s = spans.summarize_planes(planes([ev("k", 90, 10)], [loop]))
    assert s.idle_by_span == {"repro.prefetch.wait": 5 + 5,
                              "repro.prefetch.read": 30,
                              "repro.prefetch.put": 20,
                              "repro.train.step": 30}
    assert s.wait_causes == {"(none)": 10}
    assert spans.metrics(s)["train.idle_input"] == pytest.approx(60.0)
    assert spans.metrics(s)["train.device_reads_per_step"] == 0.0


def test_a_child_ends_with_its_parent():
    segs = spans._innermost([(0, 10, "a"), (5, 20, "b"), (12, 15, "c")])
    assert segs == [(0, 5, "a"), (5, 10, "b"), (12, 15, "c")]
    assert spans._innermost([]) == []


@pytest.mark.parametrize("span,metric", [
    ("repro.prefetch.queue_full", "train.idle_input"),
    ("repro.train.step", "train.idle_dispatch"),
    ("repro.train.hits_drain", "train.idle_drain"),
    ("repro.ckpt.publish", "train.idle_ckpt"),
    ("repro.train.job_end", "train.idle_job_edges"),
    ("repro.train.later_span", "train.idle_unattributed"),
    ("(none)", "train.idle_unattributed")])
def test_idle_part(span, metric):
    assert spans.idle_part(span) == metric


def test_no_program_spans_gives_no_metrics():
    loop = [ev("bench.window", 0, 100), ev("bench.train_job", 0, 100)]
    assert spans.summarize_planes(planes(DEV, [loop])) is None
    assert spans.metrics(None) == {}
    # a recorded chip trace of the hashing cell, from before the program
    # had spans
    assert spans.summarize(os.path.join(DATA, "minwise-hash.xplane.pb")) \
        is None


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError):
        spans.summarize_planes(planes(DEV, [LOOP[2:]]))
    with pytest.raises(ValueError):
        spans.summarize_planes(planes(DEV, [LOOP])[:1])


def test_cli_prints_the_split(tmp_path, capsys):
    assert spans.main([str(tmp_path)]) == 1
    assert spans.main([]) == 2
    assert spans.main([os.path.join(DATA, "minwise-hash.xplane.pb")]) == 0
    assert '"metrics": {}' in capsys.readouterr().out
