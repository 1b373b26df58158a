"""The streaming trainer's host spans and counters (``repro.perf.spans``,
``StreamFitResult.counters``), read back from a profiler trace taken on
the CPU: every span under its exact name, on the thread that does the
work, nested where it must be, carrying its step numbers and read
counts; the counters exact for a small archive; and the trained state
bit-identical with the profiler on and off; the counters on the
training launcher's result line.  No device time is read from these
traces."""
import ast
import collections
import glob
import os
import subprocess
import sys

import jax
import pytest

from repro.data import SynthRcv1Config, generate_arrays, preprocess_and_save
from repro.models.linear import BBitLinearConfig, init_bbit_linear
from repro.optim.optimizers import make_optimizer
from repro.train import fit_streaming, trees_bitwise_equal
from repro.train.steps import init_averaged_state

K, B, DOCS, SHARDS, BATCH, EVERY = 16, 4, 240, 4, 32, 2
LCFG = BBitLinearConfig(k=K, b=B)
KW = dict(epochs=1, batch_size=BATCH, lr=5e-3, seed=3,
          ckpt_every_shards=EVERY)

LOOP = {"repro.train.job_setup", "repro.train.step", "repro.prefetch.wait",
        "repro.train.hits_drain", "repro.ckpt.save", "repro.ckpt.read_device",
        "repro.ckpt.write", "repro.ckpt.serialize", "repro.ckpt.checksum",
        "repro.ckpt.fsync", "repro.ckpt.publish", "repro.train.job_end"}
PRODUCER = {"repro.prefetch.read", "repro.prefetch.put"}
READS = ("repro.train.hits_drain", "repro.ckpt.read_device")
WRITE_PARTS = ("repro.ckpt.serialize", "repro.ckpt.checksum",
               "repro.ckpt.fsync")


@pytest.fixture(scope="module")
def arch(tmp_path_factory):
    """240 docs in 4 shards of 60 rows: 2 steps a shard at batch 32."""
    rows, labels = generate_arrays(240, SynthRcv1Config(
        seed=5, topic_tokens=80, max_pairs_per_doc=400,
        max_triples_per_doc=200))
    root = str(tmp_path_factory.mktemp("trace") / "arch")
    preprocess_and_save(root, rows, labels, k=K, b=B, seed=1,
                        n_shards=SHARDS, chunk=64)
    return root


def _traced(arch, tmp_path, name, **kw):
    """One fit under the profiler: (result, spans by host line)."""
    log_dir = str(tmp_path / f"trace-{name}")
    with jax.profiler.trace(log_dir):
        res = fit_streaming(arch, LCFG, ckpt_dir=str(tmp_path / name),
                            **KW, **kw)
        jax.block_until_ready(res.params)
    return res, _spans(log_dir)


def _spans(log_dir):
    """The ``repro.*`` spans of a trace by host line, each span (name,
    start, end, stats)."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith("repro.")]
            if spans:
                lines.append(spans)
    return lines


@pytest.fixture(scope="module")
def threaded(arch, tmp_path_factory):
    return _traced(arch, tmp_path_factory.mktemp("threaded"), "ck",
                   prefetch=2)


@pytest.fixture(scope="module")
def inline(arch, tmp_path_factory):
    return _traced(arch, tmp_path_factory.mktemp("inline"), "ck",
                   prefetch=0)


def _names(lines):
    return [{s[0] for s in spans} for spans in lines]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("run", ["threaded", "inline"])
def test_every_span_appears_by_name(run, request):
    _, lines = request.getfixturevalue(run)
    assert set().union(*_names(lines)) == LOOP | PRODUCER


def test_setup_span_closes_when_setup_raises(arch, tmp_path):
    log_dir = str(tmp_path / "trace")
    with jax.profiler.trace(log_dir):
        # the held traceback keeps the failed call's frame alive
        with pytest.raises(ValueError, match="exceeds") as err:
            fit_streaming(arch, LCFG, **{**KW, "batch_size": DOCS})
    assert err.value is not None
    assert [s[0] for spans in _spans(log_dir) for s in spans] == [
        "repro.train.job_setup"]


def test_producer_spans_sit_on_their_own_thread(threaded, inline):
    _, lines = threaded
    names = _names(lines)
    loop = [n for n in names if "repro.train.step" in n]
    assert len(loop) == 1 and loop[0] == LOOP
    assert [n for n in names if n & PRODUCER] == [PRODUCER]
    # prefetch off: the loop reads and puts each batch itself, inside
    # its wait
    _, lines = inline
    loop, = [spans for spans in lines
             if any(s[0] == "repro.train.step" for s in spans)]
    waits = [s for s in loop if s[0] == "repro.prefetch.wait"]
    for s in loop:
        if s[0] in PRODUCER:
            assert any(_inside(s, w) for w in waits), s


@pytest.mark.parametrize("run", ["threaded", "inline"])
def test_checkpoint_spans_nest(run, request):
    _, lines = request.getfixturevalue(run)
    loop = [s for spans in lines for s in spans if s[0] in LOOP]
    saves = [s for s in loop if s[0] == "repro.ckpt.save"]
    publishes = [s for s in loop if s[0] == "repro.ckpt.publish"]
    assert len(saves) == len(publishes) == SHARDS // EVERY
    for outer in saves + publishes:
        inner = collections.Counter(s[0] for s in loop
                                    if s is not outer and _inside(s, outer))
        # a publish reads the average's count, then saves the snapshot
        want = {"repro.ckpt.read_device": 1, "repro.ckpt.write": 1,
                **{name: 1 for name in WRITE_PARTS}}
        if outer[0] == "repro.ckpt.publish":
            want["repro.ckpt.read_device"] = 2
        assert inner == want, outer
    for s in loop:
        if s[0] in ("repro.ckpt.read_device", "repro.ckpt.write"):
            assert any(_inside(s, o) for o in saves + publishes), s


@pytest.mark.parametrize("run", ["threaded", "inline"])
def test_write_spans_split_the_write(run, request):
    """Each write holds one serialize, checksum and fsync, in that
    order, and none of them runs outside a write."""
    _, lines = request.getfixturevalue(run)
    loop = [s for spans in lines for s in spans if s[0] in LOOP]
    writes = [s for s in loop if s[0] == "repro.ckpt.write"]
    assert len(writes) == 2 * (SHARDS // EVERY)    # saves and publishes
    for w in writes:
        inner = sorted((s for s in loop
                        if s is not w and _inside(s, w)),
                       key=lambda s: s[1])
        assert [s[0] for s in inner] == list(WRITE_PARTS), w
    for s in loop:
        if s[0] in WRITE_PARTS:
            assert any(_inside(s, w) for w in writes), s


@pytest.mark.parametrize("run", ["threaded", "inline"])
def test_step_spans_count_the_steps(run, request):
    res, lines = request.getfixturevalue(run)
    steps = sorted((s for spans in lines for s in spans
                    if s[0] == "repro.train.step"), key=lambda s: s[1])
    assert [s[3]["step_num"] for s in steps] == list(range(res.n_steps))
    assert res.n_steps == SHARDS * -(-(DOCS // SHARDS) // BATCH)


def _expected_counters():
    """The loop's reads and bytes for the small archive, worked out from
    its shards, steps and checkpoint leaves."""
    state = init_averaged_state(init_bbit_linear(LCFG, jax.random.key(0)),
                                make_optimizer("adamw", 5e-3))
    leaves = jax.tree.leaves(state)
    params = jax.tree.leaves(state.state.params)
    steps = SHARDS * -(-(DOCS // SHARDS) // BATCH)
    saves = SHARDS // EVERY
    # 6 host int64 leaves ride with the state: epoch, pos, shards_done,
    # hits, seen, fingerprint
    tree_bytes = sum(x.nbytes for x in leaves) + 6 * 8
    return {"device_reads": steps + saves * (len(leaves) + 1 + len(params)),
            "ckpt_saves": saves,
            "ckpt_bytes": saves * (tree_bytes
                                   + sum(x.nbytes for x in params))}


def test_counters_are_exact(threaded, inline):
    want = _expected_counters()
    events = inline[0].n_steps + SHARDS
    for res, _ in (threaded, inline):
        got = dict(res.counters)
        waits = got.pop("input_waits")
        assert got == want
        assert 0 <= waits <= events + 1
    # inline, the loop waits for every event it produces itself
    assert inline[0].counters["input_waits"] == events


@pytest.mark.parametrize("run", ["threaded", "inline"])
def test_span_reads_match_the_counter(run, request):
    res, lines = request.getfixturevalue(run)
    reads = sum(s[3]["n"] for spans in lines for s in spans
                if s[0] in READS)
    assert reads == res.counters["device_reads"]


def test_counters_without_checkpoints(arch):
    res = fit_streaming(arch, LCFG, prefetch=2, **KW)
    assert res.counters["device_reads"] == res.n_steps
    assert res.counters["ckpt_saves"] == res.counters["ckpt_bytes"] == 0


def test_profiler_changes_nothing_trained(arch, threaded, tmp_path):
    traced, _ = threaded
    plain = fit_streaming(arch, LCFG, prefetch=2, ckpt_dir=str(tmp_path),
                          **KW)
    assert trees_bitwise_equal(plain.params, traced.params)
    assert trees_bitwise_equal(plain.avg_params, traced.avg_params)
    assert plain.counters == {**traced.counters,
                              "input_waits": plain.counters["input_waits"]}
    assert plain.progressive_acc == traced.progressive_acc


def test_launcher_prints_the_counters(tmp_path):
    """``launch/train.py --mode stream`` ends its result line with the
    loop's counters: 4 shards, a checkpoint after each."""
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--mode", "stream",
         "--workdir", str(tmp_path), "--n-docs", str(DOCS), "--k", str(K),
         "--b", str(B), "--batch-size", str(BATCH)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, = [l for l in proc.stdout.splitlines() if l.startswith("streamed")]
    steps = int(line.split("steps=")[1].split()[0])
    counters = ast.literal_eval(line.split("counters=")[1])
    assert set(counters) == {"device_reads", "ckpt_saves", "ckpt_bytes",
                             "input_waits"}
    assert counters["ckpt_saves"] == SHARDS
    assert counters["device_reads"] > steps + SHARDS
    assert counters["ckpt_bytes"] > 0
