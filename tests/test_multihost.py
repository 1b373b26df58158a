"""Multi-host elastic streaming training: the crash-equivalence matrix
over REAL ``jax.distributed`` localhost gangs, plus the PR's satellite
coverage (per-rank backoff, straggler escalation, EF gradient
compression, offline archive fsck).

Every gang test spawns ``procs`` actual OS processes (``train.worker``
via ``run_multiprocess_supervised``), each its own jax runtime joined
through a localhost coordinator with gloo CPU collectives — not fake
devices in one process.  The equivalence claims lean on the
sum-then-scale reduction in ``train.data_parallel``: for power-of-two
realizations of the same logical schedule the update is bitwise
invariant, so a 2-process×1-device gang, a 1-process×1-device fold-2
run, and any kill/resume splice of the two must produce IDENTICAL
parameters.
"""
import contextlib
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.ckpt.elastic import process_fold
from repro.data.hashed_dataset import preprocess_and_save
from repro.ft.faults import FaultEvent, FaultPlan
from repro.ft.retry import BackoffPolicy
from repro.ft.watchdog import StepWatchdog
from repro.models.linear import BBitLinearConfig
from repro.distributed.runtime import (
    ProcessRuntime, heartbeat, init_runtime, process_slot_range,
    read_heartbeats,
)
from repro.train.streaming import fit_streaming
from repro.train.supervisor import (
    RestartPolicy, run_multiprocess_supervised, run_supervised,
)

K, B, N_DOCS, N_SHARDS, BATCH = 64, 8, 400, 8, 32
CFG = BBitLinearConfig(k=K, b=B)
# the shared hyperparameters of every run in the equivalence matrix
FIT = dict(epochs=1, batch_size=BATCH, data_parallel=2, elastic=True,
           prefetch=0, seed=0)
POLICY = RestartPolicy(max_restarts=3,
                       backoff=BackoffPolicy(base_s=0.05, cap_s=0.5))


def _make_archive(root, *, signal=False, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 2, size=N_DOCS)
    rows = []
    for y in labels:
        lo = int(y) * 500 if signal else 0
        width = 500 if signal else 1000
        rows.append(rng.integers(lo, lo + width,
                                 size=int(rng.integers(5, 30))).tolist())
    preprocess_and_save(root, rows, labels, k=K, b=B, scheme="oph",
                        n_shards=N_SHARDS)
    return root


def _leaves(tree):
    return [np.asarray(jax.device_get(x)) for x in jax.tree.leaves(tree)]


def _assert_matches_baseline(params_npz_path, baseline):
    got = np.load(params_npz_path)
    for i, leaf in enumerate(baseline["params"]):
        assert np.array_equal(got[f"p{i}"], leaf), f"params leaf {i}"
    for i, leaf in enumerate(baseline["avg"]):
        assert np.array_equal(got[f"a{i}"], leaf), f"avg leaf {i}"


@pytest.fixture(scope="module")
def archive(tmp_path_factory):
    return _make_archive(str(tmp_path_factory.mktemp("mh_archive")))


@pytest.fixture(scope="module")
def baseline(archive):
    """The single-process ground truth: 1 device folding both logical
    slots (the elastic path every gang topology must match bitwise)."""
    res = fit_streaming(archive, CFG, **FIT)
    return {"params": _leaves(res.params), "avg": _leaves(res.avg_params),
            "n_steps": res.n_steps, "examples_seen": res.examples_seen,
            "shards_processed": res.shards_processed,
            "progressive_acc": res.progressive_acc}


@contextlib.contextmanager
def cpu_worker_env(local_devices):
    """Gang workers inherit this process's environment: run them on
    the CPU backend with ``local_devices`` fake devices each."""
    pins = {"JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count="
                         f"{int(local_devices)}"}
    saved = {key: os.environ.get(key) for key in pins}
    os.environ.update(pins)
    try:
        yield
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


def _gang(archive, run_dir, *, procs=2, local_devices=1, fault=None,
          **overrides):
    kw = dict(FIT)
    kw.update(overrides)
    with cpu_worker_env(local_devices):
        return run_multiprocess_supervised(
            archive, CFG, procs=procs, run_dir=run_dir, policy=POLICY,
            fault_spec=fault.to_spec() if fault else None,
            ckpt_dir=os.path.join(run_dir, "ckpt"), **kw)


# ------------------------------------------------------- unit layer ----

def test_process_slot_range_contiguous_and_even():
    assert process_slot_range(8, 2, 0) == (0, 4)
    assert process_slot_range(8, 2, 1) == (4, 8)
    assert process_slot_range(2, 1, 0) == (0, 2)
    with pytest.raises(ValueError, match="evenly"):
        process_slot_range(3, 2, 0)


def test_process_fold_three_levels():
    # 8 logical slots over 2 procs x 2 local devices: 4-slot blocks,
    # 2 mesh devices per proc, fold 2 on each
    assert process_fold(8, 2, 2) == (4, 2, 4)
    # non-elastic refuses folding
    with pytest.raises(ValueError, match="elastic"):
        process_fold(8, 2, 2, elastic=False)
    assert process_fold(2, 2, 1, elastic=False) == (1, 1, 2)
    with pytest.raises(ValueError, match="evenly"):
        process_fold(3, 2, 1)


def test_init_runtime_validation():
    with pytest.raises(ValueError, match="coordinator"):
        init_runtime(procs=2, rank=0, coordinator=None)
    with pytest.raises(ValueError, match="rank"):
        init_runtime(procs=2, rank=5, coordinator="127.0.0.1:1")


def test_heartbeats_roundtrip(tmp_path):
    rt0 = ProcessRuntime(procs=2, rank=0, run_dir=str(tmp_path))
    rt1 = ProcessRuntime(procs=2, rank=1, run_dir=str(tmp_path))
    heartbeat(rt0, step=7, shards_done=3)
    heartbeat(rt1, step=7, shards_done=4, phase="ckpt")
    hb = read_heartbeats(str(tmp_path))
    assert set(hb) == {0, 1}
    assert hb[0]["shards_done"] == 3 and hb[1]["phase"] == "ckpt"
    assert read_heartbeats(str(tmp_path / "missing")) == {}


def test_backoff_for_rank_breaks_lockstep():
    base = BackoffPolicy(base_s=0.5, cap_s=10.0, jitter_frac=0.5, seed=3)
    # deterministic per (seed, rank) ...
    assert (base.for_rank(1).delay_s(0) == base.for_rank(1).delay_s(0))
    # ... shape-preserving ...
    assert base.for_rank(4).cap_s == base.cap_s
    # ... de-correlated: distinct ranks get distinct jitter streams
    delays = {base.for_rank(r).delay_s(2) for r in range(6)}
    assert len(delays) == 6
    # rank seeds come from SeedSequence, not seed+rank: neighbouring
    # base seeds must not alias neighbouring ranks
    assert (BackoffPolicy(seed=4).for_rank(0).seed
            != BackoffPolicy(seed=3).for_rank(1).seed)


# -------------------------------------------- crash-equivalence matrix --

def test_gang_matches_single_process(archive, baseline, tmp_path):
    run = _gang(archive, str(tmp_path / "gang"))
    assert run.restarts == 0
    rec = run.result
    assert rec["n_steps"] == baseline["n_steps"]
    assert rec["examples_seen"] == baseline["examples_seen"]
    assert rec["shards_processed"] == baseline["shards_processed"]
    assert rec["progressive_acc"] == pytest.approx(
        baseline["progressive_acc"])
    assert rec["lineage"][-1]["procs"] == 2
    # both ranks trained the identical replicated model, and it is
    # bitwise the single-process fold-2 model
    _assert_matches_baseline(run.params_paths[0], baseline)
    _assert_matches_baseline(run.params_paths[1], baseline)
    # boundary heartbeats landed for both ranks
    hb = read_heartbeats(str(tmp_path / "gang"))
    assert set(hb) == {0, 1}


def test_gang_worker_kill9_recovers_bitwise(archive, baseline, tmp_path):
    # kill -9 the NON-leader mid-epoch: a real SIGKILL, no cleanup
    plan = FaultPlan([FaultEvent(site="proc_kill", step=5, rank=1,
                                 times=1)])
    run = _gang(archive, str(tmp_path / "gang"), fault=plan)
    assert run.restarts == 1
    assert "signal 9" in run.crashes[0].error
    assert run.result["n_steps"] == baseline["n_steps"]
    _assert_matches_baseline(run.params_paths[0], baseline)
    _assert_matches_baseline(run.params_paths[1], baseline)


def test_gang_leader_killed_during_manifest_commit(archive, baseline,
                                                   tmp_path):
    # rank 0 dies AFTER all rank payloads are staged, BEFORE the step
    # manifest commits — the torn-coordination window; the previous
    # committed step must stay authoritative and the replay must splice
    # bit-exactly
    plan = FaultPlan([FaultEvent(site="manifest_write", at_save=4,
                                 rank=0, times=1)])
    run = _gang(archive, str(tmp_path / "gang"), fault=plan)
    assert run.restarts >= 1
    assert run.result["shards_processed"] == baseline["shards_processed"]
    _assert_matches_baseline(run.params_paths[0], baseline)


def test_gang_torn_rank_payload_quarantined(archive, baseline, tmp_path):
    # rank 1's payload is torn AFTER its rename (CRCs recorded from
    # memory): the commit succeeds, the respawned rank 1 must detect
    # the tear on restore, quarantine its OWN payload and fall back to
    # rank 0's replicated copy
    plan = FaultPlan([FaultEvent(site="ckpt_write", at_save=2, rank=1,
                                 times=1)])
    run_dir = str(tmp_path / "gang")
    run = _gang(archive, run_dir, fault=plan)
    assert run.restarts == 1
    _assert_matches_baseline(run.params_paths[0], baseline)
    _assert_matches_baseline(run.params_paths[1], baseline)
    # the injected tear actually fired on attempt 0 ...
    logs = [os.path.join(run_dir, f) for f in os.listdir(run_dir)
            if f.startswith("log_rank1")]
    text = "".join(open(p, errors="replace").read() for p in logs)
    assert "injected torn rank-1 checkpoint write" in text
    # ... and the respawned rank 1 quarantined its OWN payload before
    # falling back to rank 0's replicated copy.  (The quarantined
    # directory itself is later removed with its step by keep_last
    # pruning, so the restore-time log is the durable evidence.)
    assert "quarantining" in text and "rank_00001.quarantined" in text


def test_elastic_gang_to_single_process(archive, baseline, tmp_path):
    # 2-process gang checkpoints mid-run; a 1-process run adopts the
    # coordinated checkpoint and finishes — N -> M (M < N) elastic
    # process resume, bit-identical with exact counter continuity
    run_dir = str(tmp_path / "gang")
    part = _gang(archive, run_dir, stop_after_shards=4)
    assert part.result["completed"] is False
    assert part.result["shards_processed"] == 4
    res = fit_streaming(archive, CFG,
                        ckpt_dir=os.path.join(run_dir, "ckpt"), **FIT)
    assert res.completed and res.shards_processed == N_SHARDS
    assert res.examples_seen == baseline["examples_seen"]
    assert res.n_steps == baseline["n_steps"]
    for got, want in zip(_leaves(res.params), baseline["params"]):
        assert np.array_equal(got, want)
    for got, want in zip(_leaves(res.avg_params), baseline["avg"]):
        assert np.array_equal(got, want)
    # the lineage names both realizations, oldest first
    procs_seen = [r["procs"] for r in res.topology_lineage]
    assert procs_seen == [2, 1]
    # a non-elastic resume across gang sizes must refuse loudly
    with pytest.raises(ValueError, match="elastic=True"):
        fit_streaming(archive, CFG,
                      ckpt_dir=os.path.join(run_dir, "ckpt"),
                      **{**FIT, "elastic": False})


def test_elastic_single_process_to_gang(archive, baseline, tmp_path):
    # the reverse splice: a single-process run checkpoints (plain
    # layout) mid-run; a 2-process gang adopts it and finishes —
    # 1 -> N elastic process resume over the SAME checkpoint directory
    run_dir = str(tmp_path / "gang")
    ckpt_dir = os.path.join(run_dir, "ckpt")
    part = fit_streaming(archive, CFG, ckpt_dir=ckpt_dir,
                         stop_after_shards=4, **FIT)
    assert part.completed is False and part.shards_processed == 4
    run = _gang(archive, run_dir)
    rec = run.result
    assert rec["completed"] and rec["shards_processed"] == N_SHARDS
    assert rec["n_steps"] == baseline["n_steps"]
    assert rec["examples_seen"] == baseline["examples_seen"]
    _assert_matches_baseline(run.params_paths[0], baseline)
    procs_seen = [r["procs"] for r in rec["lineage"]]
    assert procs_seen == [1, 2]


def test_gang_two_by_two_deterministic(archive, tmp_path):
    # 2 procs x 2 fake devices: a 4-way reduction is not bitwise equal
    # to the 2-way baseline (float add is non-associative across a
    # different reduction tree), so THIS topology's claim is
    # determinism within the fixed topology + rank agreement
    r1 = _gang(archive, str(tmp_path / "g1"), local_devices=2,
               data_parallel=4)
    r2 = _gang(archive, str(tmp_path / "g2"), local_devices=2,
               data_parallel=4)
    a0, a1 = np.load(r1.params_paths[0]), np.load(r1.params_paths[1])
    b0 = np.load(r2.params_paths[0])
    for key in a0.files:
        assert np.array_equal(a0[key], a1[key])   # ranks agree
        assert np.array_equal(a0[key], b0[key])   # runs agree
    assert r1.result["lineage"][-1] == {
        "logical": 4, "physical": 4, "procs": 2, "devices": 4,
        "from_step": 0}


# ------------------------------------------------------- satellites ----

def test_straggler_escalation_counted(archive, tmp_path):
    # two consecutive injected 0.3s steps against a ~ms median must
    # escalate; the counter surfaces on SupervisedRun
    from repro.ft import faults

    plan = FaultPlan([
        FaultEvent(site="slow_step", step=s, delay_s=0.3, times=1)
        for s in (10, 11)])
    wd = StepWatchdog(threshold=3.0, window=16, escalate_after=2)
    with faults.arm(plan):
        sup = run_supervised(
            archive, CFG, policy=POLICY, watchdog=wd,
            ckpt_dir=str(tmp_path / "ckpt"),
            **{**FIT, "epochs": 2})
    assert sup.result.completed
    assert sup.straggler_escalations >= 1
    assert sup.restarts == 0


def test_grad_compress_parity_and_off_bitwise(tmp_path):
    # a separable corpus (class-disjoint token ranges): the exact run
    # learns it, and the int8 EF-compressed all-reduce must track it
    root = _make_archive(str(tmp_path / "sig"), signal=True, seed=1)
    exact = fit_streaming(root, CFG, **{**FIT, "epochs": 2})
    off = fit_streaming(root, CFG, grad_compress=None,
                        **{**FIT, "epochs": 2})
    # grad_compress=None IS the exact path, bitwise
    for got, want in zip(_leaves(off.params), _leaves(exact.params)):
        assert np.array_equal(got, want)
    comp = fit_streaming(root, CFG, grad_compress=8,
                         **{**FIT, "epochs": 2})
    assert exact.progressive_acc > 0.8
    assert comp.progressive_acc >= exact.progressive_acc - 0.05
    # engaged (different numerics) but deterministic
    assert not all(
        np.array_equal(a, b) for a, b in
        zip(_leaves(comp.params), _leaves(exact.params)))
    comp2 = fit_streaming(root, CFG, grad_compress=8,
                          **{**FIT, "epochs": 2})
    for got, want in zip(_leaves(comp2.params), _leaves(comp.params)):
        assert np.array_equal(got, want)
    # compression without a gradient all-reduce is a config error
    with pytest.raises(ValueError, match="data_parallel"):
        fit_streaming(root, CFG, grad_compress=8, epochs=1,
                      batch_size=BATCH)
    with pytest.raises(ValueError, match="grad_compress"):
        fit_streaming(root, CFG, grad_compress=4, **FIT)


def test_fsck_clean_corrupt_quarantine(tmp_path, capsys):
    from repro.launch.fsck import fsck_archive, main

    root = _make_archive(str(tmp_path / "arch"), seed=2)
    assert main([root]) == 0
    report = fsck_archive(root)
    assert report["verified"] == N_SHARDS and not report["corrupt"]

    # flip bytes deep inside shard 3's packed codes
    victim = os.path.join(root, "hashed_00003.codes.npy")
    with open(victim, "r+b") as f:
        f.seek(-16, os.SEEK_END)
        f.write(b"\xff" * 8)
    assert main([root]) == 1
    report = fsck_archive(root)
    assert 3 in report["corrupt"] and report["verified"] == N_SHARDS - 1

    report = fsck_archive(root, quarantine=True)
    assert report["quarantined"][3]
    assert not os.path.exists(victim)
    assert all(os.path.exists(p) for p in report["quarantined"][3])
    # a directory without meta.json is not an archive
    assert main([str(tmp_path / "nothing")]) == 2


def test_multiprocess_requires_dp_and_ckpt(archive, tmp_path):
    with pytest.raises(ValueError, match="ckpt_dir"):
        run_multiprocess_supervised(archive, CFG, procs=2,
                                    run_dir=str(tmp_path), **FIT)
    rt = ProcessRuntime(procs=2, rank=0)
    with pytest.raises(ValueError, match="data_parallel"):
        fit_streaming(archive, CFG, runtime=rt, epochs=1,
                      batch_size=BATCH)


def test_multiprocess_refused_on_chip_backend(archive, tmp_path,
                                               monkeypatch):
    """A one-host gang cannot share chips this process already holds:
    on any non-CPU backend the launcher points at data_parallel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="--data-parallel 2"):
        run_multiprocess_supervised(
            archive, CFG, procs=2, run_dir=str(tmp_path),
            ckpt_dir=str(tmp_path / "ckpt"), **FIT)
