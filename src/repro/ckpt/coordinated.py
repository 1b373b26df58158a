"""Coordinated multi-rank checkpoints: per-rank payloads, one commit.

The gang variant of ``ckpt.checkpoint``'s durability contract.  A
``procs``-wide run checkpoints at the same shard boundary on every
rank (the logical schedule is global, so boundaries align); the step
directory then holds one CRC'd payload PER RANK plus a single step
manifest written by rank 0 — and only that manifest's atomic rename
commits the step:

  <dir>/manifest.json                 shared step index (rank-0 written)
  <dir>/step_S/rank_00000/ckpt.npz    rank 0's payload
  <dir>/step_S/rank_00000/meta.json   {"rank", "crc32", ...}
  <dir>/step_S/rank_00001/…           rank 1's payload
  <dir>/step_S/meta.json              the COMMIT RECORD: step, gang
                                      size, per-rank CRC index, plus
                                      the trainer's extra_meta
                                      (schedule + topology lineage)

Write protocol (``save_coordinated``):

  1. every rank stages its payload under ``<dir>/.stage-s<S>/
     rank_<r>`` — written to a rank-private tmp dir, fsync'd, renamed
     into the stage (atomic per rank);
  2. rank 0 polls until all ``procs`` rank payloads are present (the
     collectives keep ranks within one step of each other, so this
     barrier resolves in one boundary's worth of time; a
     ``barrier_timeout_s`` turns a genuinely dead rank into a loud
     error instead of a hang), assembles the step meta from the rank
     metas, fsyncs, then renames the whole stage to ``step_S`` and
     updates the shared manifest — the single commit point.  A crash
     anywhere before that rename (including the injected
     ``manifest_write`` rank-0 kill) leaves only a ``.stage-*``
     directory that no restore ever reads;
  3. non-zero ranks return after their payload lands — they do NOT
     wait for the commit.  If rank 0 dies mid-commit the gang dies at
     the next collective and the supervisor restarts everyone from the
     previous committed step; when the respawned gang replays back to
     that boundary each rank rename-replaces its payload in the
     leftover stage (never deleted up front — a visible stage may be a
     LIVE peer's in-flight write, and a stale payload is byte-identical
     under deterministic replay anyway) and rank 0 commits as usual.

Restore (``load_step_arrays``, reached through ``ckpt.checkpoint
.restore`` — the two layouts are interchangeable): the restoring
process prefers its OWN rank's payload; a torn/corrupt payload is
quarantined (moved aside, exactly PR 7's ring-fallback discipline) and
any other rank's valid payload is used instead — sound because the
trainer's checkpointed state is fully replicated across ranks.  Only
when EVERY rank payload fails validation does the step itself count as
corrupt and the walk falls back to the previous committed step.  A
single-process resume of a coordinated checkpoint (gang of N → 1) and
a gang resume of a plain checkpoint (1 → N) both work for the same
reason: any one payload IS the full state.
"""
from __future__ import annotations

import json
import logging
import os
import shutil
import time
from typing import Any, Optional

import numpy as np

from repro.ft import faults
from repro.perf.spans import span

log = logging.getLogger("repro.ckpt")

COORDINATED_FORMAT = 5

__all__ = ["save_coordinated", "load_step_arrays", "is_coordinated_dir",
           "COORDINATED_FORMAT"]


def _rank_name(rank: int) -> str:
    return f"rank_{rank:05d}"


def _stage_dir(root: str, step: int) -> str:
    return os.path.join(root, f".stage-s{step}")


def is_coordinated_dir(step_dir: str) -> bool:
    """A committed coordinated step: rank payloads, no top-level npz."""
    return (not os.path.exists(os.path.join(step_dir, "ckpt.npz"))
            and os.path.isdir(os.path.join(step_dir, _rank_name(0))))


def _write_rank_payload(stage: str, rank: int, step: int,
                        arrays: dict) -> None:
    """Stage one rank's CRC'd payload (its leaves on the host, as
    ``checkpoint.read_leaves`` returns them) atomically (tmp + fsync +
    rename).

    Honors the armed ``ckpt_write`` fault exactly like ``checkpoint
    .save``: a ``"torn"`` directive truncates the payload AFTER the
    rename (CRCs were recorded from the in-memory arrays, so restore
    detects the tear), then raises ``InjectedCrash``.
    """
    from repro.ckpt.checkpoint import _fsync_path, leaf_crc32

    directive = faults.on_ckpt_write(step) if faults._ACTIVE is not None \
        else None
    tmp = os.path.join(stage, f".tmp-{_rank_name(rank)}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    payload = os.path.join(tmp, "ckpt.npz")
    with span("repro.ckpt.serialize"):
        np.savez(payload, **arrays)
    with span("repro.ckpt.checksum"):
        crcs = {k: leaf_crc32(v) for k, v in arrays.items()}
    meta = {"rank": int(rank), "step": int(step),
            "n_leaves": len(arrays),
            "ckpt_format": COORDINATED_FORMAT, "crc32": crcs}
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    with span("repro.ckpt.fsync"):
        if directive == "torn":
            size = os.path.getsize(payload)
            with open(payload, "r+b") as f:
                f.truncate(max(1, int(size * 0.6)))
        else:
            _fsync_path(payload)
        final = os.path.join(stage, _rank_name(rank))
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        _fsync_path(stage)
    if directive == "torn":
        raise faults.InjectedCrash(
            f"injected torn rank-{rank} checkpoint write at step {step}")


def _rank_meta(stage: str, rank: int) -> Optional[dict]:
    try:
        with open(os.path.join(stage, _rank_name(rank),
                               "meta.json")) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError, OSError):
        return None


def save_coordinated(
    root: str,
    step: int,
    tree: Any,
    *,
    rank: int,
    procs: int,
    keep_last: int = 3,
    extra_meta: Optional[dict] = None,
    barrier_timeout_s: float = 120.0,
) -> Optional[str]:
    """One rank's half of a coordinated save; every rank of the gang
    calls it at the same boundary.  Returns the committed step dir on
    rank 0, ``None`` on other ranks (which return once their payload
    is staged)."""
    from repro.ckpt.checkpoint import (
        _fsync_path, _read_manifest, _step_dir, _write_manifest,
        read_leaves,
    )

    os.makedirs(root, exist_ok=True)
    stage = _stage_dir(root, step)
    # NO stale-stage cleanup here: ranks reach this boundary at
    # slightly different times, so a visible stage may be ANOTHER
    # rank's in-flight write for this very step — deleting it races.
    # A stage left by a gang that died at this step is harmless
    # instead: replay is deterministic, so a stale completed rank
    # payload is byte-identical to the one this attempt re-stages
    # (atomically, rename-replace) over it.
    os.makedirs(stage, exist_ok=True)
    arrays = read_leaves(tree)
    with span("repro.ckpt.write", step=step):
        _write_rank_payload(stage, rank, step, arrays)
    if rank != 0:
        return None

    # ---- rank 0: wait for the gang, then commit ----------------------
    deadline = time.monotonic() + barrier_timeout_s
    metas = {}
    while len(metas) < procs:
        for r in range(procs):
            if r not in metas:
                m = _rank_meta(stage, r)
                if m is not None:
                    metas[r] = m
        if len(metas) == procs:
            break
        if time.monotonic() > deadline:
            raise RuntimeError(
                f"coordinated checkpoint at step {step} timed out "
                f"after {barrier_timeout_s:.0f}s waiting for rank "
                f"payloads {sorted(set(range(procs)) - set(metas))} "
                f"under {stage!r}")
        time.sleep(0.01)

    n_leaves = {m["n_leaves"] for m in metas.values()}
    if len(n_leaves) != 1:
        raise RuntimeError(
            f"coordinated checkpoint at step {step} has inconsistent "
            f"rank payloads (leaf counts {sorted(n_leaves)})")
    meta = {"step": int(step), "ckpt_format": COORDINATED_FORMAT,
            "procs": int(procs), "n_leaves": n_leaves.pop(),
            "rank_crc32": {str(r): metas[r]["crc32"]
                           for r in sorted(metas)}}
    if extra_meta:
        meta.update(extra_meta)
    with open(os.path.join(stage, "meta.json"), "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())

    # the injected rank-0 death window: payloads durable, manifest not
    if faults._ACTIVE is not None:
        faults.on_manifest_write(step)

    final = _step_dir(root, step)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(stage, final)
    _fsync_path(root)

    manifest = _read_manifest(root)
    steps = sorted(set(manifest.get("steps", [])) | {int(step)})
    while len(steps) > keep_last:
        victim = steps.pop(0)
        shutil.rmtree(_step_dir(root, victim), ignore_errors=True)
    _write_manifest(root, {"steps": steps, "keep": keep_last})
    return final


# ------------------------------------------------------------ restore --

def _quarantine_rank_payload(step_dir: str, rank: int,
                             why: Exception) -> None:
    src = os.path.join(step_dir, _rank_name(rank))
    dst = src + ".quarantined"
    n = 1
    while os.path.exists(dst):
        dst = f"{src}.quarantined.{n}"
        n += 1
    log.error("rank-%d payload under %r is corrupt (%s) — quarantining "
              "to %r and falling back to another rank's replicated "
              "state", rank, step_dir, why, dst)
    try:
        os.rename(src, dst)
    except OSError:
        shutil.rmtree(src, ignore_errors=True)


def load_step_arrays(step_dir: str, *, prefer_rank: int = 0) -> dict:
    """A committed coordinated step's arrays, validated against the
    rank payload's recorded CRCs.

    Tries ``prefer_rank`` first (its payload is this process's own),
    then every other rank ascending — valid because the checkpointed
    trainer state is replicated.  The preferring process quarantines
    its OWN torn payload (moves it aside); other ranks' payloads are
    only read, never moved, so concurrent gang restores cannot race.
    Raises ``CorruptCheckpointError`` when no rank payload survives.
    """
    from repro.ckpt.checkpoint import (
        CorruptCheckpointError, _load_validated,
    )

    ranks = sorted(
        int(name[len("rank_"):]) for name in os.listdir(step_dir)
        if name.startswith("rank_") and not name.endswith(".tmp")
        and "quarantined" not in name
        and os.path.isdir(os.path.join(step_dir, name)))
    order = ([prefer_rank] if prefer_rank in ranks else []) + \
        [r for r in ranks if r != prefer_rank]
    last_err: Optional[Exception] = None
    for r in order:
        d = os.path.join(step_dir, _rank_name(r))
        try:
            return _load_validated(d, _rank_meta(step_dir, r))
        except CorruptCheckpointError as e:
            last_err = e
            if r == prefer_rank:
                _quarantine_rank_payload(step_dir, r, e)
            else:
                log.error("rank-%d payload under %r is corrupt (%s) — "
                          "trying the next rank", r, step_dir, e)
    raise CorruptCheckpointError(
        f"every rank payload under {step_dir!r} failed validation "
        f"(last: {last_err!r})")
