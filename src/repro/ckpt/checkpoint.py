"""Atomic, checksummed, manifest-tracked checkpointing (no external deps).

Layout (checkpoint format 4; format-3 directories restore unchanged):
  <dir>/manifest.json            {"steps": [100, 200, ...], "keep": 3}
  <dir>/step_00000200/ckpt.npz   leaf_00000, leaf_00001, ...
  <dir>/step_00000200/meta.json  {"step", "n_leaves", "ckpt_format": 4,
                                  "crc32": {leaf_00000: ..., ...},
                                  ...caller extra_meta (e.g. the
                                  trainer's schedule + topology
                                  lineage)}
  <dir>/quarantine/step_...      corrupt checkpoints moved aside by
                                 restore — never silently reused

Durability contract:

  * **atomic + durable publication** — leaves and metadata are written
    to ``.tmp-<step>``, fsync'd (file contents AND the directory
    entry), then ``os.rename``d into place.  A crash at any point
    leaves either the previous checkpoint set or the new one — never a
    half-visible directory;
  * **integrity** — every leaf's CRC32 is recorded in ``meta.json``;
    ``restore`` recomputes and compares, so a torn write that beat the
    fsync (or later disk corruption) is *detected*, not trained on;
  * **quarantine + fallback** — a corrupt newest checkpoint is logged,
    moved under ``<dir>/quarantine/`` and dropped from the manifest;
    ``restore`` then falls back to the newest remaining valid step (a
    ring of ``keep_last`` is retained for exactly this reason).  Only
    when *no* valid checkpoint remains does restore raise
    ``FileNotFoundError`` — the caller restarts from scratch, loudly;
  * **keep-last-M pruning** with the manifest as the single source of
    truth for which steps exist;
  * **restore-into-template** — leaves are matched positionally
    against the live pytree (params/opt_state built by model init), so
    restore works on any mesh: arrays land as host numpy and the
    trainer re-shards them (``elastic.reshard``) onto whatever device
    topology exists, enabling elastic restarts on a different device
    count.

Restart determinism is tested end-to-end: save → kill → restore →
continue produces bitwise-identical parameters to an uninterrupted run
(tests/test_checkpoint.py, tests/test_fault_tolerance.py), because the
data loader replays batches as a pure function of step.

Fault injection: when a ``repro.ft.faults.FaultPlan`` is armed, ``save``
consults the ``ckpt_write`` hook — a ``"torn"`` directive truncates the
payload after the atomic rename (the write that beat the fsync), then
raises ``InjectedCrash``; the unarmed cost is one global check.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import zlib
from typing import Any, Optional, Tuple

import jax
import numpy as np

from repro.ft import faults
from repro.perf.spans import span

CKPT_FORMAT = 4
QUARANTINE_SUBDIR = "quarantine"

log = logging.getLogger("repro.ckpt")


class CorruptCheckpointError(RuntimeError):
    """A checkpoint failed validation (unreadable npz or CRC mismatch)."""


def run_fingerprint(payload: dict) -> np.int64:
    """Stable int64 fingerprint of run-defining settings.

    JSON-canonicalized (sorted keys) SHA-256, truncated to 63 bits so it
    round-trips as a non-negative np.int64 checkpoint leaf.  A restored
    run compares the stored fingerprint against its own and refuses to
    continue on mismatch — this is how ``fit_streaming`` detects "same
    tree structure, different run semantics" (different archive,
    batching, seed, loss …).  Data-parallel runs include their LOGICAL
    world size and shard-assignment policy in ``payload`` — the batch
    schedule (hence the replayed step sequence) depends on them.  The
    PHYSICAL device count is deliberately excluded: the fold-step math
    makes the update a pure function of the logical schedule, so a
    checkpoint written on N devices may resume on M ≠ N under
    ``elastic=True``; each physical realization is recorded as a
    sanctioned topology-lineage entry in the checkpoint's ``meta.json``
    (see ``fit_streaming``) instead of being refused.
    """
    src = json.dumps(payload, sort_keys=True)
    return np.int64(
        int.from_bytes(hashlib.sha256(src.encode()).digest()[:8],
                       "big") >> 1)


def _step_dir(root: str, step: int) -> str:
    return os.path.join(root, f"step_{step:08d}")


def _manifest_path(root: str) -> str:
    return os.path.join(root, "manifest.json")


def _read_manifest(root: str) -> dict:
    try:
        with open(_manifest_path(root)) as f:
            return json.load(f)
    except FileNotFoundError:
        return {"steps": []}


def _write_manifest(root: str, manifest: dict) -> None:
    tmp = _manifest_path(root) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, _manifest_path(root))


def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def save(root: str, step: int, tree: Any, keep_last: int = 3, *,
         extra_meta: Optional[dict] = None) -> str:
    """Saves a pytree snapshot; prunes old steps; returns the step dir.

    Writes leaves + per-leaf CRC32s to a ``.tmp-<step>`` staging dir,
    fsyncs file contents and the parent directory entry, then renames
    into place — atomic AND durable.  ``extra_meta`` entries are merged
    into ``meta.json`` (readable back via ``load_meta``); the trainer
    stores its schedule + topology lineage there.
    """
    os.makedirs(root, exist_ok=True)
    arrays = read_leaves(tree)
    with span("repro.ckpt.write", step=step):
        return _write(root, step, arrays, keep_last, extra_meta)


def device_leaves(tree: Any) -> int:
    """How many leaves of ``tree`` live on a device (each one a
    device → host read when the tree is saved)."""
    return sum(isinstance(x, jax.Array) for x in jax.tree.leaves(tree))


def read_leaves(tree: Any) -> dict:
    """The leaves of ``tree`` as host arrays named ``leaf_<i>``: the
    device → host reads of a save, under ``repro.ckpt.read_device``."""
    leaves = jax.tree.leaves(tree)
    with span("repro.ckpt.read_device", n=device_leaves(tree)):
        return {f"leaf_{i:05d}": np.asarray(jax.device_get(x))
                for i, x in enumerate(leaves)}


def leaf_crc32(arr: Any) -> int:
    """CRC32 of a leaf's bytes in C order, read from its own buffer.

    The value ``meta.json`` records (checkpoint format 4), computed
    without a byte-string copy of the leaf: a C-contiguous leaf is
    checksummed in place; any other is copied once, to C order.
    """
    return zlib.crc32(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))


def _write(root: str, step: int, arrays: dict, keep_last: int,
           extra_meta: Optional[dict]) -> str:
    """Serialise, checksum, fsync, rename and prune: ``save`` after
    its leaves are on the host."""
    directive = faults.on_ckpt_write(step) if faults._ACTIVE is not None \
        else None
    tmp = os.path.join(root, f".tmp-{step}")
    os.makedirs(tmp, exist_ok=True)
    payload = os.path.join(tmp, "ckpt.npz")
    with span("repro.ckpt.serialize"):
        np.savez(payload, **arrays)
    with span("repro.ckpt.checksum"):
        crcs = {k: leaf_crc32(v) for k, v in arrays.items()}
    meta = {"step": int(step), "n_leaves": len(arrays),
            "ckpt_format": CKPT_FORMAT, "crc32": crcs}
    if extra_meta:
        meta.update(extra_meta)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    with span("repro.ckpt.fsync"):
        if directive == "torn":
            # the injected failure mode: the rename becomes durable but
            # the payload pages never hit disk — model it by truncating
            # AFTER the write, skipping the payload fsync, and
            # completing the publication below before crashing
            size = os.path.getsize(payload)
            with open(payload, "r+b") as f:
                f.truncate(max(1, int(size * 0.6)))
        else:
            _fsync_path(payload)
        final = _step_dir(root, step)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        _fsync_path(root)

    manifest = _read_manifest(root)
    steps = sorted(set(manifest.get("steps", [])) | {int(step)})
    while len(steps) > keep_last:
        victim = steps.pop(0)
        shutil.rmtree(_step_dir(root, victim), ignore_errors=True)
    _write_manifest(root, {"steps": steps, "keep": keep_last})
    if directive == "torn":
        raise faults.InjectedCrash(
            f"injected torn checkpoint write at step {step}")
    return final


def latest_step(root: str) -> Optional[int]:
    steps = _read_manifest(root).get("steps", [])
    return max(steps) if steps else None


def load_meta(root: str, step: int) -> Optional[dict]:
    """The ``meta.json`` of one checkpoint step (None if unreadable) —
    how the trainer reads back its schedule + topology lineage."""
    try:
        with open(os.path.join(_step_dir(root, step), "meta.json")) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError, OSError):
        return None


def _quarantine(root: str, step: int, why: Exception) -> None:
    qdir = os.path.join(root, QUARANTINE_SUBDIR)
    os.makedirs(qdir, exist_ok=True)
    src = _step_dir(root, step)
    dst = os.path.join(qdir, os.path.basename(src))
    n = 1
    while os.path.exists(dst):
        dst = os.path.join(qdir, f"{os.path.basename(src)}.{n}")
        n += 1
    log.error("checkpoint step %d under %r is corrupt (%s) — "
              "quarantining to %r and falling back to the newest valid "
              "checkpoint", step, root, why, dst)
    try:
        os.rename(src, dst)
    except OSError:
        shutil.rmtree(src, ignore_errors=True)
    manifest = _read_manifest(root)
    steps = [s for s in manifest.get("steps", []) if int(s) != int(step)]
    _write_manifest(root, {"steps": steps,
                           "keep": manifest.get("keep", 3)})


def _load_validated(d: str, meta: Optional[dict]) -> dict:
    """npz → {name: array}, CRC-checked when the meta records CRCs.
    Raises ``CorruptCheckpointError`` on any parse/shape/CRC failure."""
    try:
        with np.load(os.path.join(d, "ckpt.npz")) as data:
            arrays = {name: np.asarray(data[name]) for name in data.files}
    except Exception as e:  # torn zip: BadZipFile/OSError/EOF/Value…
        raise CorruptCheckpointError(f"unreadable ckpt.npz: {e!r}") from e
    crcs = (meta or {}).get("crc32")
    if crcs:  # format-3 checkpoints predate CRCs: parse-check only
        for name, arr in arrays.items():
            want = crcs.get(name)
            got = leaf_crc32(arr)
            if want is None or int(want) != got:
                raise CorruptCheckpointError(
                    f"CRC mismatch on {name} (recorded {want}, "
                    f"recomputed {got})")
    return arrays


def restore(root: str, template: Any, step: Optional[int] = None, *,
            validate: bool = True,
            fallback: Optional[bool] = None) -> Tuple[Any, int]:
    """Loads leaves into the structure of ``template``; returns
    ``(tree, step)``.

    With ``step=None`` (the default) candidates are walked newest
    first; a candidate failing validation (unreadable archive or CRC
    mismatch) is logged, quarantined under ``<root>/quarantine/`` and
    the next newest is tried (``fallback`` defaults to True here).
    When every candidate is corrupt, raises ``FileNotFoundError`` —
    same as an empty directory, so callers restart from scratch rather
    than train on garbage.  An explicitly requested ``step`` never
    falls back: corruption raises ``CorruptCheckpointError``.
    A template/leaf-count mismatch raises ``ValueError`` (structural
    incompatibility, NOT corruption — nothing is quarantined).

    A step written by a multi-process gang (``ckpt.coordinated`` —
    per-rank payloads, no top-level ``ckpt.npz``) restores through the
    same walk: this process's own rank payload is preferred, any valid
    rank's replicated payload is accepted, and only a step with NO
    valid payload counts as corrupt.  Plain and coordinated layouts
    are fully interchangeable — that is what lets a single process
    resume a gang's checkpoint (N→1) and a gang resume a
    single-process one (1→N).
    """
    if fallback is None:
        fallback = step is None
    if step is None:
        steps = sorted(_read_manifest(root).get("steps", []), reverse=True)
        if not steps:
            raise FileNotFoundError(f"no checkpoints under {root}")
    else:
        steps = [int(step)]
    last_err: Optional[Exception] = None
    for s in steps:
        d = _step_dir(root, s)
        try:
            from repro.ckpt import coordinated
            if coordinated.is_coordinated_dir(d):
                from repro.distributed.runtime import current_rank
                arrays = coordinated.load_step_arrays(
                    d, prefer_rank=current_rank())
            else:
                arrays = _load_validated(d, load_meta(root, s)
                                         if validate else None)
        except CorruptCheckpointError as e:
            last_err = e
            if not fallback:
                raise
            _quarantine(root, s, e)
            continue
        leaves_t, treedef = jax.tree.flatten(template)
        if len(leaves_t) != len(arrays):
            raise ValueError(
                f"checkpoint has {len(arrays)} leaves, template has "
                f"{len(leaves_t)} — incompatible structure")
        leaves = [np.asarray(arrays[f"leaf_{i:05d}"]).astype(
            np.asarray(leaves_t[i]).dtype).reshape(np.shape(leaves_t[i]))
            for i in range(len(leaves_t))]
        return treedef.unflatten(leaves), int(s)
    raise FileNotFoundError(
        f"no valid checkpoints under {root} (last corruption: "
        f"{last_err!r})")


def restore_if_exists(root: str, template: Any):
    try:
        return restore(root, template)
    except (FileNotFoundError, ValueError):
        return None


# --------------------------------------------------- serving handoff ----
# A training checkpoint is the FULL state (params + optimizer + stream
# position) restored against the trainer's own template; a serving
# process has none of that structure.  ``publish_params`` writes a
# params-only snapshot under <root>/serve with the same atomic-rename +
# checksum + manifest discipline, so the server side can restore it
# against nothing but its live param tree (``serving.reload``) — the
# handoff that lets a mid-run fit_streaming checkpoint go live with no
# restart.

SERVE_SUBDIR = "serve"


def publish_params(root: str, step: int, params: Any,
                   keep_last: int = 3) -> str:
    """Publish a serving-consumable params-only snapshot under
    ``<root>/serve``; returns the step dir."""
    return save(os.path.join(root, SERVE_SUBDIR), step, params,
                keep_last=keep_last)


def latest_published(root: str) -> Optional[int]:
    return latest_step(os.path.join(root, SERVE_SUBDIR))


def restore_published(root: str, template: Any,
                      step: Optional[int] = None) -> Tuple[Any, int]:
    """Restore the latest (or given-step) published params snapshot."""
    return restore(os.path.join(root, SERVE_SUBDIR), template, step)
