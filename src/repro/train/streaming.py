"""One-pass / multi-epoch streaming trainer over hashed shard archives.

The paper's headline scenario trains on 200 GB — data that never fits
in memory.  Preprocessing has streamed since PR 2 (``HashedShardWriter``
writes format-v3 packed shards in O(one shard) memory); PR 3 made the
TRAINING side stream; PR 4 makes it saturate the hardware, closing the
loop arXiv:1205.2958 §5 draws against VW's online mode:

  * ``fit_streaming`` iterates the archive one shard at a time through
    ``data.hashed_dataset.iter_hashed_batches`` (minibatches sliced
    off mmap'd packed bytes — the full (n, k) code matrix is never
    materialized, resident memory is one shard's packed pages + one
    minibatch);
  * **async prefetch** (``prefetch`` ≥ 1, the default): all host-side
    batch work — mmap fault-in, shuffle, slice, jax transfer — runs in
    a bounded producer thread ``prefetch`` steps ahead of the device
    (``data.prefetch``, the producer→queue→device pipeline).  The
    determinism contract: prefetch depth changes WHEN host work
    happens, never WHAT is produced — results are bit-identical to the
    inline path (``prefetch=0``) and checkpoints are interchangeable
    across depths;
  * minibatches cross the host↔device boundary PACKED — ceil(k·b/8)
    bytes per row — and stay packed into the forward:
    ``models.linear.bbit_logits_packed`` unpacks b-bit codes
    in-register on the kernel path (Pallas, TPU) or as a fused in-jit
    temporary elsewhere; ``oph_zero`` archives feed their packed empty
    bitmask to the same fused kernels;
  * **data parallelism** (``data_parallel=N``): the epoch's shard
    order is split into consecutive groups of N, one shard per device
    of a 1-D ``("data",)`` mesh; the averaged step runs under
    ``shard_map`` with a ``psum_mean`` gradient all-reduce and a
    ``psum`` over the progressive-validation hit counters
    (``train.data_parallel``).  Uneven groups are safe: a device
    holding fewer batches (or no shard) contributes zero-weight
    padding batches, keeping every collective full-strength while the
    global row-weighted mean gradient — and hence the Polyak average —
    stays exact.  The checkpoint fingerprint records the LOGICAL
    world size and shard-assignment policy; the physical device count
    is a sanctioned lineage record instead (see elastic resume below);
  * **elastic resume** (``elastic=True``): ``data_parallel=N`` is the
    LOGICAL schedule — N shard slots per group — while the PHYSICAL
    mesh uses whatever devices are alive
    (``ckpt.elastic.mesh_from_available_devices`` /
    ``physical_data_world``), each device folding
    ``N / physical`` slots sequentially
    (``train.data_parallel``'s fold step).  Because the gradient is
    scaled AFTER the all-reduce by exact power-of-two factors, a run
    checkpointed on N devices restores on M ≠ N bit-identically; each
    physical realization is appended to a topology-lineage record in
    the checkpoint's meta.json, and resume adopts the checkpoint's
    logical schedule rather than refusing.  Restored host arrays are
    placed back on the live mesh with ``ckpt.elastic.reshard``;
  * **durability** (PR 7): checkpoints are atomic (tmp + fsync +
    rename), CRC32-checksummed per leaf, and retained as a ring; on
    restore a torn/corrupt checkpoint is logged, quarantined and the
    newest valid one used instead — only when none survives does the
    run restart from scratch (loudly).  Shard reads retry transient
    I/O errors with bounded backoff; a dead prefetch producer
    surfaces as an exception, never a hang.  Armed
    ``repro.ft.faults`` plans can inject crashes / slow steps
    (``on_train_step``) deterministically; ``train.supervisor``
    restarts the run from the latest valid checkpoint under a capped
    backoff policy — an injected-crash supervised run ends with
    params bit-identical to an uninterrupted one
    (tests/test_fault_tolerance.py);
  * the update is plain minibatch SGD/AdamW through the existing
    ``build_train_step`` machinery, wrapped with Polyak *tail*
    averaging (``optim.averaging``) — the averaged iterate is the
    VW-style online baseline;
  * **progressive validation**: every example is scored with the
    current model BEFORE its gradient step, so ``progressive_acc`` is
    the honest one-pass generalization estimate VW reports online;
  * shard order is reshuffled and every shard's rows re-permuted each
    epoch, both as pure functions of ``(seed, epoch, shard)``
    (``data.prefetch.shard_order``) — so a restarted run replays
    identical batches;
  * ``ckpt_dir`` checkpoints the FULL ``AveragedTrainState`` + stream
    position at shard(-group) boundaries through ``ckpt.checkpoint``;
    a killed run resumes at the boundary and reproduces the
    uninterrupted run bit-for-bit (tested, serial and data-parallel).

Typical use::

    stats = preprocess_and_save(root, rows, labels, k=256, b=8,
                                scheme="oph", n_shards=64)
    res = fit_streaming(root, BBitLinearConfig(k=256, b=8),
                        epochs=1, batch_size=1024,
                        ckpt_dir=root + "/ckpt")
    w = res.eval_params            # Polyak average (or raw iterate)
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.ckpt import checkpoint as ckpt
from repro.ckpt import coordinated
from repro.ckpt.elastic import (
    mesh_from_available_devices, physical_data_world, process_fold,
    replicate_spec_tree, reshard,
)
from repro.core.bbit import packed_mask_width, packed_width
from repro.data.hashed_dataset import _read_meta, shard_row_counts
from repro.ft import faults
from repro.data.prefetch import (
    Boundary, InlineStream, StreamBatch, ThreadedPrefetcher,
    group_batch_stream, serial_batch_stream, shard_order,
)
from repro import perf
from repro.perf.spans import span, step_span
from repro.models.linear import (
    BBitLinearConfig, bbit_logits_packed, init_bbit_linear,
    logits_packed_impl,
)
from repro.optim.averaging import average_or_none
from repro.optim.optimizers import make_optimizer
from repro.distributed.runtime import (
    SHARD_OWNERSHIP, ProcessRuntime, current_runtime, heartbeat,
    mesh_over_processes, process_slot_range, replicate_across_processes,
)
from repro.train.data_parallel import (
    build_dp_averaged_train_step, device_put_process_local,
    device_put_sharded,
)
from repro.train.losses import mean_loss_with_preds_fn, sum_loss_with_hits_fn
from repro.train.steps import build_averaged_train_step, init_averaged_state


# jitted step functions keyed by their semantic parameters (mode,
# world, model config, mask presence, loss, optimizer, lr, l2) — see
# fit_streaming.  Each entry's jit cache pins its compiled executables,
# so the cache is FIFO-capped: a hyperparameter sweep wider than the
# cap just recompiles (the pre-cache behavior) instead of growing
# process memory without bound.
_STEP_CACHE: dict = {}
_STEP_CACHE_MAX = 8


@dataclasses.dataclass
class StreamFitResult:
    params: Any                    # final SGD iterate
    avg_params: Optional[Any]      # Polyak tail average (None if unused)
    train_seconds: float
    progressive_acc: float         # one-pass accuracy, VW-style
    n_steps: int
    examples_seen: int
    shards_processed: int          # cumulative, survives resume
    completed: bool                # False when stop_after_shards hit
    # every (logical, physical) realization this run has trained
    # under, oldest first — the sanctioned topology-lineage record
    # also stored in each checkpoint's meta.json
    topology_lineage: list = dataclasses.field(default_factory=list)
    # what the cost-model dispatch actually ran (impl per op + profile
    # identity) — recorded in each checkpoint's meta.json extras too,
    # NOT in the replay fingerprint (a profile swap must not invalidate
    # a resume; the numerics are impl-invariant within tolerance and
    # bit-identical on the packed-kernel/unpack pair used here)
    dispatch: Optional[dict] = None
    # what the loop did at its boundaries: ``device_reads`` (arrays read
    # device → host: hit drains, checkpoint and published leaves, the
    # publish's average-count read), ``ckpt_saves``, ``ckpt_bytes``
    # (leaf bytes of checkpoints and published snapshots) and
    # ``input_waits`` (next batch not ready when the loop asked)
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def eval_params(self) -> Any:
        """The parameters to evaluate/serve: the averaged iterate when
        tail averaging ran, else the raw final iterate."""
        return self.avg_params if self.avg_params is not None else self.params


def _leaf_bytes(tree: Any) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _planned_steps(counts, batch_size: int, *, epochs: int, seed: int,
                   shuffle: bool, world: int) -> int:
    """Total train steps the full run will take.

    Per group of ``world`` shards the devices run in lockstep for the
    LONGEST member, so each group costs max_d ceil(rows_d/B) — and
    because the grouping follows the per-epoch shard shuffle, each
    epoch's count depends on that epoch's order.  ``world=1`` (groups
    of one shard) reduces exactly to the serial Σ_shards ceil(rows/B),
    computed by the shuffle-independent short-cut.
    """
    n_shards = len(counts)
    ceil = [-(-c // batch_size) for c in counts]
    if world == 1:
        return epochs * sum(ceil)
    total = 0
    for epoch in range(epochs):
        order = shard_order(seed, epoch, n_shards, shuffle)
        for lo in range(0, n_shards, world):
            total += max(ceil[int(s)] for s in order[lo: lo + world])
    return total


def fit_streaming(
    root: str,
    cfg: BBitLinearConfig,
    *,
    loss: str = "logistic",
    optimizer: str = "adamw",
    lr: float = 1e-2,
    l2: float = 1e-6,
    epochs: int = 1,
    batch_size: int = 256,
    seed: int = 0,
    average: bool = True,
    avg_start_frac: float = 0.5,
    shuffle_shards: bool = True,
    mmap: bool = True,
    prefetch: int = 2,
    data_parallel: Optional[int] = None,
    elastic: bool = False,
    max_devices: Optional[int] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every_shards: int = 1,
    ckpt_keep_last: int = 3,
    resume: bool = True,
    stop_after_shards: Optional[int] = None,
    watchdog: Optional[Any] = None,
    runtime: Optional[ProcessRuntime] = None,
    grad_compress: Optional[int] = None,
    ckpt_barrier_timeout_s: float = 120.0,
) -> StreamFitResult:
    """Streams a format-v1/2/3 hashed archive through minibatch SGD.

    ``prefetch`` is the async pipeline depth: host-side batch assembly
    and jax transfer run that many steps ahead of the device in a
    background thread (0 = inline/serial; results are bit-identical
    either way).  ``data_parallel=N`` is the LOGICAL world: N disjoint
    shard slots per step with a ``psum_mean`` gradient all-reduce (see
    ``train.data_parallel``).  Without ``elastic`` it must equal the
    physical device count and the checkpoint fingerprint pins it, so a
    resume on a different topology fails loudly; with ``elastic=True``
    the N slots fold onto whatever devices are alive (bit-identically
    — power-of-two counts), a checkpointed run resumes on M ≠ N
    devices by adopting the checkpoint's logical schedule, and each
    physical realization is appended to the checkpoint's
    topology-lineage record (meta.json, ``StreamFitResult
    .topology_lineage``).  ``max_devices`` caps the devices the slots
    fold onto (default: every visible device).  ``watchdog`` (a
    ``ft.watchdog.StepWatchdog``) observes per-step dispatch latency
    and escalates
    persistent stragglers; ``ckpt_keep_last`` sizes the retained
    checkpoint ring (the fallback set when the newest checkpoint is
    torn/corrupt — see ``ckpt.checkpoint``'s durability contract).
    ``avg_start_frac`` opens the Polyak
    tail-averaging window after that fraction of the planned total
    steps (0.0 = average from the first step; ignored when
    ``average=False``).  ``stop_after_shards`` (requires ``ckpt_dir``)
    processes at most that many shards IN THIS CALL (rounded up to a
    whole group under data parallelism), checkpoints and returns with
    ``completed=False`` — the deterministic "kill" used by the resume
    tests and benchmarks; call again with the same arguments to
    continue.  Resume requires the same archive and hyperparameters;
    the checkpoint stores the full averaged train state plus stream
    position and progressive-validation counters, so the continued run
    is bit-identical to an uninterrupted one.

    **Multi-process gangs**: under an initialized
    ``distributed.runtime`` (``runtime`` defaults to
    ``current_runtime()``) the ``data_parallel`` logical slots split
    into one contiguous block per process
    (``runtime.process_slot_range``) — each rank STREAMS only its own
    shards while the step-count/boundary bookkeeping stays global, so
    every rank takes the identical step sequence and the two
    all-reduces simply span the gang's mesh
    (``runtime.mesh_over_processes``).  Checkpoints become coordinated
    (``ckpt.coordinated``): every rank writes its own CRC'd payload
    into a staging directory and rank 0 commits the step with an
    atomic rename once all ``procs`` payloads landed
    (``ckpt_barrier_timeout_s`` bounds the wait).  Elastic resume
    extends across gang sizes: an N-process checkpoint resumes on
    M ≠ N processes (including 1) under ``elastic=True`` by adopting
    the checkpoint's logical schedule — bit-identically for
    power-of-two realizations — with the gang size appended to the
    topology lineage, never refused.

    ``grad_compress`` (8 or 1, data-parallel only) swaps the exact
    fp32 gradient all-reduce for the error-feedback compressed
    exchange (``distributed.grad_compression`` — int8 blockwise-absmax
    or sign+scale on the wire).  It changes the trained numerics (and
    so is part of the run fingerprint); ``None`` (default) leaves the
    exact path bitwise untouched.  The residual memory is NOT
    checkpointed — it resets to zero on resume, so compressed runs
    trade the bitwise-resume guarantee for bandwidth.

    The call records host spans in the profiler's trace when one is
    being taken (``repro.perf.spans``; docs/DESIGN.md "Tracing"):
    ``repro.train.job_setup`` up to the first step, one
    ``repro.train.step`` per step, the boundary's hit drain,
    checkpoint and publish, and ``repro.train.job_end``.
    """
    with span("repro.train.job_setup"):
        meta = _read_meta(root)
        if meta.get("shards", 0) <= 0 or meta.get("n", 0) <= 0:
            raise ValueError(
                f"cannot stream-train on an empty archive at {root!r} "
                f"(n={meta.get('n')}, shards={meta.get('shards')})")
        k, b = meta["k"], meta["b"]
        if (cfg.k, cfg.b) != (k, b):
            raise ValueError(
                f"config (k={cfg.k}, b={cfg.b}) does not match archive "
                f"(k={k}, b={b})")
        if epochs < 1 or batch_size < 1 or ckpt_every_shards < 1:
            raise ValueError(
                "epochs, batch_size and ckpt_every_shards must be >= 1")
        if prefetch < 0:
            raise ValueError(f"prefetch depth must be >= 0, got {prefetch}")
        if cfg.n_classes != 2 and loss != "softmax":
            raise ValueError(
                f"loss={loss!r} is binary-only; multiclass streaming "
                "(n_classes > 2) requires loss='softmax'")
        if cfg.n_classes == 2 and loss == "softmax":
            # a single-logit softmax is identically zero loss — the run
            # would "succeed" with untrained params
            raise ValueError(
                "loss='softmax' needs n_classes > 2; binary configs use a "
                "margin loss ('logistic', 'hinge', 'squared_hinge')")
        if stop_after_shards is not None and not ckpt_dir:
            raise ValueError(
                "stop_after_shards without ckpt_dir would discard the "
                "partial run — a repeat call could only restart from "
                "scratch, never continue")

        counts = shard_row_counts(root)
        n_shards = len(counts)
        small = [i for i, c in enumerate(counts) if 0 < c < batch_size]
        if small:
            raise ValueError(
                f"batch_size={batch_size} exceeds the "
                f"{min(counts[i] for i in small)} rows of shard(s) "
                f"{small[:4]}{'…' if len(small) > 4 else ''} in {root!r}"
                " — lower batch_size or re-shard the archive with fewer "
                "shards")

        # ``data_parallel`` names the LOGICAL schedule; the physical mesh
        # (and the step function) are built only after a possible elastic
        # adoption of a checkpoint's schedule below.
        dp = data_parallel is not None
        logical = int(data_parallel) if dp else 1

        rt = runtime if runtime is not None else (current_runtime()
                                                  or ProcessRuntime())
        procs = rt.procs
        if procs > 1:
            if not dp:
                raise ValueError(
                    f"a {procs}-process gang requires data_parallel — the "
                    "serial schedule has no shard slots to split across "
                    "processes")
            # validates logical % procs up front (the stream, mesh and
            # checkpoint protocol all assume even contiguous blocks)
            process_slot_range(logical, procs, rt.rank)
        if grad_compress is not None:
            if not dp:
                raise ValueError(
                    "grad_compress applies to the data-parallel gradient "
                    "all-reduce — pass data_parallel")
            if grad_compress not in (1, 8):
                raise ValueError(
                    f"grad_compress must be 8 (int8 blockwise) or 1 "
                    f"(sign+scale), got {grad_compress}")
        compress = (None if grad_compress is None
                    else {"bits": int(grad_compress), "block": 256})

        # oph_zero archives carry a packed per-row empty bitmask; batches
        # then travel as (codes_bytes, mask_bytes) tuples.  v3 answers this
        # from the filesystem, older formats from the recorded scheme —
        # neither touches shard data.
        if meta["format_version"] >= 3:
            has_empty = os.path.exists(
                os.path.join(root, "hashed_00000.empty.npy"))
        else:
            has_empty = meta.get("scheme") == "oph_zero"

        # packed bytes straight into the forward — in-register unpack on
        # the kernel path, a fused in-jit temporary elsewhere; the host
        # never widens anything.
        def fwd(params, batch):
            if has_empty:
                pk, em = batch
                return bbit_logits_packed(params, pk, cfg, empty_packed=em)
            return bbit_logits_packed(params, batch, cfg)

        opt = make_optimizer(optimizer, lr)

        astate = init_averaged_state(
            init_bbit_linear(cfg, jax.random.key(seed)), opt)
        epoch0, pos0, shards_done, hits, seen = 0, 0, 0, 0, 0
        if (ckpt_dir and not resume
                and ckpt.latest_step(ckpt_dir) is not None):
            # a fresh run's low step numbers would be pruned under the old
            # run's higher ones, and a later resume would silently pick up
            # the stale run — refuse rather than interleave two runs
            raise ValueError(
                f"ckpt_dir {ckpt_dir!r} already holds checkpoints (latest "
                f"step {ckpt.latest_step(ckpt_dir)}); with resume=False "
                "point at a fresh directory or delete the old run first")
        restored_tree = None
        restored_step = None
        prior_lineage: list = []
        if ckpt_dir and resume and ckpt.latest_step(ckpt_dir) is not None:
            template = {"astate": astate, "epoch": np.int64(0),
                        "pos": np.int64(0), "shards_done": np.int64(0),
                        "hits": np.int64(0), "seen": np.int64(0),
                        "fingerprint": np.int64(0)}
            try:
                restored_tree, restored_step = ckpt.restore(ckpt_dir,
                                                            template)
            except FileNotFoundError:
                # every retained checkpoint failed validation: restore
                # quarantined each one (loudly, see ckpt.checkpoint) — the
                # only honest continuation is a fresh start from scratch
                restored_tree = None
            except ValueError as e:
                # restarting from scratch here would silently discard the
                # run the caller believes they are continuing
                raise ValueError(
                    f"checkpoint under {ckpt_dir!r} is incompatible with "
                    "this run's model/optimizer state (resume requires the "
                    f"same archive and hyperparameters): {e}") from e
        if restored_tree is not None:
            smeta = ckpt.load_meta(ckpt_dir, restored_step) or {}
            sched = smeta.get("schedule")
            if sched is not None:
                ck_dp = bool(sched.get("dp"))
                ck_logical = int(sched.get("logical_world", 1))
                ck_procs = int(sched.get("procs", 1))
                if ck_procs != procs and not elastic:
                    raise ValueError(
                        f"checkpoint under {ckpt_dir!r} was written by a "
                        f"{ck_procs}-process gang but this run has {procs} "
                        "process(es) — pass elastic=True to resume across "
                        "gang sizes")
                if (ck_dp, ck_logical) != (dp, logical):
                    if not elastic:
                        raise ValueError(
                            f"checkpoint under {ckpt_dir!r} is incompatible:"
                            " it was written under "
                            + (f"data_parallel={ck_logical}" if ck_dp
                               else "the serial schedule")
                            + " but this run requested "
                            + (f"data_parallel={logical}" if dp
                               else "the serial schedule")
                            + " — pass elastic=True to adopt the "
                            "checkpoint's logical schedule on the current "
                            "devices")
                    dp, logical = ck_dp, ck_logical
            prior_lineage = list(smeta.get("lineage", []))

        total_steps = _planned_steps(
            counts, batch_size, epochs=epochs, seed=seed,
            shuffle=shuffle_shards, world=logical)
        avg_start_step = (int(math.floor(avg_start_frac * total_steps))
                          if average else total_steps + 1)

        # a structural restore can succeed while the run semantics differ
        # (same model/optimizer shapes, different archive/batching/seed/
        # logical schedule) — fingerprint everything replay depends on and
        # refuse a mismatch.  prefetch depth is deliberately EXCLUDED: it
        # never changes the replayed step sequence, so checkpoints are
        # interchangeable across depths; the PHYSICAL device count is too
        # (the fold step makes the update a function of the logical
        # schedule alone) — it lives in the meta.json lineage record, not
        # the fingerprint.
        fingerprint = ckpt.run_fingerprint(
            {"archive": {"n": meta["n"], "shards": n_shards, "k": k, "b": b,
                         "scheme": meta.get("scheme"),
                         "seed": meta.get("seed")},
             "cfg": dataclasses.asdict(cfg),
             "loss": loss, "optimizer": optimizer, "lr": lr, "l2": l2,
             "epochs": epochs, "batch_size": batch_size, "seed": seed,
             "average": average, "avg_start_step": avg_start_step,
             "shuffle_shards": shuffle_shards,
             "world": logical,
             "shard_assignment": ("contiguous_groups" if dp else "serial"),
             # the slot→process mapping RULE is replay-relevant (a
             # different ownership policy would stream different shards per
             # rank); the gang SIZE is not — like the physical device
             # count it rides the lineage record, so checkpoints resume
             # across gang sizes
             "process_topology": {"shard_ownership": SHARD_OWNERSHIP},
             "grad_compress": (int(grad_compress) if grad_compress
                               else None)})

        if restored_tree is not None:
            if int(restored_tree["fingerprint"]) != int(fingerprint):
                raise ValueError(
                    f"checkpoint under {ckpt_dir!r} is incompatible: it was "
                    "written by a run with different hyperparameters, a "
                    "different archive, or a different data-parallel "
                    "topology (fingerprint mismatch) — resume requires "
                    "identical settings")
            astate = restored_tree["astate"]
            epoch0 = int(restored_tree["epoch"])
            pos0 = int(restored_tree["pos"])
            shards_done = int(restored_tree["shards_done"])
            hits = int(restored_tree["hits"])
            seen = int(restored_tree["seen"])

        d_local = 1
        if dp:
            n_dev = len(jax.devices())
            if max_devices is not None:
                n_dev = min(n_dev, int(max_devices))
            if procs > 1:
                # three-level fold: logical slots → per-process contiguous
                # blocks → per-device fold within each process
                _, d_local, physical = process_fold(
                    logical, procs, rt.local_devices, elastic=elastic)
                mesh = mesh_over_processes(d_local)
            else:
                if not elastic and logical > n_dev:
                    raise ValueError(
                        f"data_parallel={logical} needs {logical} devices "
                        f"but only {n_dev} are visible — pass elastic=True "
                        "to fold the logical shard slots onto the "
                        "available devices")
                physical = (physical_data_world(logical, n_dev) if elastic
                            else logical)
                mesh = mesh_from_available_devices(model_parallel=1,
                                                   max_devices=physical)
            if procs > 1:
                # a gang mesh spans devices this process cannot address:
                # both fresh and restored host state must be assembled
                # into global replicated arrays (plain device_put fails)
                astate = replicate_across_processes(astate, mesh)
            elif restored_tree is not None:
                # place the restored host arrays explicitly onto the live
                # mesh, fully replicated — the elastic-restore re-shard
                astate = reshard(astate, replicate_spec_tree(astate, mesh))
        else:
            physical = 1

        # the sanctioned topology-lineage record: every (logical, physical)
        # realization this run has trained under, appended on change and
        # stored in each checkpoint's meta.json next to the schedule
        lineage = list(prior_lineage)
        realization = {"logical": int(logical), "physical": int(physical),
                       "procs": int(procs),
                       "devices": int(len(jax.devices())),
                       "from_step": int(shards_done)}
        if not lineage or any(lineage[-1].get(key) != realization[key]
                              for key in ("logical", "physical", "procs")):
            lineage.append(realization)

        # the jitted step (and every compiled shape variant behind it) is
        # cached process-wide on the semantic step parameters: a fresh
        # closure per call would give each fit its own jit cache, silently
        # recompiling every step variant on every fit — measured at ~30×
        # the warm step cost on repeated bench/test fits.  The physical
        # world is part of the key: the same logical schedule folds into
        # differently-shaped per-device programs on different meshes.
        # resolve the packed-logits dispatch ONCE, up front: it pins the
        # trace (part of the step-cache key — a profile loaded between two
        # fits must not reuse a step traced for the other impl) and is the
        # run's dispatch-of-record in checkpoints + StreamFitResult
        chosen_impl = logits_packed_impl(cfg, rows=batch_size)
        _perf_rep = perf.dispatch_report()
        dispatch_record = {"logits_packed": chosen_impl,
                           "table_version": _perf_rep["table_version"],
                           "profile_loaded": _perf_rep["profile_loaded"]}

        step_key = ("dp" if dp else "serial", logical, physical, procs,
                    cfg, has_empty, loss, optimizer, lr, l2, chosen_impl,
                    grad_compress)
        step_fn = _STEP_CACHE.get(step_key)
        if step_fn is None:
            if dp:
                step_fn = build_dp_averaged_train_step(
                    sum_loss_with_hits_fn(fwd, loss), opt, mesh, l2=l2,
                    logical_world=logical, compress=compress)
            else:
                # shared minibatch loss + matching decision rule (one
                # definition, train/losses.py); the pre-update predictions
                # ride the train step's forward as a has_aux output —
                # progressive validation costs no second forward per batch.
                loss_with_preds = mean_loss_with_preds_fn(fwd, loss, l2=l2)

                def loss_and_hits(params, batch, labels):
                    total, pred = loss_with_preds(params, batch, labels)
                    return total, jnp.sum(pred == labels)

                step_fn = build_averaged_train_step(loss_and_hits, opt,
                                                    has_aux=True)
            while len(_STEP_CACHE) >= _STEP_CACHE_MAX:
                _STEP_CACHE.pop(next(iter(_STEP_CACHE)))
            _STEP_CACHE[step_key] = step_fn

        # error-feedback residual memory for the compressed all-reduce:
        # per-device local state with a leading (physical,) axis sharded
        # over the mesh's data rows.  Deliberately NOT checkpointed — it
        # resets to zero on resume (see the docstring's tradeoff note).
        err0 = None
        if compress is not None:
            if procs > 1:
                err0 = jax.tree.map(
                    lambda p: device_put_process_local(
                        np.zeros((d_local,) + tuple(p.shape), np.float32),
                        mesh, physical),
                    astate.state.params)
            else:
                err_sh = NamedSharding(mesh, PartitionSpec("data"))
                err0 = jax.tree.map(
                    lambda p: jax.device_put(
                        np.zeros((physical,) + tuple(p.shape), np.float32),
                        err_sh),
                    astate.state.params)

        counters = {"device_reads": 0, "ckpt_saves": 0, "ckpt_bytes": 0,
                    "input_waits": 0}

        def save_boundary(next_epoch: int, next_pos: int) -> None:
            tree = {"astate": astate, "epoch": np.int64(next_epoch),
                    "pos": np.int64(next_pos),
                    "shards_done": np.int64(shards_done),
                    "hits": np.int64(hits), "seen": np.int64(seen),
                    "fingerprint": fingerprint}
            extra = {"schedule": {"dp": dp,
                                  "logical_world": int(logical),
                                  "procs": int(procs)},
                     "lineage": lineage,
                     "dispatch": dispatch_record}
            counters["ckpt_saves"] += 1
            counters["ckpt_bytes"] += _leaf_bytes(tree)
            counters["device_reads"] += ckpt.device_leaves(tree)
            with span("repro.ckpt.save", shard=shards_done):
                if procs > 1:
                    # every rank writes its own CRC'd payload; rank 0
                    # commits the step once all payloads landed
                    # (ckpt.coordinated)
                    coordinated.save_coordinated(
                        ckpt_dir, shards_done, tree, rank=rt.rank,
                        procs=procs, keep_last=ckpt_keep_last,
                        barrier_timeout_s=ckpt_barrier_timeout_s,
                        extra_meta=extra)
                else:
                    ckpt.save(ckpt_dir, shards_done, tree,
                              keep_last=ckpt_keep_last, extra_meta=extra)
            if procs > 1 and not rt.is_leader:
                return
            # also publish the current EVAL iterate (Polyak average once
            # the tail window opened, else the raw iterate) as a params-
            # only snapshot under <ckpt_dir>/serve — what a live server's
            # /reload (serving.reload) swaps in without a restart; rank 0
            # only in a gang (one server, one snapshot)
            with span("repro.ckpt.publish", shard=shards_done):
                with span("repro.ckpt.read_device", n=1):
                    averaged = float(astate.avg_count) > 0
                serve_now = (astate.avg_params if averaged
                             else astate.state.params)
                ckpt.publish_params(ckpt_dir, shards_done, serve_now)
            counters["ckpt_bytes"] += _leaf_bytes(serve_now)
            counters["device_reads"] += 1 + ckpt.device_leaves(serve_now)

        # ---- event stream: serial or grouped, inline or prefetched ------
        if dp:
            if procs > 1:
                # each rank streams ONLY its contiguous slot block; the
                # global stacked batch is assembled from every process's
                # local rows (mesh rows are process-contiguous by
                # construction, so local slots == local mesh rows)
                slot_range = process_slot_range(logical, procs, rt.rank)
                put = lambda x: device_put_process_local(  # noqa: E731
                    x, mesh, logical)
            else:
                slot_range = None
                put = lambda x: device_put_sharded(x, mesh)  # noqa: E731

            def transfer(codes, empty, labels, valid):
                batch = ((put(codes), put(empty)) if has_empty
                         else put(codes))
                return (batch, put(labels), put(valid))

            stream = group_batch_stream(
                root, batch_size, seed=seed, epochs=epochs,
                n_shards=n_shards, counts=counts, world=logical,
                shuffle=shuffle_shards, start_epoch=epoch0, start_pos=pos0,
                has_empty=has_empty, packed_width=packed_width(k, b),
                mask_width=packed_mask_width(k), transfer=transfer,
                mmap=mmap, slot_range=slot_range)
        else:
            def transfer(bp, bem, bl):
                batch = ((jnp.asarray(bp), jnp.asarray(bem)) if has_empty
                         else jnp.asarray(bp))
                return (batch, jnp.asarray(bl))

            stream = serial_batch_stream(
                root, batch_size, seed=seed, epochs=epochs,
                n_shards=n_shards, shuffle=shuffle_shards,
                start_epoch=epoch0, start_pos=pos0, has_empty=has_empty,
                transfer=transfer, mmap=mmap)

        events = (ThreadedPrefetcher(stream, prefetch) if prefetch
                  else InlineStream(stream))

        global_step = int(astate.state.step)
        processed_here = 0
        stopped = False
        pending_hits = []
    t0 = time.perf_counter()
    try:
        for ev in events:
            if isinstance(ev, StreamBatch):
                active = np.float32(global_step >= avg_start_step)
                # the step span and the watchdog time the same interval
                with step_span(global_step):
                    if watchdog is not None:
                        watchdog.start_step()
                    # inside the watchdog window: an injected slow step
                    # is observed as step latency, an injected crash
                    # dies mid-step — both as a real fault would
                    if faults._ACTIVE is not None:
                        faults.on_train_step(global_step)
                    if compress is not None:
                        (astate, err0), (_, h) = step_fn(
                            (astate, err0), active, *ev.args)
                    else:
                        astate, (_, h) = step_fn(astate, active,
                                                 *ev.args)
                    if watchdog is not None:
                        # dispatch is async: this observes host-side
                        # step latency (enqueue + any producer stall),
                        # which is exactly where injected slow steps
                        # and starving input pipelines show up
                        watchdog.end_step(global_step)
                # device scalars, drained once per shard: no per-step
                # host sync to break async dispatch overlap
                pending_hits.append(h)
                seen += ev.n_rows
                global_step += 1
                continue
            assert isinstance(ev, Boundary)
            if pending_hits:
                with span("repro.train.hits_drain",
                          n=len(pending_hits)):
                    hits += int(np.sum(jax.device_get(pending_hits)))
                counters["device_reads"] += len(pending_hits)
                pending_hits = []
            prev_done = shards_done
            shards_done += ev.shards_consumed
            processed_here += ev.shards_consumed
            if rt.is_multiprocess:
                heartbeat(rt, step=global_step,
                          shards_done=shards_done)
            at_stop = (stop_after_shards is not None
                       and processed_here >= stop_after_shards)
            done = ev.next_epoch >= epochs
            crossed = (shards_done // ckpt_every_shards
                       > prev_done // ckpt_every_shards)
            if ckpt_dir and (crossed or at_stop or done):
                save_boundary(ev.next_epoch, ev.next_pos)
            if at_stop and not done:
                stopped = True
                break
    except BaseException:
        events.close()
        raise
    with span("repro.train.job_end"):
        # ThreadedPrefetcher.close() joins the producer; the inline
        # stream's close() runs its cleanup NOW (dropping the open
        # mmap'd shard iterators) instead of waiting on GC
        events.close()
        dt = time.perf_counter() - t0

        assert stopped or global_step > 0, \
            "streaming run performed no steps"
        counters["input_waits"] = events.waits
        return StreamFitResult(
            params=astate.state.params,
            avg_params=average_or_none(astate.avg_params,
                                       astate.avg_count),
            train_seconds=dt,
            progressive_acc=hits / max(seen, 1),
            n_steps=global_step,
            examples_seen=seen,
            shards_processed=shards_done,
            completed=not stopped,
            topology_lineage=lineage,
            dispatch=dispatch_record,
            counters=counters,
        )
