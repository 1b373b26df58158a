"""Async shard/minibatch prefetch: the producer→queue→device pipeline.

PR 3's streaming trainer was strictly serial: every step waited on mmap
fault-in + host-side shuffle/slice before the device saw any work —
exactly the host-bound pattern the paper says must be hidden
("preprocessing/loading cost should be overlapped with or dominated by
compute", arXiv:1108.3072 §3; arXiv:1205.2958 §5 shows the online/VW
baseline is I/O-bound at scale).  This module moves ALL host-side batch
work off the training thread:

  producer thread                        consumer (train loop)
  ───────────────                        ─────────────────────
  walk the deterministic shard order ─┐
  mmap the shard, fault in its pages  │   bounded  ┌─ step(batch i)
  permute + slice the next minibatch  ├─▶ Queue   ─┤  step(batch i+1)
  jax transfer (device_put/asarray)  ─┘  (depth)   └─ drain hits / ckpt

The pieces:

  * ``shard_order`` — the epoch's shard permutation, a pure function of
    ``(seed, epoch)`` (moved here from ``train.streaming`` so producers
    and trainer share one definition);
  * ``serial_batch_stream`` / ``group_batch_stream`` — plain generators
    yielding ``StreamBatch`` / ``Boundary`` events for the
    single-device and data-parallel (shards grouped across devices)
    schedules.  The generator IS the serial path: running it inline
    (prefetch off) or through the thread (prefetch on) executes the
    same code on the same values;
  * ``ThreadedPrefetcher`` — wraps any event generator in a bounded
    daemon thread (``depth`` ≥ 1 items transferred ahead; depth 2 is
    classic double buffering).  Because the producer runs ``depth``
    items ahead, the NEXT shard's mmap pages start faulting in while
    the device is still training on the current shard's tail.

Determinism contract: prefetch changes WHEN host work happens, never
WHAT is produced — the event sequence (batch contents, row counts,
shard boundaries) is identical for any depth, including depth 0
(inline).  ``train.streaming.fit_streaming`` therefore produces
bit-identical parameters, progressive-validation counters and
checkpoints with prefetch on or off (tested), and a run checkpointed
under one depth resumes under any other.

Exceptions raised by the producer surface in the consumer at the point
of the failed event WITH the producer thread's original traceback; a
failure inside a shard's read/slice path is wrapped in
``ShardStreamError`` carrying (shard, epoch, position) context, so the
consumer learns exactly where the stream died.  Should the producer
thread die without managing to post its error sentinel, the consumer's
``next()`` detects the dead thread and raises instead of hanging on
the queue forever.  ``close()`` (also called when the consumer loop
exits early, e.g. ``stop_after_shards``) unblocks and joins the thread.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Iterator, Optional, Sequence, Tuple

import numpy as np

from repro.data.hashed_dataset import iter_hashed_batches
from repro.perf.spans import span

__all__ = [
    "StreamBatch", "Boundary", "ShardStreamError", "shard_order",
    "serial_batch_stream", "group_batch_stream", "ThreadedPrefetcher",
    "InlineStream",
]


class ShardStreamError(RuntimeError):
    """A shard's read/slice path failed inside a batch stream.

    Carries the (shard, epoch, position) the stream died at; the
    original failure is chained as ``__cause__`` with its full
    traceback (the batch streams raise via ``raise ... from``), so a
    consumer on the other side of a ``ThreadedPrefetcher`` sees both
    the where and the why.
    """

    def __init__(self, msg: str, *, shard: int, epoch: int,
                 position: int):
        super().__init__(msg)
        self.shard = shard
        self.epoch = epoch
        self.position = position


@dataclasses.dataclass
class StreamBatch:
    """One training step's worth of device-resident data.

    ``args`` is the positional tail of the train step call —
    ``(batch, labels)`` for the serial schedule, ``(batch, labels,
    valid)`` for the data-parallel one; ``n_rows`` counts the REAL
    examples inside (padding excluded) for progressive-validation
    bookkeeping.
    """
    args: tuple
    n_rows: int


@dataclasses.dataclass
class Boundary:
    """End of a shard (serial) or shard group (data-parallel): the
    trainer drains hit counters, advances ``shards_done`` by
    ``shards_consumed`` and may checkpoint at ``(next_epoch,
    next_pos)`` — the stream position a resumed run restarts from."""
    next_epoch: int
    next_pos: int
    shards_consumed: int


def shard_order(seed: int, epoch: int, n_shards: int,
                shuffle: bool) -> np.ndarray:
    """The epoch's shard visit order — a pure function of
    ``(seed, epoch)``, so a restarted run replays it exactly."""
    if not shuffle:
        return np.arange(n_shards)
    rng = np.random.default_rng(np.random.SeedSequence((seed, epoch)))
    return rng.permutation(n_shards)


def _mask_consistent(bem, has_empty: bool, shard: int, root: str) -> None:
    if (bem is None) == has_empty:
        raise ValueError(
            f"shard {shard} of {root!r} "
            f"{'lacks' if bem is None else 'carries'} an empty bitmask "
            f"while shard 0 {'has one' if has_empty else 'does not'} — "
            "archive written with desynced empty masks?")


def serial_batch_stream(
    root: str,
    batch_size: int,
    *,
    seed: int,
    epochs: int,
    n_shards: int,
    shuffle: bool,
    start_epoch: int,
    start_pos: int,
    has_empty: bool,
    transfer: Callable[..., tuple],
    mmap: bool = True,
) -> Iterator[Any]:
    """Single-device event stream: one shard at a time, minibatches in
    the deterministic ``(seed, epoch, shard)`` permutation.

    ``transfer(packed, empty|None, labels) -> (batch, labels)`` does
    the host→device move; it runs on whatever thread iterates this
    generator (the prefetch thread when wrapped, the train loop when
    inline) — same values either way.
    """
    for epoch in range(start_epoch, epochs):
        order = shard_order(seed, epoch, n_shards, shuffle)
        first = start_pos if epoch == start_epoch else 0
        for pos in range(first, n_shards):
            s = int(order[pos])
            try:
                batches = iter_hashed_batches(
                    root, batch_size, shard_ids=[s],
                    perm_seed=(seed, epoch), mmap=mmap)
                while True:
                    with span("repro.prefetch.read", epoch=epoch, shard=s):
                        item = next(batches, None)
                    if item is None:
                        break
                    bp, bl, _rid, bem = item
                    _mask_consistent(bem, has_empty, s, root)
                    with span("repro.prefetch.put", n=len(bl)):
                        args = transfer(bp, bem, bl)
                    yield StreamBatch(args=args, n_rows=len(bl))
            except Exception as e:
                # GeneratorExit (consumer close) is BaseException —
                # deliberately not caught here
                raise ShardStreamError(
                    f"shard {s} failed at epoch {epoch} position {pos} "
                    f"of {root!r}: {e}", shard=s, epoch=epoch,
                    position=pos) from e
            next_epoch, next_pos = ((epoch, pos + 1)
                                    if pos + 1 < n_shards
                                    else (epoch + 1, 0))
            yield Boundary(next_epoch, next_pos, 1)


def group_batch_stream(
    root: str,
    batch_size: int,
    *,
    seed: int,
    epochs: int,
    n_shards: int,
    counts: Sequence[int],
    world: int,
    shuffle: bool,
    start_epoch: int,
    start_pos: int,
    has_empty: bool,
    packed_width: int,
    mask_width: int,
    transfer: Callable[..., tuple],
    mmap: bool = True,
    slot_range: "Optional[Tuple[int, int]]" = None,
) -> Iterator[Any]:
    """Data-parallel event stream: consecutive GROUPS of ``world``
    shards from the epoch order, one shard per device, in lockstep.

    Per global step, device d's next minibatch from its shard is
    stacked into row d of fixed-shape ``(world, B, …)`` arrays (fixed
    shapes → one jit trace for the whole run).  Shards in a group can
    hold different batch counts (uneven rows, short final group): a
    device whose shard is exhausted — or that got no shard at all —
    contributes an all-padding batch with ``valid`` all-False, so it
    keeps participating in every collective (an absent device would
    hang the all-reduce) while adding exactly zero gradient, zero
    hits and zero rows.  Per-shard batch contents equal the serial
    schedule's (same ``iter_hashed_batches`` permutation contract).

    ``slot_range=(lo, hi)`` is the multi-process ownership window
    (``distributed.runtime.process_slot_range``): this process reads
    ONLY the shards occupying slots [lo, hi) of each group and the
    stacked arrays carry just those ``hi - lo`` rows — the caller
    assembles the global batch from every process's block
    (``jax.make_array_from_process_local_data``).  Everything
    schedule-shaped stays GLOBAL regardless: ``n_rows`` counts the
    real examples across ALL slots (computed from ``counts``, no
    remote reads — the progressive-validation denominator must agree
    on every rank), the step count per group is the global
    ``max ceil(rows/B)``, and ``Boundary.shards_consumed`` is the full
    group size.

    ``start_pos`` must sit on a group boundary (a multiple of
    ``world``) — which is the only place the trainer checkpoints.
    """
    slot_lo, slot_hi = (0, world) if slot_range is None else slot_range
    if not (0 <= slot_lo < slot_hi <= world):
        raise ValueError(
            f"slot_range {slot_range} outside the [0, {world}) slots")
    if start_pos % world != 0 and start_pos < n_shards:
        raise ValueError(
            f"data-parallel resume position {start_pos} is not a "
            f"multiple of the world size {world} — checkpoint written "
            "under a different schedule?")
    local = slot_hi - slot_lo
    for epoch in range(start_epoch, epochs):
        order = shard_order(seed, epoch, n_shards, shuffle)
        first = start_pos if epoch == start_epoch else 0
        for lo in range(first, n_shards, world):
            group = [int(s) for s in order[lo: lo + world]]
            iters = {d: iter_hashed_batches(
                root, batch_size, shard_ids=[group[d]],
                perm_seed=(seed, epoch), mmap=mmap)
                for d in range(len(group)) if slot_lo <= d < slot_hi}
            n_batches = [-(-counts[s] // batch_size) for s in group]
            for t in range(max(n_batches)):
                with span("repro.prefetch.read", epoch=epoch,
                          shard=group[0]):
                    codes = np.zeros((local, batch_size, packed_width),
                                     np.uint8)
                    empty = (np.zeros((local, batch_size, mask_width),
                                      np.uint8) if has_empty else None)
                    labels = np.zeros((local, batch_size), np.int32)
                    valid = np.zeros((local, batch_size), bool)
                    for d, it in iters.items():
                        if t >= n_batches[d]:
                            continue
                        try:
                            bp, bl, _rid, bem = next(it)
                            _mask_consistent(bem, has_empty, group[d],
                                             root)
                        except StopIteration as e:
                            raise RuntimeError(
                                f"shard {group[d]} yielded fewer batches "
                                f"than its row count promised") from e
                        except Exception as e:
                            raise ShardStreamError(
                                f"shard {group[d]} (device slot {d}) "
                                f"failed at epoch {epoch} position "
                                f"{lo + d} of {root!r}: {e}", shard=group[d],
                                epoch=epoch, position=lo + d) from e
                        m = len(bl)
                        codes[d - slot_lo, :m] = bp
                        labels[d - slot_lo, :m] = bl
                        valid[d - slot_lo, :m] = True
                        if has_empty:
                            empty[d - slot_lo, :m] = bem
                # the GLOBAL real-row count for this step — a pure
                # function of the row counts, so every process agrees
                # without reading each other's shards
                n_rows = sum(
                    min(batch_size, counts[group[d]] - t * batch_size)
                    for d in range(len(group)) if t < n_batches[d])
                with span("repro.prefetch.put", n=n_rows):
                    args = transfer(codes, empty, labels, valid)
                yield StreamBatch(args=args, n_rows=n_rows)
            next_epoch, next_pos = ((epoch, lo + world)
                                    if lo + world < n_shards
                                    else (epoch + 1, 0))
            yield Boundary(next_epoch, next_pos, len(group))


class ThreadedPrefetcher:
    """Runs an event generator in a bounded background (daemon) thread.

    Up to ``depth`` produced items wait in the queue while the consumer
    trains — host slicing, page fault-in and the jax transfer for step
    i+1…i+depth overlap with step i's device compute.  Iteration
    yields exactly the wrapped generator's items in order; a producer
    exception re-raises at the corresponding point in the consumer.

    Always ``close()`` when abandoning the stream early (the trainer
    does this in a ``finally``): it unblocks a producer stuck on a full
    queue and joins the thread.  Exhausting the stream normally needs
    no cleanup but ``close()`` is idempotent and cheap.
    """

    # times the consumer found the queue empty (the trainer's
    # ``input_waits`` counter); on the class, so an instance built
    # without ``__init__`` counts too
    waits = 0

    def __init__(self, gen: Iterator[Any], depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(
            target=self._produce, args=(gen,), daemon=True,
            name="shard-prefetch")
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self, gen) -> None:
        try:
            for item in gen:
                if not self._put(("item", item)):
                    return
            self._put(("done", None))
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            self._put(("error", e))

    def __iter__(self):
        return self

    def _get(self):
        while True:
            try:
                return self._q.get(timeout=0.25)
            except queue.Empty:
                # the sentinel protocol means a live producer ALWAYS
                # eventually posts; a dead thread with an empty queue
                # means it was killed before its error/done sentinel
                # could land (e.g. interpreter teardown) — surface
                # that instead of blocking forever
                if not self._thread.is_alive():
                    self._done = True
                    raise RuntimeError(
                        "prefetch producer thread died without "
                        "delivering an event or error sentinel — "
                        "the stream is lost") from None

    def __next__(self):
        if self._done:
            raise StopIteration
        if self._q.empty():
            self.waits += 1
        with span("repro.prefetch.wait"):
            kind, val = self._get()
        if kind == "item":
            return val
        self._done = True
        if kind == "error":
            # re-raise the producer's exception with its original
            # traceback (it travelled on the exception object)
            raise val
        raise StopIteration

    def close(self) -> None:
        # mark exhausted FIRST: a next() issued after (or racing) close
        # must raise StopIteration, not block forever on a queue whose
        # done/error sentinel is being drained away below
        self._done = True
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        # wake a consumer that entered get() just before close
        try:
            self._q.put_nowait(("done", None))
        except queue.Full:
            pass
        self._thread.join(timeout=10.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class InlineStream:
    """An event generator run on the consumer's own thread (prefetch
    off), under the same ``repro.prefetch.wait`` span as
    ``ThreadedPrefetcher``: the producer's read and put spans nest
    inside it.  Every event is a wait (``waits``), since the consumer
    produces each one itself."""

    def __init__(self, gen: Iterator[Any]):
        self._gen = gen
        self.waits = 0

    def __iter__(self):
        return self

    def __next__(self):
        with span("repro.prefetch.wait"):
            item = next(self._gen)
        self.waits += 1
        return item

    def close(self) -> None:
        # runs the generator's cleanup now (its open mmap'd shard
        # iterators) instead of waiting on GC
        self._gen.close()
