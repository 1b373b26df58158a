"""Serving engines.

``HashedClassifierEngine`` — the paper's inference path as a service.
The headline claim (30 hashed values/point matching VW at 2^14,
arXiv:1108.3072) is ultimately an inference-cost argument: tiny codes
mean tiny per-request compute, IF the serving path doesn't squander it
on host round-trips and padding.  This engine serves raw sparse
documents through ONE fused device dispatch per micro-batch:

  raw idx/nnz ─▶ scheme.encode_packed_jit (hash → b-bit → pack; Pallas
  kernel on TPU, XLA elsewhere — ``kernels.dispatch.choose`` via
  ``ops.fused_encode_on_device``)
  ─▶ bbit_scores_packed (packed-input logits kernels) ─▶ scores

so on the kernel path no ``(B, k)`` int32 code matrix ever
materializes — codes travel packed (ceil(k·b/8) bytes/row) and unpack
in-register, exactly like the PR-4 training step.  Scores are
bit-identical to the reference ``encode_jnp`` + ``bbit_logits``
two-step (``fused=False`` keeps that path selectable for A/B benches).

Batching architecture (see ``serving.batcher.BucketBatcher``):

  * LANE ROUTING — ``submit`` validates the doc and routes it to an
    ``nnz``-bucket lane (pow-2-ish widths, growing past the largest
    bucket), so one giant document never inflates a whole batch's
    padding; drained batches pad rows to a pow-2 row bucket.
  * PRECOMPILE — every (row_bucket × nnz_bucket × replica) score
    function is compiled at engine startup, so steady-state serving
    never hits a first-request compile spike (``compile_misses`` counts
    any stray shape that does recompile, e.g. an over-bucket giant doc
    or a direct ``score_docs`` batch larger than ``max_batch``).
  * OVERLAP — the drain thread pads batch N+1 while the device runs
    batch N (async dispatch); a resolver thread owns the blocking
    device→host sync and future resolution.
  * REPLICAS — ``replicas=N`` device_puts the params once per device
    of a 1-D ``launch.mesh.make_replica_mesh`` mesh and round-robins
    micro-batches across them (no collectives; independent throughput
    scaling).

Input contract: docs are 1-D non-negative integer id arrays.  Empty
docs (nnz=0) are scheme-dependent: zero-coded OPH (``oph_zero``) scores
them through its all-empty-bins path (score = bias); schemes without
empty semantics (``minwise``, densified ``oph``) reject them at
``submit`` — their hash of an empty set is undefined sentinel garbage.

Operability (the network tier's substrate — see ``serving.server``):

  * VERSIONED WEIGHTS — the live params are one immutable
    ``serving.reload.WeightSet`` (version + per-replica device
    handles); ``swap_weights`` publishes a new set with a single
    reference swap, and a micro-batch dispatch reads the reference
    exactly once, so every score is computed against exactly one
    version — echoed on the result (``result.version``).
  * STATS — ``submit`` feeds a ``serving.stats.StatsWindow`` (rolling
    latency/rows/tenant window); ``stats()`` is the thread-safe
    snapshot behind ``GET /status``: p50/p95/p99, rows/s, per-lane
    occupancy, ``compile_misses``, per-tenant counts, batcher health.
  * ADAPTIVE BUCKETS — with ``adapt_every=N``, every N submits the
    engine re-derives the nnz lane grid from the batcher's observed
    size histogram (``adapt_buckets()``), precompiles any new shapes
    on a background thread, then swaps the grid — a skewed workload
    converges to tighter padding than the static config grid without
    a restart (traffic during the swap routes on whichever grid it
    caught; both are precompiled).

``greedy_generate`` — reference LM decode loop over any ModelAPI
(prefill + KV-cache decode), used by the serving example and tests.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.schemes import make_scheme
from repro.data.packing import bucket_width, pad_rows
from repro.kernels import dispatch
from repro.launch.mesh import make_replica_mesh
from repro.models.linear import (BBitLinearConfig, bbit_scores,
                                 bbit_scores_packed)
from repro.serving.batcher import BucketBatcher
from repro.serving.reload import WeightSet
from repro.serving.stats import StatsWindow

DEFAULT_NNZ_BUCKETS = (128, 512, 2048, 8192, 32768)


class VersionedScore(float):
    """A score that knows which model version produced it — a plain
    ``float`` everywhere (math, JSON, numpy) plus ``.version``."""
    __slots__ = ("version",)

    def __new__(cls, value, version: str):
        obj = super().__new__(cls, value)
        obj.version = version
        return obj


class VersionedVector(np.ndarray):
    """Multiclass twin of ``VersionedScore``: an ndarray row of scores
    carrying ``.version``."""

    def __new__(cls, arr, version: str):
        obj = np.asarray(arr).view(cls)
        obj.version = version
        return obj

    def __array_finalize__(self, obj):
        if obj is not None:
            self.version = getattr(obj, "version", None)


def _grow_bucket(n: int, buckets: Sequence[int]) -> int:
    """Pad width for an nnz of ``n``: the smallest fixed bucket that
    fits, growing by powers of two past the largest one.  Clamping to
    ``buckets[-1]`` instead would hand the scorer an ``idx`` wider than
    its ``nnz`` mask and corrupt giant-document scores."""
    for b in buckets:
        if n <= b:
            return b
    return bucket_width(n, floor=buckets[-1])


class HashedClassifierEngine:
    def __init__(self, params, cfg: BBitLinearConfig, seed: int = 0,
                 max_batch: int = 64, max_wait_ms: float = 2.0,
                 scheme: str = "minwise", *,
                 fused: bool = True,
                 replicas: int = 1,
                 nnz_buckets: Sequence[int] = DEFAULT_NNZ_BUCKETS,
                 row_buckets: Optional[Sequence[int]] = None,
                 precompile: bool = True,
                 pipeline_depth: int = 2,
                 stats_window: int = 2048,
                 adapt_every: int = 0,
                 version: str = "v0",
                 dedup_cache: bool = False,
                 dedup_entries: int = 4096,
                 dedup_rows_per_band: int = 4,
                 dedup_probe_bands: int = 4):
        self.cfg = cfg
        self.scheme = make_scheme(scheme, cfg.k, seed)
        self.family = getattr(self.scheme, "family", None)
        self.fused = fused
        # duplicate-traffic short-circuit: band-signature probe + exact
        # packed-code guard, sitting after (host-side) encode and before
        # device dispatch — see serving/dedup.py for the contract
        self.dedup: Optional["DedupCache"] = None
        if dedup_cache:
            from repro.retrieval.bands import band_geometry
            from repro.serving.dedup import DedupCache
            band_geometry(cfg.k, cfg.b, dedup_rows_per_band)
            self.dedup = DedupCache(max_entries=dedup_entries,
                                    version=version)
            self._dedup_rows_per_band = int(dedup_rows_per_band)
            self._dedup_probe_bands = int(dedup_probe_bands)
        # zero-coded schemes give an empty doc exact semantics (every
        # bin empty → contributions masked out → score == bias)
        self._allows_empty = getattr(self.scheme, "densify", True) is False
        self.nnz_buckets = tuple(sorted(int(b) for b in nnz_buckets))
        if not self.nnz_buckets:
            raise ValueError("need at least one nnz bucket")
        # one pow-2 row grid for every nnz lane
        if row_buckets is None:
            top = bucket_width(max_batch, floor=1)
            row_buckets = tuple(1 << i for i in range(top.bit_length()))
        self.row_buckets = tuple(sorted(int(r) for r in row_buckets))

        self.mesh = make_replica_mesh(replicas)
        self.devices = list(self.mesh.devices.flat)
        # params replicated ONCE per version — each micro-batch reuses
        # its replica's resident copy, no per-request weight traffic;
        # the WeightSet is swapped atomically by ``swap_weights``
        self._weights = WeightSet(
            version=version,
            params=tuple(jax.device_put(params, d)
                         for d in self.devices),
            created_at=time.time())
        self.reloads = 0
        self._swap_lock = threading.Lock()
        self._rr = 0
        self._rr_lock = threading.Lock()
        self.device_batches = [0] * len(self.devices)
        self.stats_window = StatsWindow(stats_window)
        self.adapt_every = int(adapt_every)
        self.rebuckets = 0
        self._submits = 0
        self._adapting = threading.Event()
        self._started_at = time.time()

        scheme_obj, lcfg = self.scheme, cfg

        @jax.jit
        def _score_fused(idx, nnz, params):
            packed, empty = scheme_obj.encode_packed_jit(idx, nnz, lcfg.b)
            return bbit_scores_packed(params, packed, lcfg,
                                      empty_packed=empty)

        @jax.jit
        def _score_reference(idx, nnz, params):
            mask = (jnp.arange(idx.shape[1], dtype=jnp.int32)[None, :]
                    < nnz[:, None])
            codes, empty = scheme_obj.encode_jnp(idx, mask, lcfg.b)
            return bbit_scores(params, codes, lcfg, empty=empty)

        self._score_fused = _score_fused
        self._score_reference = _score_reference
        self._score_fn = _score_fused if fused else _score_reference

        self._compiled: set = set()
        self.compile_misses = 0
        self.precompile_seconds = 0.0
        if precompile:
            self._precompile()

        self.batcher = BucketBatcher(
            self._dispatch_batch, self._resolve_batch,
            route=lambda doc: self._nnz_bucket(len(doc)),
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            depth=pipeline_depth)

    # ---------------------------------------------------------- buckets --
    def _nnz_bucket(self, n: int) -> int:
        return _grow_bucket(n, self.nnz_buckets)

    def _row_bucket(self, n: int) -> int:
        for r in self.row_buckets:
            if n <= r:
                return r
        return bucket_width(n, floor=self.row_buckets[-1])

    def _precompile(self) -> None:
        """Compile every (row_bucket, nnz_bucket, replica) lane shape up
        front — steady-state traffic then never pays a compile spike."""
        t0 = time.perf_counter()
        self._precompile_grid(self.nnz_buckets, self.row_buckets)
        self.precompile_seconds = time.perf_counter() - t0

    def _precompile_grid(self, nnz_buckets: Sequence[int],
                         row_buckets: Sequence[int]) -> None:
        """Compile any not-yet-seen shapes of a lane grid (jit caches
        by shape, so a weight swap never re-pays this)."""
        w = self._weights
        for d, dev in enumerate(self.devices):
            for m in nnz_buckets:
                idx = jax.device_put(np.zeros((1, m), np.int32), dev)
                nnz = jax.device_put(np.ones((1,), np.int32), dev)
                for r in row_buckets:
                    if (r, m, d) in self._compiled:
                        continue
                    ib = jnp.broadcast_to(idx, (r, m))
                    zb = jnp.broadcast_to(nnz, (r,))
                    self._score_fn(ib, zb, w.params[d]) \
                        .block_until_ready()
                    self._compiled.add((r, m, d))

    # ----------------------------------------------------------- scoring --
    def _validate(self, doc, *, check_neg: bool = True) -> np.ndarray:
        if (type(doc) is np.ndarray and doc.dtype == np.int64
                and doc.ndim == 1):
            # already the canonical dtype/shape: skip the generic
            # asarray/issubdtype machinery (measurable at batch rates)
            if check_neg and doc.size and int(doc.min()) < 0:
                raise ValueError("doc has negative feature indices")
            if doc.size == 0 and not self._allows_empty:
                raise ValueError(
                    f"empty document: scheme {self.scheme.name!r} has "
                    "no empty semantics (its min over zero hashes is "
                    "sentinel garbage) — reject upstream or serve with "
                    "the zero-coded 'oph_zero' scheme, whose "
                    "all-empty-bins path scores it as the bias")
            return doc
        arr = np.asarray(doc)
        if arr.ndim != 1 or not np.issubdtype(arr.dtype, np.integer):
            raise TypeError(
                f"doc must be a 1-D integer id array, got shape "
                f"{arr.shape} dtype {arr.dtype}")
        # check_neg=False defers the negativity reduce to the caller's
        # ONE fused pass over the batch concat (submit_many) — a
        # per-row .min() is numpy fixed overhead at batch rates
        if check_neg and arr.size and int(arr.min()) < 0:
            raise ValueError("doc has negative feature indices")
        if arr.size == 0 and not self._allows_empty:
            raise ValueError(
                f"empty document: scheme {self.scheme.name!r} has no "
                "empty semantics (its min over zero hashes is sentinel "
                "garbage) — reject upstream or serve with the "
                "zero-coded 'oph_zero' scheme, whose all-empty-bins "
                "path scores it as the bias")
        return arr.astype(np.int64, copy=False)

    def _next_device(self) -> int:
        with self._rr_lock:
            d = self._rr % len(self.devices)
            self._rr += 1
        return d

    def _dispatch_batch(self, key: int, docs: List[np.ndarray],
                        device_index: Optional[int] = None,
                        weights: Optional[WeightSet] = None) -> Tuple:
        """Pad ``docs`` to the (row_bucket, key) lane shape and issue
        the fused scorer asynchronously (runs on the drain thread; the
        blocking sync happens in ``_resolve_batch``).  Reads the live
        ``WeightSet`` reference exactly ONCE, so the whole batch scores
        against one version even if a reload lands mid-flight."""
        w = self._weights if weights is None else weights
        n = len(docs)
        rows = self._row_bucket(n)
        # pad_rows owns the id-folding policy (indices ≥ 2^31 fold to
        # [0, 2^31), same as training-side preprocessing) — only the
        # row/width padding to the lane's bucket shape happens here
        packed_idx, packed_nnz = pad_rows(docs, pad_to_multiple=1)
        idx = np.zeros((rows, key), np.int32)
        nnz = np.zeros((rows,), np.int32)
        idx[:n, :packed_idx.shape[1]] = packed_idx
        nnz[:n] = packed_nnz
        d = self._next_device() if device_index is None else device_index
        dev = self.devices[d]
        self.device_batches[d] += 1
        scores = self._score_fn(jax.device_put(idx, dev),
                                jax.device_put(nnz, dev),
                                w.on(d))
        shape_key = (rows, key, d)
        if shape_key not in self._compiled:
            self.compile_misses += 1
            self._compiled.add(shape_key)
        return scores, n, w.version

    def _resolve_batch(self, handle: Tuple) -> List:
        scores, n, version = handle
        host = np.asarray(scores)
        if host.ndim == 1:
            return [VersionedScore(x, version) for x in host[:n]]
        return [VersionedVector(row, version) for row in host[:n]]

    # ----------------------------------------------------- dedup cache ----
    def _dedup_keys(self, arrs: Sequence[np.ndarray],
                    cat: Optional[np.ndarray] = None) -> List[Tuple]:
        """One host-side hash pass over a whole batch → each doc's
        (band-signature probe, full packed bytes, empty bytes) — the
        cache's (probe, guard) pairs.  The bytes are bit-identical to
        the device encode (same fold/mask semantics via ``pad_rows`` +
        ``encode_packed_numpy``), and the encode is pad-width
        invariant, so a key computed in any batch equals the key
        computed alone.  Batching exists because the per-doc cost is
        numpy FIXED overhead (~200µs of small-array calls, not
        arithmetic): one batched pass amortizes it to ~µs/row, which
        is what lets a cache hit undercut the device round trip."""
        from repro.retrieval.bands import band_keys_packed
        ragged = getattr(self.scheme, "encode_packed_numpy_ragged", None)
        if ragged is not None:
            # no padded intermediate at all: concat + fold (the exact
            # ``pad_rows`` id-folding policy) + one ragged encode
            lens = np.fromiter((a.size for a in arrs), dtype=np.int64,
                               count=len(arrs))
            if cat is None:
                cat = (np.concatenate(arrs) if len(arrs) > 1
                       else np.asarray(arrs[0]))
            tokens = (cat & np.int64((1 << 31) - 1)).astype(np.int32)
            packed, empty = ragged(tokens, lens, self.cfg.b)
        else:
            idx, nnz = pad_rows(list(arrs), pad_to_multiple=1)
            packed, empty = self.scheme.encode_packed_numpy(
                idx, nnz, self.cfg.b)
        keys = band_keys_packed(packed, self.cfg.k, self.cfg.b,
                                self._dedup_rows_per_band)
        sigs = keys[:, :self._dedup_probe_bands].tolist()
        return [(tuple(s), packed[i].tobytes(),
                 None if empty is None else empty[i].tobytes())
                for i, s in enumerate(sigs)]

    def _dedup_key(self, arr: np.ndarray):
        return self._dedup_keys([arr])[0]

    def _submit_dedup(self, arr: np.ndarray, key: Optional[Tuple] = None):
        """Cache short-circuit: a hit returns an already-resolved Future
        (no batcher, no device); a miss dispatches normally and fills
        the cache when its batch resolves.

        The cached object is the RESOLVED batcher Future itself, shared
        by every subsequent hit: a finished Future is effectively
        immutable (``add_done_callback`` invokes immediately instead of
        appending, ``cancel`` is a no-op), and handing it out directly
        skips the ~µs-scale ``threading.Condition`` allocation a fresh
        Future per hit would cost — which profiles as the hit path's
        single biggest line item once the encode is batched."""
        sig, packed, empty = self._dedup_key(arr) if key is None else key
        version = self._weights.version
        hit = self.dedup.get(sig, packed, empty, version, nnz=arr.size)
        if hit is not None:
            return hit
        return self._submit_dedup_miss(arr, (sig, packed, empty), version)

    def _submit_dedup_miss(self, arr: np.ndarray, key: Tuple,
                           version: str):
        """Miss leg of the dedup path: normal batcher dispatch plus a
        cache fill when the batch resolves."""
        sig, packed, empty = key
        fut = self.batcher.submit(arr)
        cache = self.dedup

        def _fill(f):
            if f.cancelled() or f.exception() is not None:
                return
            result = f.result()
            cache.put(sig, packed, empty, f,
                      getattr(result, "version", version))

        fut.add_done_callback(_fill)
        return fut

    # ------------------------------------------------------------- API ----
    def submit(self, doc: Sequence[int], tenant: Optional[str] = None):
        """Validate + route one doc; returns a Future of its score (a
        ``VersionedScore`` — a float carrying ``.version``).  Resolve
        latency and the optional ``tenant`` feed the stats window."""
        arr = self._validate(doc)
        t0 = time.perf_counter()
        if self.dedup is not None:
            fut = self._submit_dedup(arr)
        else:
            fut = self.batcher.submit(arr)

        def _record(f, t0=t0, tenant=tenant):
            self.stats_window.record(
                time.perf_counter() - t0, rows=1, tenant=tenant,
                error=(not f.cancelled()
                       and f.exception() is not None))

        fut.add_done_callback(_record)
        if self.adapt_every:
            self._submits += 1
            if self._submits % self.adapt_every == 0:
                self._adapt_async()
        return fut

    def submit_many(self, docs: Sequence[Sequence[int]],
                    tenant: Optional[str] = None) -> List[Future]:
        """Batch ``submit``: identical routing and results, but with
        the dedup cache enabled the whole batch's keys come from ONE
        vectorized host-encode pass (``_dedup_keys``) instead of a
        per-doc pass — the batch front door (HTTP ``POST /score``
        arrives batched already) is where duplicate short-circuiting
        actually pays.  With the cache off this is a plain loop."""
        arrs = [self._validate(d, check_neg=False) for d in docs]
        if not arrs:
            return []
        cat = (np.concatenate(arrs) if len(arrs) > 1
               else np.asarray(arrs[0]))
        if cat.size and int(cat.min()) < 0:
            raise ValueError("doc has negative feature indices")
        t0 = time.perf_counter()
        futs = []

        def _record(f, t0=t0, tenant=tenant):
            self.stats_window.record(
                time.perf_counter() - t0, rows=1, tenant=tenant,
                error=(not f.cancelled()
                       and f.exception() is not None))

        if self.dedup is not None:
            keys = self._dedup_keys(arrs, cat=cat)
            version = self._weights.version
            hits = self.dedup.get_many(keys, version,
                                       [a.size for a in arrs])
            n_hits = 0
            for i, arr in enumerate(arrs):
                hit = hits[i]
                if hit is not None:
                    # resolved shared Future; stats recorded in one
                    # batched call below instead of per-row callbacks
                    futs.append(hit)
                    n_hits += 1
                    continue
                fut = self._submit_dedup_miss(arr, keys[i], version)
                fut.add_done_callback(_record)
                futs.append(fut)
            if n_hits:
                self.stats_window.record_batch(
                    time.perf_counter() - t0, n_hits, tenant=tenant)
        else:
            for arr in arrs:
                fut = self.batcher.submit(arr)
                fut.add_done_callback(_record)
                futs.append(fut)
        if self.adapt_every:
            before = self._submits
            self._submits += len(arrs)
            if (before // self.adapt_every
                    != self._submits // self.adapt_every):
                self._adapt_async()
        return futs

    def score_docs(self, docs: Sequence[Sequence[int]],
                   device_index: Optional[int] = None,
                   weights: Optional[WeightSet] = None) -> np.ndarray:
        """Synchronous batch scoring, bypassing the batcher (the
        batcher-off baseline; also what tests use as the oracle).
        Thread-safe.  ``weights`` pins the batch to a specific
        ``WeightSet`` (version-exact oracles, mixed-version repair in
        the HTTP tier).  Batches wider than the configured buckets
        compile on first use (counted in ``compile_misses``)."""
        items = [self._validate(d) for d in docs]
        key = self._nnz_bucket(max((len(d) for d in items), default=1))
        handle = self._dispatch_batch(key, items,
                                      device_index=device_index,
                                      weights=weights)
        scores, n, _ = handle
        return np.asarray(scores)[:n]

    # ------------------------------------------------- versioned weights --
    @property
    def params(self):
        """The replica-0 resident params of the live version (template
        for checkpoint restores; back-compat accessor)."""
        return self._weights.params[0]

    @property
    def version(self) -> str:
        return self._weights.version

    def current_weights(self) -> WeightSet:
        """The live immutable WeightSet (pin it to score version-exact
        across a reload)."""
        return self._weights

    def swap_weights(self, params, version: Optional[str] = None) -> str:
        """Atomically publish a new weight version.

        The new set is fully staged off to the side (structure check
        against the live tree, device_put per replica, blocked until
        resident) and then swapped in with ONE reference assignment —
        concurrent batches score against exactly the old or exactly the
        new version, and in-flight batches keep the set they captured.
        Returns the new version string.
        """
        live = jax.tree.structure(self._weights.params[0])
        new = jax.tree.structure(params)
        if live != new:
            raise ValueError(
                f"swap_weights: params tree structure {new} does not "
                f"match the live tree {live} — same model config "
                "required for a hot swap")
        for a, b in zip(jax.tree.leaves(self._weights.params[0]),
                        jax.tree.leaves(params)):
            if tuple(np.shape(a)) != tuple(np.shape(b)):
                raise ValueError(
                    f"swap_weights: leaf shape {np.shape(b)} does not "
                    f"match the live leaf {np.shape(a)} — a hot swap "
                    "cannot change k/b/n_classes")
        with self._swap_lock:
            version = version or f"v{self.reloads + 1}"
            staged = tuple(jax.device_put(params, d)
                           for d in self.devices)
            for tree in staged:
                jax.block_until_ready(tree)
            self._weights = WeightSet(version=version, params=staged,
                                      created_at=time.time())
            if self.dedup is not None:
                # same critical section as the reference swap: no window
                # where new-version traffic can hit an old-version score
                self.dedup.invalidate(version)
            self.reloads += 1
        return version

    # ------------------------------------------------- adaptive buckets --
    def _adapt_async(self) -> None:
        """Kick one background re-derivation (submit must never block
        on precompiles; overlapping triggers collapse into one)."""
        if self._adapting.is_set():
            return
        self._adapting.set()

        def run():
            try:
                self.adapt_buckets()
            finally:
                self._adapting.clear()

        threading.Thread(target=run, daemon=True,
                         name="serve-adapt").start()

    def adapt_buckets(self, max_buckets: Optional[int] = None,
                      coverage: float = 0.995) -> Tuple[int, ...]:
        """Re-derive the nnz lane grid from observed traffic.

        Precompiles any new (row × nnz × replica) shapes FIRST, then
        swaps the grid, so post-swap traffic still never pays a
        serve-time compile.  No-op (returns the current grid) until the
        batcher has seen enough samples or when the suggestion matches
        the live grid.  Requests racing the swap route on whichever
        grid they caught — both grids' shapes are compiled.
        """
        suggestion = self.batcher.suggest_buckets(
            max_buckets=max_buckets or len(self.nnz_buckets),
            coverage=coverage)
        if not suggestion or tuple(suggestion) == self.nnz_buckets:
            return self.nnz_buckets
        self._precompile_grid(suggestion, self.row_buckets)
        self.nnz_buckets = tuple(suggestion)   # route() reads this live
        self.rebuckets += 1
        return self.nnz_buckets

    # -------------------------------------------------------- stats -------
    def stats(self) -> dict:
        """Thread-safe operability snapshot (the ``GET /status`` body):
        rolling latency percentiles + rows/s + per-tenant counts from
        the stats window, queue depths and per-lane occupancy, compile
        and reload counters, and the batcher's watchdog health."""
        snap = self.stats_window.snapshot()
        depths = self.batcher.depths()
        snap.update(
            version=self._weights.version,
            reloads=self.reloads,
            uptime_s=time.time() - self._started_at,
            compile_misses=self.compile_misses,
            precompile_seconds=self.precompile_seconds,
            batches_run=self.batcher.batches_run,
            requests_served=self.batcher.requests_served,
            device_batches=list(self.device_batches),
            lanes={str(k): v for k, v in depths["lanes"].items()},
            queued=depths["queued"],
            inflight_batches=depths["inflight_batches"],
            pipeline_depth=depths["depth"],
            nnz_buckets=list(self.nnz_buckets),
            row_buckets=list(self.row_buckets),
            rebuckets=self.rebuckets,
            health=self.batcher.health(),
            dispatch=dispatch.dispatch_report(),
            dedup=(dict(self.dedup.stats(), enabled=True,
                        rows_per_band=self._dedup_rows_per_band,
                        probe_bands=self._dedup_probe_bands)
                   if self.dedup is not None else {"enabled": False}),
        )
        return snap

    def flush(self):
        """Dispatch every queued request now instead of waiting out the
        coalescing window (end-of-stream clients, graceful drain)."""
        self.batcher.flush()

    def close(self):
        self.batcher.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def greedy_generate(api, params, prompt: np.ndarray, max_new: int,
                    max_len: Optional[int] = None,
                    extras: Optional[dict] = None) -> np.ndarray:
    """Greedy decode via prefill + cached steps; prompt (B, S0) int32."""
    b, s0 = prompt.shape
    if max_new <= 0:
        return np.asarray(prompt, dtype=np.int32).copy()
    max_len = max_len or (s0 + max_new)
    batch = {"tokens": jnp.asarray(prompt)}
    if extras:
        batch.update(extras)
    logits, cache = api.prefill(params, batch)
    # right-size the cache for generation (KV families only)
    full = api.init_cache(b, max_len)

    def grow(full_leaf, pre_leaf):
        if full_leaf.shape == pre_leaf.shape:
            return pre_leaf.astype(full_leaf.dtype)
        # find the (single) axis that differs — the sequence axis
        axes = [i for i, (a, c) in enumerate(
            zip(full_leaf.shape, pre_leaf.shape)) if a != c]
        ax = axes[0]
        return jax.lax.dynamic_update_slice_in_dim(
            full_leaf, pre_leaf.astype(full_leaf.dtype), 0, axis=ax)

    cache = jax.tree.map(grow, full, cache)
    # token bookkeeping is one preallocated buffer + vectorized numpy
    # argmax/assignment per step — not O(B) Python int()/appends
    out = np.empty((b, s0 + max_new), dtype=np.int32)
    out[:, :s0] = prompt
    nxt = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
    out[:, s0] = nxt
    cache_len = s0
    for t in range(1, max_new):
        logits, cache = api.decode_step(
            params, {"token": jnp.asarray(nxt[:, None])}, cache,
            jnp.asarray(cache_len, jnp.int32))
        nxt = np.argmax(np.asarray(logits), axis=-1).astype(np.int32)
        out[:, s0 + t] = nxt
        cache_len += 1
    return out
