"""Hashing-scheme registry: minwise (k-permutation) vs OPH, one API.

A *scheme* is the full recipe sparse-document → (n, k) b-bit code
matrix.  The paper's pipeline hard-codes one recipe (k multiply-shift
permutations, §2/§6); OPH (arXiv:1208.1259) is a second, k×-cheaper
recipe producing statistically equivalent codes.  Everything downstream
of preprocessing — bit-packed shards, the liblinear trainer, the
serving engine — consumes codes through this registry so schemes stay
interchangeable:

    sch = make_scheme("oph", k=256, seed=0)
    codes = sch.encode_padded(idx, nnz, b=8)        # offline, numpy in/out
    codes, empty = sch.encode_jnp(idx, mask, b=8)   # jit-able, serving
    packed, em = sch.encode_packed(idx, nnz, b=8)   # on-disk bytes direct

``encode_jnp`` returns an optional per-bin ``empty`` mask (only the
zero-coded OPH variant produces one; ``None`` otherwise) which
``bbit_logits`` uses to zero out empty-bin contributions.
Registered schemes: ``minwise``, ``oph`` (densified), ``oph_zero``.

The ``*_device`` variants return un-synced jax arrays so the streaming
preprocessor can keep several chunks in flight (double buffering);
``encode_packed*`` is the device-resident hot path — hash, b-bit mask
and byte packing fused on the accelerator (Pallas kernel on TPU, XLA
elsewhere), so only ``n·ceil(k·b/8)`` bytes cross to the host.
``encode_packed_jit`` is the same fused recipe as a traceable function
(no host-side tile loop): the serving engine composes it with
``bbit_logits_packed`` into ONE jitted raw-docs→scores dispatch per
shape bucket, byte-identical to the offline writers.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bbit import pack_codes_jnp, pack_mask_jnp
from repro.core.minhash import minhash_jnp
from repro.core.oph import (
    OPH_EMPTY_CODE,
    OPHHash,
    densify_rotation,
    oph_bin_minima_jnp,
)
from repro.core.universal_hash import MultiplyShiftHash, _fmix32_numpy

SCHEMES: Dict[str, Type["HashingScheme"]] = {}


def register_scheme(name: str):
    def deco(cls):
        cls.name = name
        SCHEMES[name] = cls
        return cls
    return deco


def make_scheme(name: str, k: int, seed: int) -> "HashingScheme":
    if name not in SCHEMES:
        raise ValueError(
            f"unknown hashing scheme {name!r}; have {sorted(SCHEMES)}")
    return SCHEMES[name](k=k, seed=seed)


def _prefix_mask(indices: jax.Array, nnz) -> jax.Array:
    m = indices.shape[1]
    return (jnp.arange(m, dtype=jnp.int32)[None, :]
            < jnp.asarray(nnz)[:, None])


# -- tiled XLA encode: compile-count O(1) in the pad width ------------------
#
# The packed path streams fixed-width nonzero tiles through ONE compiled
# minima graph and accumulates the running min on the device — the same
# structure as the Pallas kernels' nnz grid dimension.  Pad width then
# never appears in a jit signature: a heavy-tailed corpus compiles ONE
# tile graph + one finisher (per row bucket) instead of one graph per
# chunk width (the PR-1 recompile pathology).  Tiles past every row's
# nnz are skipped on the host, so over-padded chunks cost nothing.
ENCODE_TILE_M = 512


@functools.partial(jax.jit, static_argnames=("k",))
def _oph_tile_step(vals, tile, nnz, col0, a, bv, k):
    """vals ← min(vals, bin minima of one nonzero tile): ONE dispatch
    (and one compiled graph per tile width) per tile."""
    col = col0 + jnp.arange(tile.shape[1], dtype=jnp.int32)
    mask = col[None, :] < nnz[:, None]
    t, _ = oph_bin_minima_jnp(tile, mask, a, bv, k)
    return jnp.minimum(vals, t)


@jax.jit
def _minwise_tile_step(vals, tile, nnz, col0, a, bv):
    col = col0 + jnp.arange(tile.shape[1], dtype=jnp.int32)
    mask = col[None, :] < nnz[:, None]
    return jnp.minimum(vals, minhash_jnp(tile, mask, a, bv))


def _stream_tiles(indices: np.ndarray, nnz, k: int, tile_step):
    """Running min of ``tile_step`` over fixed-width nonzero tiles.

    A tile fully past ``max(nnz)`` is all-padding (its mask is all
    False) and contributes only sentinels — skipped on the host, so the
    effective hashed width is ceil(max_nnz/T)·T however generously the
    chunk was padded.
    """
    indices = np.asarray(indices)
    n, m = indices.shape
    nnz = np.asarray(nnz)
    nnz_j = jnp.asarray(nnz)
    vals = jnp.full((n, k), jnp.uint32(0xFFFFFFFF), jnp.uint32)
    T = ENCODE_TILE_M
    m_live = min(m, int(nnz.max(initial=0)))
    for lo in range(0, m_live, T):
        span = min(T, m - lo)
        if span == T:
            tile = indices[:, lo: lo + T]
        else:
            tile = np.zeros((n, T), dtype=indices.dtype)
            tile[:, :span] = indices[:, lo: lo + span]
        vals = tile_step(vals, jnp.asarray(tile), nnz_j,
                         jnp.asarray(np.int32(lo)))
    return vals


@functools.partial(jax.jit, static_argnames=("b",))
def _minwise_finish_packed(z, b):
    codes = (z & jnp.uint32((1 << b) - 1)).astype(jnp.uint16)
    return pack_codes_jnp(codes, b)


@functools.partial(jax.jit, static_argnames=("b", "densify"))
def _oph_finish_packed(vals, b, densify):
    empty = vals == jnp.uint32(0xFFFFFFFF)
    mask_b = jnp.uint32((1 << b) - 1)
    if densify:
        vals, _ = densify_rotation(vals, empty)
        # all-empty rows keep the sentinel → all-ones low bits, exactly
        # what packing the OPH_EMPTY_CODE-marked reference matrix yields
        codes = (vals & mask_b).astype(jnp.uint16)
    else:
        codes = jnp.where(empty, jnp.uint16(0),
                          (vals & mask_b).astype(jnp.uint16))
    return pack_codes_jnp(codes, b), pack_mask_jnp(empty)


class HashingScheme:
    """Base: sparse rows → (n, k) uint16 b-bit codes."""

    name: str = "?"

    def __init__(self, k: int, seed: int):
        self.k = k
        self.seed = seed

    @property
    def hash_evals_per_nonzero(self) -> int:
        """Hash evaluations issued per nonzero (the Table-2 cost driver)."""
        raise NotImplementedError

    # -- dispatch (routed through repro.kernels.dispatch) --------------------

    def _encode_shape(self, indices, b: int) -> dict:
        return {"scheme": self.name, "k": self.k, "b": int(b),
                "rows": int(indices.shape[0]),
                "nnz": int(indices.shape[1])}

    def _choose_encode(self, indices, b: int, use_kernel: bool) -> str:
        """Kernel-vs-XLA choice for unpacked encode.  ``use_kernel=False``
        pins the XLA arm (the historical contract); True defers to
        ``dispatch.choose`` (TPU→Pallas)."""
        from repro.kernels import dispatch
        return dispatch.choose("encode", self._encode_shape(indices, b),
                           impl=None if use_kernel else "xla")

    def _fused_pack(self, indices, b: int, use_kernel: bool = True) -> bool:
        """Fused encode→pack choice — shared with the serving engine via
        ``ops.fused_encode_on_device`` (the single predicate both the
        offline writers and the jitted hot path branch on)."""
        from repro.kernels import ops
        return ops.fused_encode_on_device(
            int(b), scheme=self.name, k=self.k,
            rows=int(indices.shape[0]), nnz=int(indices.shape[1]),
            impl=None if use_kernel else "xla")

    def encode_jnp(
        self, indices: jax.Array, mask: jax.Array, b: int,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """jit-able path → (codes int32 (n, k), empty mask or None)."""
        raise NotImplementedError

    def encode_device(
        self, indices, nnz, b: int, *, use_kernel: bool = True,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """One padded chunk → un-synced (codes, empty|None) jax arrays.

        Kernel-backed on TPU; XLA-compiled jnp elsewhere (interpret-mode
        Pallas would crawl on CPU).  Dispatch returns immediately, so
        callers can pipeline chunks (double buffering) before syncing.
        """
        raise NotImplementedError

    def encode_packed_device(
        self, indices, nnz, b: int, *, use_kernel: bool = True,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """One padded chunk → un-synced (packed uint8 (n, ceil(k·b/8)),
        packed empty bitmask or None) — the fused device-resident path.

        Bytes are bit-identical to ``pack_codes`` over ``encode_padded``
        output (and ``np.packbits`` over the empty mask): the shard
        writer appends them verbatim.
        """
        raise NotImplementedError

    def encode_packed_jit(
        self, indices: jax.Array, nnz: jax.Array, b: int,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Traceable fused encode→pack — the serving hot path's front
        half → (packed uint8 (n, ceil(k·b/8)), packed empty | None).

        Unlike ``encode_packed_device`` (a host-side driver that streams
        fixed-width tiles through its own jitted steps) this composes
        INSIDE a caller's jit, so an engine can fuse raw padded docs →
        packed codes → ``bbit_logits_packed`` scores into one device
        dispatch.  Dispatch mirrors ``ops.fused_encode_on_device``: the
        Pallas fused kernel on TPU, pure-XLA hash+pack elsewhere.
        Output bytes are bit-identical to ``encode_packed_device``'s.
        """
        raise NotImplementedError

    def encode_padded(
        self, indices: np.ndarray, nnz: np.ndarray, b: int,
        *, use_kernel: bool = True,
    ) -> np.ndarray:
        """Offline path for one padded chunk → uint16 (n, k) codes.

        Zero-coded schemes mark empty bins with ``OPH_EMPTY_CODE`` in
        the returned matrix.
        """
        codes, empty = self.encode_device(indices, nnz, b,
                                          use_kernel=use_kernel)
        out = np.asarray(codes).astype(np.uint16)
        if empty is not None:
            out[np.asarray(empty)] = OPH_EMPTY_CODE
        return out

    def encode_packed(
        self, indices: np.ndarray, nnz: np.ndarray, b: int,
        *, use_kernel: bool = True,
    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Synchronous ``encode_packed_device`` → numpy arrays."""
        packed, empty = self.encode_packed_device(indices, nnz, b,
                                                  use_kernel=use_kernel)
        return (np.asarray(packed),
                None if empty is None else np.asarray(empty))

    def encode_packed_numpy(
        self, indices: np.ndarray, nnz: np.ndarray, b: int,
    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Pure-numpy twin of ``encode_packed``: bit-identical bytes,
        zero device dispatches.

        This is the serving dedup cache's key path — a single document
        is fingerprinted with ONE host-side hash pass (no padded device
        round-trip), and because the bytes equal the device encode
        bit-for-bit, packed-code equality on the host transfers exactly
        to score equality on the device (tests/test_dedup_cache.py
        enforces the parity per scheme).  Pad width never affects the
        output (padding is masked), so callers may pad however is
        cheapest.
        """
        raise NotImplementedError


@register_scheme("minwise")
class MinwiseScheme(HashingScheme):
    """The paper's scheme: k independent multiply-shift permutations."""

    def __init__(self, k: int, seed: int):
        super().__init__(k, seed)
        self.family = MultiplyShiftHash.make(k, seed)
        self._a, self._b = self.family.params()

    @property
    def hash_evals_per_nonzero(self) -> int:
        return self.k

    def encode_jnp(self, indices, mask, b):
        z = minhash_jnp(indices, mask, self._a, self._b)
        codes = (z & jnp.uint32((1 << b) - 1)).astype(jnp.int32)
        return codes, None

    def encode_device(self, indices, nnz, b, *, use_kernel=True):
        indices = jnp.asarray(indices)
        if self._choose_encode(indices, b, use_kernel) == "pallas":
            from repro.kernels import ops
            return ops.minhash_bbit(indices, jnp.asarray(nnz),
                                    self._a, self._b, b), None
        codes, _ = self.encode_jnp(indices, _prefix_mask(indices, nnz), b)
        return codes, None

    def encode_packed_device(self, indices, nnz, b, *, use_kernel=True):
        from repro.kernels import ops
        if self._fused_pack(indices, b, use_kernel):
            return ops.minhash_packed(jnp.asarray(indices),
                                      jnp.asarray(nnz),
                                      self._a, self._b, b), None
        z = _stream_tiles(
            indices, nnz, self.k,
            lambda v, t, nz, c0: _minwise_tile_step(v, t, nz, c0,
                                                    self._a, self._b))
        return _minwise_finish_packed(z, b), None

    def encode_packed_jit(self, indices, nnz, b):
        from repro.kernels import ops
        if self._fused_pack(indices, b):
            return ops.minhash_packed(indices, nnz,
                                      self._a, self._b, b), None
        z = minhash_jnp(indices, _prefix_mask(indices, nnz),
                        self._a, self._b)
        return _minwise_finish_packed(z, b), None

    # k-chunking bounds the (n, m, chunk) intermediate the same way
    # minhash_jnp's m_chunk/k_chunk tiling does on device.
    _NUMPY_K_CHUNK = 64

    def encode_packed_numpy(self, indices, nnz, b):
        from repro.core.bbit import pack_codes
        indices = np.asarray(indices)
        n, m = indices.shape
        mask = (np.arange(m, dtype=np.int64)[None, :]
                < np.asarray(nnz, dtype=np.int64)[:, None])
        t = indices.astype(np.uint32)[:, :, None]
        a_np = np.asarray(self.family.a, dtype=np.uint32)
        b_np = np.asarray(self.family.b, dtype=np.uint32)
        z = np.empty((n, self.k), dtype=np.uint32)
        sentinel = np.uint32(0xFFFFFFFF)
        for lo in range(0, self.k, self._NUMPY_K_CHUNK):
            hi = min(lo + self._NUMPY_K_CHUNK, self.k)
            h = _fmix32_numpy(a_np[None, None, lo:hi] * t
                              + b_np[None, None, lo:hi])
            z[:, lo:hi] = np.where(mask[:, :, None], h, sentinel).min(axis=1)
        codes = (z & np.uint32((1 << b) - 1)).astype(np.uint16)
        return pack_codes(codes, b), None


@register_scheme("oph")
class OPHScheme(HashingScheme):
    """One-permutation hashing, densified by rotation: k valid codes
    from ONE hash evaluation per nonzero."""

    densify: bool = True

    def __init__(self, k: int, seed: int):
        super().__init__(k, seed)
        self.family = OPHHash.make(k, seed)
        self._a, self._b = self.family.params()

    @property
    def hash_evals_per_nonzero(self) -> int:
        return 1

    def _finish(self, vals, empty, b):
        if not self.densify and b > 15:
            raise ValueError("oph_zero reserves 0xFFFF: b must be <= 15")
        if self.densify:
            vals, empty = densify_rotation(vals, empty)
            codes = (vals & jnp.uint32((1 << b) - 1)).astype(jnp.int32)
            return codes, None       # fixed-width: minwise-compatible
        codes = (vals & jnp.uint32((1 << b) - 1)).astype(jnp.int32)
        return codes, empty

    def encode_jnp(self, indices, mask, b):
        vals, empty = oph_bin_minima_jnp(
            indices, mask, self._a, self._b, self.k)
        return self._finish(vals, empty, b)

    def encode_device(self, indices, nnz, b, *, use_kernel=True):
        indices = jnp.asarray(indices)
        if self._choose_encode(indices, b, use_kernel) == "pallas":
            from repro.kernels import ops
            vals = ops.oph(indices, jnp.asarray(nnz),
                           self._a, self._b, self.k)
            empty = vals == jnp.uint32(0xFFFFFFFF)
            return self._finish(vals, empty, b)
        return self.encode_jnp(indices, _prefix_mask(indices, nnz), b)

    def encode_packed_device(self, indices, nnz, b, *, use_kernel=True):
        from repro.kernels import ops
        if not self.densify and b > 15:
            raise ValueError("oph_zero reserves 0xFFFF: b must be <= 15")
        if self._fused_pack(indices, b, use_kernel):
            packed, empty = ops.oph_packed(
                jnp.asarray(indices), jnp.asarray(nnz),
                self._a, self._b, self.k, b,
                densify=self.densify)
            return packed, (None if self.densify else empty)
        vals = _stream_tiles(
            indices, nnz, self.k,
            lambda v, t, nz, c0: _oph_tile_step(v, t, nz, c0, self._a,
                                                self._b, self.k))
        packed, empty = _oph_finish_packed(vals, b, self.densify)
        return packed, (None if self.densify else empty)

    def encode_packed_jit(self, indices, nnz, b):
        from repro.kernels import ops
        if not self.densify and b > 15:
            raise ValueError("oph_zero reserves 0xFFFF: b must be <= 15")
        if self._fused_pack(indices, b):
            packed, empty = ops.oph_packed(indices, nnz, self._a,
                                           self._b, self.k, b,
                                           densify=self.densify)
            return packed, (None if self.densify else empty)
        vals, _ = oph_bin_minima_jnp(
            indices, _prefix_mask(indices, nnz), self._a, self._b, self.k)
        packed, empty = _oph_finish_packed(vals, b, self.densify)
        return packed, (None if self.densify else empty)

    def encode_packed_numpy(self, indices, nnz, b):
        indices = np.asarray(indices)
        n, m = indices.shape
        lens = np.minimum(np.asarray(nnz, dtype=np.int64), m)
        mask = np.arange(m, dtype=np.int64)[None, :] < lens[:, None]
        # ragged fast path: hash + scatter-min touch only real nonzeros
        # (a padded pass spends most of its time on the pad lanes of
        # the widest doc in the batch) — bit-identical minima
        return self.encode_packed_numpy_ragged(indices[mask], lens, b)

    def encode_packed_numpy_ragged(self, tokens, lens, b):
        """Ragged host encode: ``tokens`` is the row-major concat of
        every doc's (already id-folded) nonzeros, ``lens`` the per-doc
        counts.  Same bytes as ``encode_packed_numpy`` with no pad
        lanes materialized at all — the serving dedup key path calls
        this directly so per-row cost tracks true nnz."""
        from repro.core.bbit import pack_codes
        from repro.core.oph import (OPH_EMPTY_CODE,
                                    densify_rotation_numpy,
                                    oph_bin_minima_ragged_numpy,
                                    split_zero_codes)
        if not self.densify and b > 15:
            raise ValueError("oph_zero reserves 0xFFFF: b must be <= 15")
        vals, empty = oph_bin_minima_ragged_numpy(tokens, lens,
                                                  self.family)
        if self.densify:
            # rotation densify is row-independent and the identity on
            # fully-occupied rows (the common case at real document
            # sizes: P(empty bin) = (1-1/k)^nnz), so only rows that
            # actually have an empty bin go through it
            need = empty.any(axis=1)
            if need.any():
                sub_vals, sub_empty = densify_rotation_numpy(
                    vals[need], empty[need])
                vals[need] = sub_vals
                empty[need] = sub_empty
        codes = (vals & np.uint32((1 << b) - 1)).astype(np.uint16)
        codes = np.where(empty, OPH_EMPTY_CODE, codes)
        if self.densify:
            # all-empty rows keep OPH_EMPTY_CODE → all-ones low b bits,
            # matching _oph_finish_packed's sentinel bytes exactly
            return pack_codes(codes, b), None
        codes0, empty = split_zero_codes(codes)
        return pack_codes(codes0, b), np.packbits(empty, axis=1)


@register_scheme("oph_zero")
class OPHZeroScheme(OPHScheme):
    """Zero-coded OPH: empty bins carry no signal (ragged codes +
    ``OPH_EMPTY_CODE`` sentinel / empty mask)."""

    densify = False
