"""Pallas TPU kernels for the perf-critical hot spots.

  minhash      — k-way multiply-shift min-hash preprocessing (paper §6)
  oph          — one-permutation hashing bin minima (arXiv:1208.1259):
                 ONE hash per nonzero vs minhash's k
  bbit_linear  — fused one-hot-expansion linear fwd/bwd (paper §3)
  vw_sketch    — VW signed feature hashing (paper §5.2)

Import ``repro.kernels.ops`` for the dispatching public API,
``repro.kernels.dispatch`` for the kernel choice behind it and
``repro.kernels.ref`` for the pure-jnp oracles.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
