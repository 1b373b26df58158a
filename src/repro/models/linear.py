"""Linear models over b-bit minwise-hashed codes (paper §3).

The weight lives as a (k, 2^b, C) table — the expanded 2^b·k weight
vector reshaped — and the forward pass is the fused Pallas kernel
(one-hot MXU contraction) or an XLA gather; both equal the paper's
explicit-expansion dot product (unit-tested).

Also provides ``VWLinear`` (dense linear over VW sketches) so the
paper's §5 comparison trains both methods through identical machinery.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro import perf
from repro.kernels import ops, ref


@dataclasses.dataclass(frozen=True)
class BBitLinearConfig:
    k: int
    b: int
    n_classes: int = 2
    # 'auto' → Pallas kernel on TPU, XLA gather elsewhere (interpret-mode
    # Pallas would crawl on CPU); 'always'/'never' force either path.
    use_kernel: str = "auto"
    param_dtype: str = "float32"
    normalize: bool = False      # optional 1/sqrt(k) feature scaling

    @property
    def n_out(self) -> int:
        return 1 if self.n_classes == 2 else self.n_classes

    @property
    def n_weights(self) -> int:
        return self.k * (1 << self.b) * self.n_out + self.n_out


def init_bbit_linear(cfg: BBitLinearConfig, key: Optional[jax.Array] = None):
    dtype = jnp.dtype(cfg.param_dtype)
    table = jnp.zeros((cfg.k, 1 << cfg.b, cfg.n_out), dtype)
    bias = jnp.zeros((cfg.n_out,), dtype)
    if key is not None:
        table = 0.01 * jax.random.normal(key, table.shape, dtype)
    return {"table": table, "bias": bias}


def _doubling_broadcast(v: jax.Array, n: int) -> jax.Array:
    """(C,) → (n, C) by repeated self-concatenation.  Its transpose sums
    the cotangent rows by halving — every add has two operands — so the
    gradient does not depend on how XLA fuses or vectorizes a
    reduction."""
    x = v[None]
    while x.shape[0] < n:
        x = jnp.concatenate([x, x])
    return x[:n]


@jax.custom_jvp
def _add_bias(out: jax.Array, bias: jax.Array) -> jax.Array:
    """``out + bias`` whose bias gradient sums rows in a fixed order, so
    a data-parallel step gives the same bits whether its shard slots run
    on separate devices or fold onto one."""
    return out + bias.astype(jnp.float32)


@_add_bias.defjvp
def _add_bias_jvp(primals, tangents):
    out, bias = primals
    t_out, t_bias = tangents
    return (_add_bias(out, bias),
            t_out + _doubling_broadcast(t_bias.astype(jnp.float32),
                                        out.shape[0]))


def _forced_impl(cfg: BBitLinearConfig, kernel: str, fallback: str
                 ) -> Optional[str]:
    """Map the config's ``use_kernel`` tri-state onto a perf pin:
    'always'→kernel, 'never'→the fallback arm, 'auto'→None (let
    ``perf.choose`` decide — static TPU heuristic unless a measured
    profile says otherwise)."""
    if cfg.use_kernel == "always" or cfg.use_kernel is True:
        return kernel
    if cfg.use_kernel == "never" or cfg.use_kernel is False:
        return fallback
    return None


def logits_impl(cfg: BBitLinearConfig, rows: Optional[int] = None) -> str:
    """The widened-codes dispatch choice: 'kernel' | 'gather'."""
    shape = {"k": cfg.k, "b": cfg.b, "v": 1 << cfg.b}
    if rows is not None:
        shape["rows"] = int(rows)
    return perf.choose("logits", shape,
                       impl=_forced_impl(cfg, "kernel", "gather"))


def logits_packed_impl(cfg: BBitLinearConfig,
                       rows: Optional[int] = None) -> str:
    """The packed-rows dispatch choice: 'kernel' | 'unpack'."""
    shape = {"k": cfg.k, "b": cfg.b, "v": 1 << cfg.b}
    if rows is not None:
        shape["rows"] = int(rows)
    return perf.choose("logits_packed", shape,
                       impl=_forced_impl(cfg, "kernel", "unpack"))


def bbit_logits(params, codes: jax.Array, cfg: BBitLinearConfig,
                empty: Optional[jax.Array] = None):
    """codes uint16/int32 (n, k) → logits (n, n_out) float32.

    ``empty`` (bool (n, k), zero-coded OPH only) drops the marked bins'
    contributions — the all-zero one-hot block of arXiv:1208.1259 §6.
    """
    if empty is not None:
        gathered = jnp.take_along_axis(
            params["table"][None],
            codes.astype(jnp.int32)[:, :, None, None],
            axis=2,
        )[:, :, 0, :].astype(jnp.float32)
        out = ref.pairwise_sum(jnp.where(empty[:, :, None], 0.0, gathered))
    elif logits_impl(cfg, rows=codes.shape[0]) == "kernel":
        out = ops.bbit_linear(codes.astype(jnp.int32), params["table"])
    else:
        out = ref.bbit_linear_fwd(codes, params["table"])
    if cfg.normalize:
        out = out / jnp.sqrt(jnp.float32(cfg.k))
    return _add_bias(out, params["bias"])


def bbit_logits_packed(params, packed: jax.Array, cfg: BBitLinearConfig,
                       empty_packed: Optional[jax.Array] = None):
    """Packed uint8 (n, ceil(k·b/8)) rows → logits (n, n_out) float32.

    The streaming trainer's forward: minibatches arrive in the on-disk
    packed layout and stay packed.  On the kernel path (TPU, byte-
    aligned b with 2^b within the one-hot bound, or b = 16) the Pallas
    kernels unpack b-bit codes in-register, so the (n, k) int32 code
    matrix of the old ``unpack_codes_jnp`` + ``bbit_logits`` two-step never
    materializes — and ``empty_packed`` (the ``oph_zero`` bitmask,
    np.packbits layout) is fused into the same pass instead of forcing
    the XLA gather.  Elsewhere it lowers to exactly that two-step
    inside the caller's jit (bit-identical numerics; the widened codes
    are a fused temporary).
    """
    if logits_packed_impl(cfg, rows=packed.shape[0]) == "kernel":
        out = ops.bbit_linear_packed(packed, params["table"], cfg.k,
                                     cfg.b, empty=empty_packed)
        if cfg.normalize:
            out = out / jnp.sqrt(jnp.float32(cfg.k))
        return _add_bias(out, params["bias"])
    from repro.core.bbit import unpack_codes_jnp, unpack_mask_jnp
    codes = unpack_codes_jnp(packed, cfg.k, cfg.b).astype(jnp.int32)
    empty = (unpack_mask_jnp(empty_packed, cfg.k)
             if empty_packed is not None else None)
    return bbit_logits(params, codes, cfg, empty=empty)


def bbit_scores(params, codes: jax.Array, cfg: BBitLinearConfig,
                empty: Optional[jax.Array] = None) -> jax.Array:
    """Serving-shaped scores: binary → (n,) margin, multiclass →
    (n, C) logits — the value a classifier service returns per row."""
    logits = bbit_logits(params, codes, cfg, empty=empty)
    return logits[:, 0] if cfg.n_classes == 2 else logits


def bbit_scores_packed(params, packed: jax.Array, cfg: BBitLinearConfig,
                       empty_packed: Optional[jax.Array] = None
                       ) -> jax.Array:
    """``bbit_scores`` straight off packed uint8 rows (see
    ``bbit_logits_packed``) — the fused serving hot path's back half."""
    logits = bbit_logits_packed(params, packed, cfg,
                                empty_packed=empty_packed)
    return logits[:, 0] if cfg.n_classes == 2 else logits


def predict_classes(params, codes, cfg: BBitLinearConfig) -> jax.Array:
    logits = bbit_logits(params, codes, cfg)
    if cfg.n_classes == 2:
        return (logits[:, 0] > 0).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class VWLinearConfig:
    m: int                       # number of VW buckets
    n_classes: int = 2

    @property
    def n_out(self) -> int:
        return 1 if self.n_classes == 2 else self.n_classes


def init_vw_linear(cfg: VWLinearConfig, key: Optional[jax.Array] = None):
    w = jnp.zeros((cfg.m, cfg.n_out), jnp.float32)
    if key is not None:
        w = 0.01 * jax.random.normal(key, w.shape, jnp.float32)
    return {"w": w, "bias": jnp.zeros((cfg.n_out,), jnp.float32)}


def vw_logits(params, sketches: jax.Array, cfg: VWLinearConfig):
    return sketches @ params["w"] + params["bias"]


def vw_predict(params, sketches, cfg: VWLinearConfig) -> jax.Array:
    logits = vw_logits(params, sketches, cfg)
    if cfg.n_classes == 2:
        return (logits[:, 0] > 0).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
