"""Mixture-of-Experts layer: token-choice top-k with capacity (GShard).

Two execution paths:

  * ``mesh=None`` (smoke tests, tiny expert counts): dense fallback —
    every expert runs on every token, combined with the gate matrix.
  * ``mesh`` given: ``shard_map`` expert parallelism over the 'model'
    axis.  Activations enter replicated across 'model' (they are only
    batch-sharded), so the cheapest correct dispatch is: every model
    shard packs the full (E·C, d) buffer (sort-based, no (T,E,C)
    one-hot), processes the expert slice it owns, scatters its partial
    per-token outputs, and a single bf16 ``psum`` over 'model' combines
    them.  Wire cost 2·T·d vs ≥ 2·k·cf·T·d for an all_to_all dispatch
    of replicated tokens — ~5× fewer bytes at top-8/cf=1.25.
  * Expert weights are FSDP-sharded over the data axes (d-dim) and
    all-gathered just-in-time inside the shard_map (ZeRO-3; required to
    fit kimi-k2's 1.04T params).

Expert-count padding: when E doesn't divide the model-axis size (e.g.
granite's 40 experts on 16-way TP), storage is padded to the next
multiple (dead slots never routed to — the router's logit matrix keeps
exactly E outputs).

Token-choice semantics match the published configs; overflow beyond
``capacity`` (factor 1.25) is dropped, GShard-style.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig

EXPERT_PAD_TO = 16   # default: model-axis size of the production mesh


def padded_experts(cfg: ArchConfig) -> int:
    e = cfg.moe_experts
    pad = max(getattr(cfg, "moe_pad_to", EXPERT_PAD_TO), EXPERT_PAD_TO)
    return ((e + pad - 1) // pad) * pad


def init_moe_params(cfg: ArchConfig, key, dtype) -> dict:
    e_store = padded_experts(cfg)
    d, f = cfg.d_model, cfg.moe_d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale_in = d ** -0.5
    scale_out = f ** -0.5
    p = {
        "router": (jax.random.normal(k1, (d, cfg.moe_experts)) * scale_in
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (e_store, d, f)) * scale_in
                   ).astype(dtype),
        "w_up": (jax.random.normal(k3, (e_store, d, f)) * scale_in
                 ).astype(dtype),
        "w_down": (jax.random.normal(k4, (e_store, f, d)) * scale_out
                   ).astype(dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        ks = jax.random.split(k5, 3)
        p["shared"] = {
            "w_gate": (jax.random.normal(ks[0], (d, fs)) * scale_in
                       ).astype(dtype),
            "w_up": (jax.random.normal(ks[1], (d, fs)) * scale_in
                     ).astype(dtype),
            "w_down": (jax.random.normal(ks[2], (fs, d)) * scale_out
                       ).astype(dtype),
        }
    return p


def moe_param_pspecs(cfg: ArchConfig, dp_axes=("data",)) -> dict:
    """Experts over 'model' (EP); d-dim over data axes (FSDP).

    weight_stationary serving mode 2D-shards the expert dim over
    (data…, model) instead — experts fully resident per device, tokens
    travel (§Perf: kimi decode collective term)."""
    dshard = tuple(dp_axes) if dp_axes else None
    if cfg.moe_serving_dispatch == "weight_stationary":
        all_axes = tuple(dp_axes) + ("model",)
        p = {
            "router": P(None, None),
            "w_gate": P(all_axes, None, None),
            "w_up": P(all_axes, None, None),
            "w_down": P(all_axes, None, None),
        }
        if cfg.n_shared_experts:
            p["shared"] = {"w_gate": P(None, "model"),
                           "w_up": P(None, "model"),
                           "w_down": P("model", None)}
        return p
    p = {
        "router": P(None, None),
        "w_gate": P("model", dshard, None),
        "w_up": P("model", dshard, None),
        "w_down": P("model", None, dshard),
    }
    if cfg.n_shared_experts:
        p["shared"] = {
            "w_gate": P(None, "model"),
            "w_up": P(None, "model"),
            "w_down": P("model", None),
        }
    return p


def _routing(x2d: jax.Array, router: jax.Array, top_k: int):
    """x2d (T, d) → gates (T, k) fp32, expert ids (T, k) int32."""
    logits = x2d.astype(jnp.float32) @ router          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32)


def _dense_fallback(x2d, params, cfg: ArchConfig):
    """All experts on all tokens (smoke-test path; E is tiny there)."""
    gates, idx = _routing(x2d, params["router"], cfg.moe_top_k)
    t = x2d.shape[0]
    e = cfg.moe_experts
    dense_gates = jnp.zeros((t, e), jnp.float32)
    dense_gates = dense_gates.at[
        jnp.arange(t)[:, None], idx].add(gates)
    wg, wu, wd = (params["w_gate"][:e], params["w_up"][:e],
                  params["w_down"][:e])
    h = jnp.einsum("td,edf->tef", x2d, wg)
    h = jax.nn.silu(h) * jnp.einsum("td,edf->tef", x2d, wu)
    y = jnp.einsum("tef,efd->ted", h, wd)
    return jnp.einsum("ted,te->td", y.astype(jnp.float32),
                      dense_gates).astype(x2d.dtype)


def _pack_by_expert(x2d, gates, idx, n_slots: int, capacity: int):
    """Sort-based capacity packing into an (n_slots·C, d) buffer.

    Returns (buf, slot (T,k; n_slots·C = dropped), gates w/ drops zeroed).
    """
    t, k = idx.shape
    flat_e = idx.reshape(-1)
    sort_ix = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_ix]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(n_slots),
                                 side="left")
    pos_in_e = jnp.arange(t * k) - seg_start[sorted_e]
    keep = pos_in_e < capacity
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos_in_e,
                            n_slots * capacity)
    slot_flat = jnp.zeros((t * k,), jnp.int32).at[sort_ix].set(
        slot_sorted.astype(jnp.int32))
    slot = slot_flat.reshape(t, k)
    token_of_sorted = sort_ix // k
    buf = jnp.zeros((n_slots * capacity + 1, x2d.shape[1]), x2d.dtype)
    buf = buf.at[slot_sorted].set(x2d[token_of_sorted], mode="drop")
    gates = jnp.where(slot == n_slots * capacity, 0.0, gates)
    return buf[:-1], slot, gates


def _expert_ffn(xe, w_gate, w_up, w_down):
    """xe (E_l, C', d) through per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _weight_stationary_ffn(x, params, cfg: ArchConfig, mesh):
    """Serving dispatch: experts 2D-sharded over (dp…, model), fully
    resident; tokens all_to_all over 'data' within each model column;
    bf16 psum over 'model' combines columns.  Wire bytes per layer ≈
    2·(E_col·C·d) instead of the FSDP weight gather (≈ E_local·3·d·f),
    a ~2000× reduction at decode batch sizes (EXPERIMENTS.md §Perf)."""
    b, s, d = x.shape
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mdl = mesh.shape["model"]
    dpn = 1
    for a in dp_axes:
        dpn *= mesh.shape[a]
    n_dev = dpn * mdl
    e_store = padded_experts(cfg)          # multiple of n_dev via config
    assert e_store % n_dev == 0, (e_store, n_dev)
    e_per_dev = e_store // n_dev
    all_axes = dp_axes + ("model",)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  P(all_axes, None, None), P(all_axes, None, None),
                  P(all_axes, None, None)),
        out_specs=P(dp_axes, None, None),
    )
    def _ws(x_l, router, w_gate, w_up, w_down):
        bl, sl, _ = x_l.shape
        t_l = bl * sl
        m_idx = jax.lax.axis_index("model")
        x2d = x_l.reshape(t_l, d)
        gates, idx = _routing(x2d, router, cfg.moe_top_k)
        cap = int(cfg.moe_capacity * cfg.moe_top_k * t_l
                  // cfg.moe_experts) + 1
        buf, slot, gates = _pack_by_expert(x2d, gates, idx, e_store, cap)
        buf = buf.reshape(e_store, cap, d)
        # experts owned by model column m: e with (e//e_per_dev)%mdl==m;
        # i.e. e = (q*mdl + m)*e_per_dev + r over data-rows q
        col_experts = ((jnp.arange(dpn)[:, None] * mdl + m_idx)
                       * e_per_dev
                       + jnp.arange(e_per_dev)[None, :]).reshape(-1)
        sub = jnp.take(buf, col_experts, axis=0)     # (dpn·e_pd, cap, d)
        sub = sub.reshape(dpn, e_per_dev, cap, d)
        for ax in dp_axes:                           # tokens → owners
            sub = jax.lax.all_to_all(sub, ax, split_axis=0,
                                     concat_axis=0, tiled=False)
        # now leading dpn indexes SOURCE data-row; my experts' tokens
        xe = sub.transpose(1, 0, 2, 3).reshape(e_per_dev, dpn * cap, d)
        ye = _expert_ffn(xe, w_gate, w_up, w_down)
        ye = ye.reshape(e_per_dev, dpn, cap, d).transpose(1, 0, 2, 3)
        for ax in reversed(dp_axes):                 # results → sources
            ye = jax.lax.all_to_all(ye, ax, split_axis=0,
                                    concat_axis=0, tiled=False)
        ye = ye.reshape(dpn * e_per_dev, cap, d)
        # scatter column results into the global (E·C) slot space
        ye_col = jnp.zeros((e_store * cap + 1, d), x_l.dtype)
        rowsel = (col_experts[:, None] * cap
                  + jnp.arange(cap)[None, :]).reshape(-1)
        ye_col = ye_col.at[rowsel].set(
            ye.reshape(-1, d).astype(x_l.dtype))
        per_assign = ye_col[slot.reshape(-1)].reshape(
            t_l, cfg.moe_top_k, d)
        y = jnp.einsum("tkd,tk->td", per_assign,
                       gates.astype(x_l.dtype),
                       preferred_element_type=jnp.float32)
        return jax.lax.psum(y.astype(x_l.dtype), "model"
                            ).reshape(bl, sl, d)

    return _ws(x, params["router"], params["w_gate"], params["w_up"],
               params["w_down"])


def moe_ffn(
    x: jax.Array,                 # (B, S, d)
    params: dict,
    cfg: ArchConfig,
    mesh: Optional[Mesh] = None,
    serving: bool = False,
) -> jax.Array:
    """Top-k MoE FFN; EP over 'model' when a mesh is provided."""
    b, s, d = x.shape
    if mesh is None or "model" not in mesh.axis_names:
        y = _dense_fallback(x.reshape(-1, d), params, cfg)
        out = y.reshape(b, s, d)
    elif (serving and cfg.moe_serving_dispatch == "weight_stationary"
          and len([a for a in ("pod", "data")
                   if a in mesh.axis_names]) == 1):
        # (single data axis; the multi-pod variant would chain
        # all_to_alls hierarchically — not needed for the §Perf cells)
        out = _weight_stationary_ffn(x, params, cfg, mesh)
        if cfg.n_shared_experts:
            sh = params["shared"]
            h = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
            out = out + (h @ sh["w_down"]).astype(out.dtype)
        return out
    else:
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        ep = mesh.shape["model"]
        e_store = padded_experts(cfg)
        e_local = e_store // ep
        w_specs = (P("model", dp_axes or None, None),
                   P("model", dp_axes or None, None),
                   P("model", None, dp_axes or None))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(dp_axes, None, None), P(None, None)) + w_specs,
            out_specs=P(dp_axes, None, None),
        )
        def _sharded(x_l, router, w_gate, w_up, w_down):
            bl, sl, _ = x_l.shape
            t_l = bl * sl
            m_idx = jax.lax.axis_index("model")
            x2d = x_l.reshape(t_l, d)
            gates, idx = _routing(x2d, router, cfg.moe_top_k)
            cap = int(cfg.moe_capacity * cfg.moe_top_k * t_l
                      // cfg.moe_experts) + 1
            buf, slot, gates = _pack_by_expert(
                x2d, gates, idx, e_store, cap)
            # my expert slice: rows [m_idx·e_local·cap, +e_local·cap)
            xe = jax.lax.dynamic_slice_in_dim(
                buf, m_idx * (e_local * cap), e_local * cap, axis=0
            ).reshape(e_local, cap, d)
            # FSDP: gather expert weights' data-sharded dim just-in-time.
            # P(("pod","data")) tiles pod-major — regather minor-first.
            for ax_name in reversed(dp_axes):
                w_gate = jax.lax.all_gather(w_gate, ax_name, axis=1,
                                            tiled=True)
                w_up = jax.lax.all_gather(w_up, ax_name, axis=1,
                                          tiled=True)
                w_down = jax.lax.all_gather(w_down, ax_name, axis=2,
                                            tiled=True)
            ye = _expert_ffn(xe, w_gate, w_up, w_down)   # (E_l, cap, d)
            # per-assignment gather: local slots resolve, others → 0
            ye_flat = ye.reshape(e_local * cap, d).astype(x_l.dtype)
            local_slot = slot - m_idx * (e_local * cap)
            in_range = (local_slot >= 0) & (local_slot < e_local * cap)
            safe = jnp.where(in_range, local_slot, 0)
            per_assign = ye_flat[safe.reshape(-1)].reshape(
                t_l, cfg.moe_top_k, d)
            per_assign = jnp.where(in_range[..., None], per_assign,
                                   jnp.zeros((), x_l.dtype))
            # bf16 operands, f32 accumulation (keeps the (T,k,d) buffer
            # at input precision — it was the largest MoE transient)
            y = jnp.einsum("tkd,tk->td", per_assign,
                           gates.astype(x_l.dtype),
                           preferred_element_type=jnp.float32)
            y = jax.lax.psum(y.astype(x_l.dtype), "model")
            return y.reshape(bl, sl, d)

        out = _sharded(x, params["router"], params["w_gate"],
                       params["w_up"], params["w_down"])

    if cfg.n_shared_experts:
        sh = params["shared"]
        h = jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])
        out = out + (h @ sh["w_down"]).astype(out.dtype)
    return out
