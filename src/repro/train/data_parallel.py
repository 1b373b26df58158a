"""Data-parallel averaged train step: ``shard_map`` + ``psum_mean``.

The multi-device half of the streaming hot path (ROADMAP: "multi-host
data-parallel streaming over ``distributed/``"): each device of a 1-D
``("data",)`` mesh (``launch.mesh.make_data_mesh``) trains on its OWN
shard of the epoch — batches arrive stacked ``(world, B, …)`` from
``data.prefetch.group_batch_stream`` — while parameters stay
replicated:

  * every device computes the masked per-example SUM loss over its
    valid rows (``train.losses.sum_loss_with_hits_fn``; padding rows
    and shard-less devices contribute nothing);
  * the all-reduced gradient SUM is scaled by ``physical / Σ_devices
    valid`` once AFTER ``psum_mean`` (= psum / physical), landing on
    exactly the gradient of the mean loss over the union of all
    devices' real rows — uneven tails and zero-row devices change the
    weighting not at all.  Scaling after the reduction (sum-then-
    scale, not scale-then-sum) is what makes the update bitwise
    invariant to the physical device count: for power-of-two device
    counts the psum_mean division and the ``physical/total`` factor
    are exact power-of-two rescalings of the same gradient sum, so
    the same logical schedule produces bit-identical parameters
    whether its shard slots live on N devices or fold onto fewer
    (the elastic-resume property, tests/test_fault_tolerance.py).
    The L2 term is added once after the all-reduce (replicated params
    → identical on every device);
  * **elastic folding** (``logical_world > physical``): the stacked
    batch keeps its LOGICAL leading axis; each device receives a
    ``(fold, B, …)`` block and loops its ``fold = logical/physical``
    shard slots sequentially, accumulating loss/hit/row sums and the
    gradient sum in slot order before the collectives run — the
    schedule, and hence the replayed step sequence, is a function of
    the logical world only;
  * each step pays exactly TWO all-reduces — the (loss, hits, rows)
    scalar triple crosses stacked, the gradient tree crosses fused
    inside ``psum_mean`` — because collective setup cost, not payload,
    dominates small steps (hit counts ride as f32, exact far beyond
    any realistic batch); the trainer drains one replicated hits
    scalar per step exactly like the serial path;
  * the optimizer and Polyak-average update run on the all-reduced
    gradient with replicated inputs → parameters remain bitwise
    replicated without any weight broadcast, and a device that
    contributed zero rows still applies the identical global update
    (Polyak averaging cannot skew).

A device with NO valid rows this step is safe but a step where NO
device has rows cannot happen: ``group_batch_stream`` emits exactly
``max_d ceil(rows_d / B)`` steps per group, and the device attaining
the max has a non-empty batch at every one of them.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.collectives import psum_mean
from repro.distributed.grad_compression import (
    tree_compressed_allreduce_mean,
)
from repro.optim.averaging import polyak_update
from repro.optim.optimizers import Optimizer
from repro.train.steps import AveragedTrainState, TrainState

AXIS = "data"


def init_dp_error_state(params, physical: int):
    """Zero error-feedback memory for the compressed all-reduce: one
    f32 copy of every param leaf PER DATA-MESH DEVICE, stacked on a
    leading ``physical`` axis (the memory is device-local state — each
    device accumulates its own quantization residual)."""
    return jax.tree.map(
        lambda p: jnp.zeros((physical,) + tuple(p.shape), jnp.float32),
        params)


def device_put_sharded(x, mesh: Mesh):
    """Places a stacked ``(world, …)`` host array with row d on device
    d (leading-axis sharding over the mesh's data axis)."""
    return jax.device_put(x, NamedSharding(mesh, P(AXIS)))


def device_put_process_local(x_local, mesh: Mesh, logical: int):
    """Assembles the global stacked ``(logical, …)`` array from this
    process's contiguous slot block (multi-process gangs).

    ``device_put`` can only address local devices; on a mesh spanning
    processes the global array is built from each process's local
    rows — valid because ``distributed.runtime.mesh_over_processes``
    orders devices by process, so process p's slots are exactly the
    leading-axis rows its mesh devices carry."""
    sh = NamedSharding(mesh, P(AXIS))
    global_shape = (logical,) + tuple(x_local.shape[1:])
    return jax.make_array_from_process_local_data(sh, x_local,
                                                  global_shape)


def build_dp_averaged_train_step(
    loss_sum_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    *,
    l2: float = 0.0,
    donate: bool = True,
    logical_world: int = None,
    compress: "dict | None" = None,
):
    """``loss_sum_fn(params, batch, labels, valid) -> (loss_sum, hits)``
    (per-device, masked sums); returns a jitted

        ``step(carry, active, batch, labels, valid)
            -> (carry, (mean_loss, hits))``

    where ``batch``/``labels``/``valid`` are stacked
    ``(logical_world, B, …)`` arrays sharded over the mesh's data axis
    (``device_put_sharded``), ``carry`` is the replicated ``astate``,
    ``mean_loss`` is the global mean over valid rows (plus the L2
    term, matching ``mean_loss_with_preds_fn``'s parameterization) and
    ``hits`` the global correct-prediction count — both replicated
    scalars.

    ``logical_world`` (default: the mesh's data-axis size) may exceed
    the physical device count by an integer factor — each device then
    folds ``logical_world / physical`` shard slots sequentially (the
    elastic-resume path, see the module docstring).

    ``compress`` (e.g. ``{"bits": 8, "block": 256}``) swaps the exact
    fp32 ``psum_mean`` gradient exchange for the error-feedback
    compressed all-reduce (``distributed.grad_compression`` — int8
    blockwise-absmax or sign+scale on the wire, the paper family's
    b-bit storage argument applied to the gradient).  The carry then
    becomes ``(astate, err)`` with ``err`` the per-device residual
    memory from ``init_dp_error_state`` (leading ``physical`` axis,
    sharded over the mesh).  ``compress=None`` leaves the exact path
    byte-for-byte untouched.
    """
    physical = mesh.shape[AXIS]
    logical = physical if logical_world is None else int(logical_world)
    if logical % physical:
        raise ValueError(
            f"logical world {logical} is not a multiple of the mesh's "
            f"{physical} data-axis devices — shard slots cannot fold "
            "evenly")
    fold = logical // physical

    def _accumulate(params, batch, labels, valid):
        # per-device blocks arrive with a leading axis of ``fold``:
        # run each shard slot and accumulate sums in slot order
        def slot(params, f):
            batch_f = jax.tree.map(lambda x: x[f], batch)
            labels_f, valid_f = labels[f], valid[f]

            def local_objective(p):
                lsum, hits = loss_sum_fn(p, batch_f, labels_f, valid_f)
                return lsum, (lsum, hits)

            (_, (lsum, hits)), g = jax.value_and_grad(
                local_objective, has_aux=True)(params)
            # the barrier keeps XLA from folding the running sum into
            # this slot's gradient (e.g. as a scatter-add's initial
            # value), which would round each slot differently from an
            # unfolded device and break fold-invariance
            return jax.lax.optimization_barrier(
                (lsum, hits.astype(jnp.float32),
                 jnp.sum(valid_f.astype(jnp.float32)), g))

        lsum, hits_f, rows, gsum = slot(params, 0)
        for f in range(1, fold):
            l_f, h_f, r_f, g_f = slot(params, f)
            lsum = lsum + l_f
            hits_f = hits_f + h_f
            rows = rows + r_f
            gsum = jax.tree.map(jnp.add, gsum, g_f)
        return lsum, hits_f, rows, gsum

    def _apply(astate, active, grads, lsum_g, hits_g, total):
        mean_loss = lsum_g / total
        if l2:
            # replicated params → identical reg term on every device;
            # added AFTER the all-reduce so it is counted exactly once
            grads = jax.tree.map(
                lambda g, p: g + (l2 * p.astype(jnp.float32))
                .astype(g.dtype),
                grads, astate.state.params)
            mean_loss = mean_loss + 0.5 * l2 * sum(
                jnp.sum(p.astype(jnp.float32) ** 2)
                for p in jax.tree.leaves(astate.state.params))
        hits = hits_g.astype(jnp.int32)

        new_params, new_opt = optimizer.update(
            grads, astate.state.opt_state, astate.state.params,
            astate.state.step)
        avg, count = polyak_update(astate.avg_params, astate.avg_count,
                                   new_params, active)
        new_state = TrainState(new_params, new_opt,
                               astate.state.step + 1)
        return (AveragedTrainState(new_state, avg, count),
                mean_loss, hits)

    def _local(astate: AveragedTrainState, active, batch, labels, valid):
        lsum, hits_f, rows, gsum = _accumulate(
            astate.state.params, batch, labels, valid)
        # exactly TWO all-reduces per step (collective setup dominates
        # small steps): the scalar triple crosses stacked, then the
        # whole gradient tree crosses fused inside psum_mean.
        scalars = jax.lax.psum(jnp.stack([lsum, hits_f, rows]), AXIS)
        lsum_g, hits_g, total = scalars[0], scalars[1], scalars[2]
        # scale AFTER the reduction: psum_mean (= psum / physical)
        # then × physical/total lands on psum(grad lsum) / total — the
        # gradient of the mean loss over the union of all devices'
        # real rows — via exact power-of-two rescalings, so the result
        # is bitwise independent of how the logical slots fold onto
        # physical devices.  The scale is cast to each leaf's dtype: a
        # strong-f32 multiply would widen bf16 grads.
        scale = jnp.float32(physical) / total
        grads = jax.tree.map(
            lambda g: g * scale.astype(g.dtype),
            psum_mean(gsum, AXIS))
        return _apply(astate, active, grads, lsum_g, hits_g, total)

    def _local_compressed(carry, active, batch, labels, valid):
        astate, err_blk = carry
        lsum, hits_f, rows, gsum = _accumulate(
            astate.state.params, batch, labels, valid)
        scalars = jax.lax.psum(jnp.stack([lsum, hits_f, rows]), AXIS)
        lsum_g, hits_g, total = scalars[0], scalars[1], scalars[2]
        # the gradient crosses quantized: EF all-reduce returns the
        # mean of the dequantized per-device sums (= psum_mean of the
        # quantized payload), so the same post-reduction
        # physical/total scaling applies; the residual stays local
        err = jax.tree.map(lambda x: x[0], err_blk)
        grads, new_err = tree_compressed_allreduce_mean(
            gsum, err, AXIS, block=int(compress.get("block", 256)),
            bits=int(compress.get("bits", 8)))
        scale = jnp.float32(physical) / total
        grads = jax.tree.map(
            lambda g: g * scale.astype(g.dtype), grads)
        astate, mean_loss, hits = _apply(astate, active, grads, lsum_g,
                                         hits_g, total)
        new_err_blk = jax.tree.map(lambda x: x[None], new_err)
        return (astate, new_err_blk), mean_loss, hits

    if compress is None:
        smapped = shard_map(
            _local, mesh=mesh,
            in_specs=(P(), P(), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(), P()),
            # the packed-logits custom_vjp has no varying-axis rule;
            # outputs are replicated by construction (post-psum values
            # only)
            check_vma=False)
    else:
        smapped = shard_map(
            _local_compressed, mesh=mesh,
            in_specs=((P(), P(AXIS)), P(), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=((P(), P(AXIS)), P(), P()),
            check_vma=False)

    def step(carry, active, batch, labels, valid):
        carry, loss, hits = smapped(carry, active, batch, labels,
                                    valid)
        return carry, (loss, hits)

    return jax.jit(step, donate_argnums=(0,) if donate else ())
