"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test compiles at real widths for a described (not attached) v5e
chip with the TPU compiler installed here, and checks that the compiled
HLO holds the Mosaic kernel (``tpu_custom_call``): the block-shape and
lowering rules interpret mode cannot check.  Nothing runs, so these say
nothing about results or speed.

The topology is described only inside the module fixture, never while
the file is imported: one process at a time may load the TPU library,
and every xdist worker imports every test file.  The persistent compile
cache is off around these compiles (an entry written for a described
chip cannot be read back without one).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import bbit_linear, fused_encode, hamming, minhash, oph

K, N_ROWS, NNZ, BATCH = 256, 1024, 4096, 1024
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            desc = None
            reason = f"no v5e:2x2 topology can be described here: {e}"
        try:
            if desc is None:
                pytest.skip(reason)
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("bits", [1, 8])
@pytest.mark.parametrize("scheme", ["oph", "minwise"])
def test_fused_encode_compiles(one_chip, scheme, bits):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                  sharding=one_chip)
    idx, nnz = spec((N_ROWS, NNZ), jnp.int32), spec((N_ROWS,), jnp.int32)
    if scheme == "oph":
        fn = lambda i, z, a, b: fused_encode.oph_pack_pallas(  # noqa: E731
            i, z, a, b, k=K, bits=bits)
        params = (spec((1,), jnp.uint32),) * 2
    else:
        fn = lambda i, z, a, b: fused_encode.minhash_pack_pallas(  # noqa
            i, z, a, b, bits=bits)
        params = (spec((K,), jnp.uint32),) * 2
    compiled, text = _compile(fn, idx, nnz, *params)
    assert CUSTOM_CALL in text
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("direction,c,bits,k", [
    pytest.param("fwd", 1, 8, K, id="fwd"),
    pytest.param("bwd", 1, 8, K, id="bwd"),
    pytest.param("bwd", 3, 8, K, id="bwd-c3"),
    # the paper's k=500, b=16 model: the factored arm
    pytest.param("fwd", 1, 16, 500, id="fwd-b16-k500"),
    pytest.param("bwd", 1, 16, 500, id="bwd-b16-k500")])
def test_packed_logits_compile(one_chip, direction, c, bits, k):
    v = 1 << bits
    packed = jax.ShapeDtypeStruct((BATCH, k * bits // 8), jnp.uint8,
                                  sharding=one_chip)
    if direction == "fwd":
        fn = lambda p, w: bbit_linear.bbit_linear_packed_fwd_pallas(  # noqa
            p, w, k=k, bits=bits)
        other = jax.ShapeDtypeStruct((k, v, c), jnp.float32,
                                     sharding=one_chip)
    else:
        fn = lambda p, d: bbit_linear.bbit_linear_packed_bwd_dw_pallas(  # noqa
            p, d, v, k=k, bits=bits)
        other = jax.ShapeDtypeStruct((BATCH, c), jnp.float32,
                                     sharding=one_chip)
    _, text = _compile(fn, packed, other)
    assert CUSTOM_CALL in text


@pytest.mark.parametrize("kernel", ["minhash", "oph", "logits_fwd",
                                    "logits_bwd"])
def test_unpacked_kernels_compile(one_chip, kernel):
    """The raw-minima encoders and the widened-code logits kernels."""
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                  sharding=one_chip)
    idx, nnz = spec((N_ROWS, NNZ), jnp.int32), spec((N_ROWS,), jnp.int32)
    codes, v = spec((BATCH, K), jnp.int32), 256
    fn, args = {
        "minhash": (minhash.minhash_pallas,
                    (idx, nnz) + (spec((K,), jnp.uint32),) * 2),
        "oph": (lambda i, z, a, b: oph.oph_pallas(i, z, a, b, k=K),
                (idx, nnz) + (spec((1,), jnp.uint32),) * 2),
        "logits_fwd": (bbit_linear.bbit_linear_fwd_pallas,
                       (codes, spec((K, v, 1), jnp.float32))),
        "logits_bwd": (lambda c, d: bbit_linear.bbit_linear_bwd_dw_pallas(
            c, d, v), (codes, spec((BATCH, 1), jnp.float32))),
    }[kernel]
    _, text = _compile(fn, *args)
    assert CUSTOM_CALL in text


def test_hamming_compiles(one_chip):
    query = jax.ShapeDtypeStruct((256,), jnp.uint8, sharding=one_chip)
    cands = jax.ShapeDtypeStruct((65536, 256), jnp.uint8, sharding=one_chip)
    _, text = _compile(hamming.hamming_distance_pallas, query, cands)
    assert CUSTOM_CALL in text


def test_dp_step_compiles_with_allreduce(topo, monkeypatch):
    """The data_parallel=4 streaming step on a 2x2 mesh: the packed
    logits kernels inside, the gradient all-reduce across chips."""
    from repro.models.linear import (BBitLinearConfig, bbit_logits_packed,
                                     init_bbit_linear)
    from repro.optim.optimizers import make_optimizer
    from repro.train.data_parallel import build_dp_averaged_train_step
    from repro.train.losses import sum_loss_with_hits_fn
    from repro.train.steps import init_averaged_state

    # dispatch asks jax.default_backend(); steer it to the chip's arms
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = BBitLinearConfig(k=K, b=8)
    opt = make_optimizer("adamw", 1e-2)
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    step = build_dp_averaged_train_step(
        sum_loss_with_hits_fn(
            lambda p, x: bbit_logits_packed(p, x, cfg), "logistic"),
        opt, mesh, l2=1e-6, donate=False)
    rep, dat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    astate = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
        jax.eval_shape(lambda: init_averaged_state(
            init_bbit_linear(cfg, jax.random.key(0)), opt)))
    world = len(topo.devices)
    args = (astate, jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((world, BATCH, K), jnp.uint8, sharding=dat),
            jax.ShapeDtypeStruct((world, BATCH), jnp.int32, sharding=dat),
            jax.ShapeDtypeStruct((world, BATCH), jnp.bool_, sharding=dat))
    text = step.lower(*args).compile().as_text()
    assert "all-reduce" in text
    assert text.count(CUSTOM_CALL) >= 2      # logits forward + dW
