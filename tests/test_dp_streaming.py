"""Data-parallel streaming: psum_mean dtype law, dp-step gradient math
vs a single-device reference, uneven shard groups (zero-row devices),
and kill/resume bitwise determinism under shard_map.

Subprocess tests run on 2 fake XLA devices (the main pytest process
keeps its single real device — see conftest).  The in-process variants
at the bottom only run when the process ALREADY sees ≥ 2 devices: CI's
multi-device tier-1 job sets XLA_FLAGS=--xla_force_host_platform_
device_count=2 so the shard_map path is exercised on CPU-only runners
without subprocess indirection."""
import numpy as np
import pytest

import jax

from conftest import run_in_subprocess

_DP_COMMON = """
    import tempfile, numpy as np, jax, jax.numpy as jnp
    from repro.data import (SynthRcv1Config, generate_arrays,
                            preprocess_and_save, shard_row_counts)
    from repro.models.linear import BBitLinearConfig
    from repro.train import fit_streaming

    def make_archive(d, n_docs=240, k=16, b=4, n_shards=3, scheme="minwise"):
        cfg = SynthRcv1Config(seed=11, topic_tokens=150,
                              background_frac=0.35,
                              max_pairs_per_doc=2000,
                              max_triples_per_doc=1000)
        rows, labels = generate_arrays(n_docs, cfg)
        preprocess_and_save(d, rows, labels, k=k, b=b, seed=1,
                            n_shards=n_shards, scheme=scheme, chunk=64)
        return rows, labels
"""


def test_psum_mean_preserves_dtype_under_shard_map():
    """Satellite fix: psum(x)/psum(1) used to promote bf16 → f32 via
    weak int typing; the count must cast to x.dtype.  Also checks the
    pytree form (whole gradient trees all-reduce in one call)."""
    run_in_subprocess("""
        import functools, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.distributed import psum_mean
        from repro.launch.mesh import make_data_mesh
        mesh = make_data_mesh(2)
        tree = {"a": jnp.arange(8, dtype=jnp.bfloat16).reshape(2, 4),
                "b": jnp.ones((2, 3), jnp.float32)}

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("data"),), out_specs=P(None))
        def mean(t):
            out = psum_mean(jax.tree.map(lambda x: x[0], t), "data")
            return jax.tree.map(lambda x: x[None], out)

        out = mean(tree)
        assert out["a"].dtype == jnp.bfloat16, out["a"].dtype
        assert out["b"].dtype == jnp.float32
        want = np.asarray(tree["a"], np.float32).mean(axis=0)
        np.testing.assert_allclose(
            np.asarray(out["a"][0], np.float32), want, atol=0.05)
        np.testing.assert_allclose(np.asarray(out["b"][0]), 1.0)
        print("OK")
    """, devices=2)


def test_dp_step_matches_single_device_gradient_math():
    """One dp step over ragged device batches == one plain step over
    the concatenated valid rows (global row-weighted mean + L2)."""
    run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_data_mesh
        from repro.models.linear import (BBitLinearConfig, bbit_logits_packed,
                                         init_bbit_linear)
        from repro.optim.optimizers import make_optimizer
        from repro.train import (build_dp_averaged_train_step,
                                 device_put_sharded, init_averaged_state,
                                 mean_loss_with_preds_fn,
                                 sum_loss_with_hits_fn)
        from repro.core.bbit import pack_codes
        k, b, B, l2 = 16, 4, 6, 1e-3
        cfg = BBitLinearConfig(k=k, b=b)
        fwd = lambda p, pk: bbit_logits_packed(p, pk, cfg)
        mesh = make_data_mesh(2)
        opt = make_optimizer("sgd", 0.1)
        step = build_dp_averaged_train_step(
            sum_loss_with_hits_fn(fwd, "logistic"), opt, mesh, l2=l2,
            donate=False)
        rng = np.random.default_rng(0)
        codes = rng.integers(0, 16, size=(2, B, k)).astype(np.uint16)
        packed = np.stack([pack_codes(c, b) for c in codes])
        labels = rng.integers(0, 2, size=(2, B)).astype(np.int32)
        valid = np.ones((2, B), bool)
        valid[1, 2:] = False               # ragged: device 1 has 2 rows
        astate = init_averaged_state(
            init_bbit_linear(cfg, jax.random.key(0)), opt)
        a2, (loss, hits) = step(
            astate, np.float32(1.0),
            device_put_sharded(packed, mesh),
            device_put_sharded(labels, mesh),
            device_put_sharded(valid, mesh))
        # reference: one plain step over the 8 concatenated valid rows
        sel = valid.reshape(-1)
        flat = packed.reshape(-1, packed.shape[-1])[sel]
        flab = labels.reshape(-1)[sel]
        lf = mean_loss_with_preds_fn(fwd, "logistic", l2=l2)
        (rl, rpred), g = jax.value_and_grad(lf, has_aux=True)(
            astate.state.params, jnp.asarray(flat), jnp.asarray(flab))
        newp = jax.tree.map(lambda p, gg: p - 0.1 * gg,
                            astate.state.params, g)
        for x, y in zip(jax.tree.leaves(a2.state.params),
                        jax.tree.leaves(newp)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)
        assert abs(float(loss) - float(rl)) < 1e-6
        assert int(hits) == int(np.sum(np.asarray(rpred) == flab))
        # Polyak average joined exactly once with the updated params
        assert float(a2.avg_count) == 1.0
        for x, y in zip(jax.tree.leaves(a2.avg_params),
                        jax.tree.leaves(a2.state.params)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)
        print("OK")
    """, devices=2)


def test_dp_streaming_uneven_shards_and_resume():
    """5 shards on 2 devices (short final group → one device idles with
    zero rows), oph_zero masks included: no collective hang, exact
    example accounting, bitwise run-to-run + kill/resume determinism,
    and refusal to resume on a different topology."""
    run_in_subprocess(_DP_COMMON + """
    with tempfile.TemporaryDirectory() as d:
        make_archive(d, n_docs=250, n_shards=5, scheme="oph_zero")
        counts = shard_row_counts(d)
        assert len(counts) == 5
        lcfg = BBitLinearConfig(k=16, b=4)
        kw = dict(epochs=2, batch_size=32, lr=5e-3, seed=0)
        dp = fit_streaming(d, lcfg, data_parallel=2, **kw)
        assert dp.completed and dp.examples_seen == 2 * sum(counts)
        assert dp.shards_processed == 10
        assert 0.5 < dp.progressive_acc <= 1.0
        dp2 = fit_streaming(d, lcfg, data_parallel=2, **kw)
        eq = lambda a, b: all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
        assert eq(dp.params, dp2.params) and eq(dp.avg_params,
                                                dp2.avg_params)
        with tempfile.TemporaryDirectory() as ck:
            part = fit_streaming(d, lcfg, data_parallel=2, ckpt_dir=ck,
                                 stop_after_shards=3, **kw)
            # group granularity: 3 requested rounds up to 2 groups
            assert not part.completed and part.shards_processed == 4
            res = fit_streaming(d, lcfg, data_parallel=2, ckpt_dir=ck,
                                **kw)
            assert res.completed and eq(dp.params, res.params)
            assert eq(dp.avg_params, res.avg_params)
            assert res.n_steps == dp.n_steps
            assert res.examples_seen == dp.examples_seen
            assert abs(res.progressive_acc - dp.progressive_acc) < 1e-12
            # topology is fingerprinted: serial resume must refuse
            try:
                fit_streaming(d, lcfg, ckpt_dir=ck, **kw)
                raise SystemExit("serial resume of a dp checkpoint "
                                 "was not refused")
            except ValueError as e:
                assert "incompatible" in str(e)
        print("OK")
    """, devices=2)


def test_dp_streaming_single_device_world_matches_semantics():
    """world=1 exercises the whole shard_map/psum path on one device;
    progressive accounting must match the serial schedule exactly."""
    run_in_subprocess(_DP_COMMON + """
    with tempfile.TemporaryDirectory() as d:
        make_archive(d, n_docs=200, n_shards=2)
        counts = shard_row_counts(d)
        lcfg = BBitLinearConfig(k=16, b=4)
        kw = dict(epochs=2, batch_size=32, lr=5e-3, seed=0)
        one = fit_streaming(d, lcfg, data_parallel=1, **kw)
        ser = fit_streaming(d, lcfg, **kw)
        assert one.n_steps == ser.n_steps
        assert one.examples_seen == ser.examples_seen
        # same batches, same math up to padded-batch summation order
        assert abs(one.progressive_acc - ser.progressive_acc) < 0.02
        print("OK")
    """, devices=2)


def test_dp_streaming_max_devices_folds_onto_fewer():
    """``max_devices=1`` folds a logical dp=2 schedule onto one of two
    visible devices: same steps, same parameters bit for bit, and the
    lineage records the physical world actually used."""
    run_in_subprocess(_DP_COMMON + """
    with tempfile.TemporaryDirectory() as d:
        make_archive(d, n_docs=200, n_shards=4)
        lcfg = BBitLinearConfig(k=16, b=4)
        kw = dict(epochs=1, batch_size=32, lr=5e-3, seed=0,
                  data_parallel=2, elastic=True)
        runs = {cap: fit_streaming(d, lcfg, max_devices=cap,
                                   ckpt_dir=f"{d}/ck{cap}", **kw)
                for cap in (None, 1)}
        two, one = runs[None], runs[1]
        assert [e["physical"] for e in two.topology_lineage] == [2]
        assert [e["physical"] for e in one.topology_lineage] == [1]
        assert (one.n_steps, one.examples_seen) == (two.n_steps,
                                                    two.examples_seen)
        for x, y in zip(jax.tree.leaves(one.params),
                        jax.tree.leaves(two.params)):
            assert np.array_equal(np.asarray(x), np.asarray(y))
        print("OK")
    """, devices=2)


# ------------------------------------------------ in-process (CI tier) ----
needs_two = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (CI multi-device job sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=2)")


@needs_two
def test_dp_fit_streaming_in_process(tmp_path):
    from repro.data import (SynthRcv1Config, generate_arrays,
                            preprocess_and_save)
    from repro.models.linear import BBitLinearConfig
    from repro.train import fit_streaming

    cfg = SynthRcv1Config(seed=11, topic_tokens=150, background_frac=0.35,
                          max_pairs_per_doc=2000, max_triples_per_doc=1000)
    rows, labels = generate_arrays(150, cfg)
    d = str(tmp_path / "arch")
    preprocess_and_save(d, rows, labels, k=16, b=4, seed=1, n_shards=3,
                        chunk=64)
    res = fit_streaming(d, BBitLinearConfig(k=16, b=4), epochs=2,
                        batch_size=32, lr=5e-3, seed=0, data_parallel=2)
    assert res.completed and res.examples_seen == 2 * 150
    assert 0.5 < res.progressive_acc <= 1.0


@needs_two
def test_psum_mean_dtype_in_process():
    import functools
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.distributed import psum_mean
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(2)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                       out_specs=P(None))
    def mean(x):
        return psum_mean(x[0], "data")[None]

    x = jnp.asarray(np.arange(8).reshape(2, 4), jnp.bfloat16)
    out = mean(x)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out[0], np.float32),
                               [2.0, 3.0, 4.0, 5.0])
