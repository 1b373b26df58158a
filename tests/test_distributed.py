"""Distributed-runtime tests on 8 fake devices (subprocess-isolated so
the main test process keeps its single real device)."""
import pytest

from conftest import run_in_subprocess


def test_grad_compression_and_hlo_accounting():
    run_in_subprocess("""
        import functools, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from jax import shard_map
        from repro.distributed import (
            compressed_allreduce_mean, collective_bytes_from_hlo,
            collective_stats_from_hlo)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        g = jnp.arange(4*64, dtype=jnp.float32).reshape(4, 64) / 100.
        e = jnp.zeros((4, 64), jnp.float32)
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)))
        def cr(g, e):
            mg, ne = compressed_allreduce_mean(g[0], e[0], "data", bits=8)
            return mg[None], ne[None]
        mg, ne = cr(g, e)
        want = jnp.mean(g, axis=0)
        assert float(jnp.abs(mg[0]-want).max()) < 0.02
        # error feedback: long-run mean drift vanishes
        tot = jnp.zeros(64); ee = e
        for _ in range(30):
            m, ee = cr(g, ee); tot = tot + m[0]
        assert float(jnp.abs(tot/30 - want).max()) < 1e-3
        # wire payload is int8 (the b-bit story): all-gathers present,
        # and the int8 payload dominates the f32 scales
        hlo = jax.jit(cr).lower(g, e).compile().as_text()
        stats = collective_stats_from_hlo(hlo)
        assert any(s["op"] == "all-gather" for s in stats)
        total = collective_bytes_from_hlo(hlo)["total"]
        assert total < 4 * 64 * 4 * 4  # far below fp32 all-gather cost
        # 1-bit mode
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)))
        def cr1(g, e):
            mg, ne = compressed_allreduce_mean(g[0], e[0], "data", bits=1)
            return mg[None], ne[None]
        tot = jnp.zeros(64); ee = e
        for _ in range(60):
            m, ee = cr1(g, ee); tot = tot + m[0]
        # sign-compression converges in running mean (Cesàro); per-tensor
        # scale makes it slower than int8 — generous bound
        assert float(jnp.abs(tot/60 - want).max()) < 0.15
        print("OK")
    """)


def test_sequence_parallel_primitives():
    run_in_subprocess("""
        import functools, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from jax import shard_map
        from repro.distributed import (merge_partial_attention,
                                       seq_parallel_ssm_scan)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        scores = np.random.default_rng(2).normal(size=(2, 32)).astype('f')
        V = np.random.default_rng(3).normal(size=(32, 5)).astype('f')
        full = jax.nn.softmax(jnp.asarray(scores), -1) @ jnp.asarray(V)
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P(None, "data"), P("data", None)),
            out_specs=P(None, None))
        def att(s, v):
            lm = jnp.max(s, -1); le = jnp.exp(s - lm[:, None])
            return merge_partial_attention(lm, jnp.sum(le, -1), le @ v,
                                           "data")
        out = att(jnp.asarray(scores), jnp.asarray(V))
        assert float(jnp.abs(out - full).max()) < 1e-5
        # SSM prefix composition across shards
        A = np.random.default_rng(4).uniform(.5, .99, (4, 3)).astype('f')
        B = np.random.default_rng(5).normal(size=(4, 3)).astype('f')
        h0 = np.ones(3, 'f')
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P("data", None), P("data", None), P(None)),
            out_specs=P("data", None))
        def sp(a, b, h):
            out = seq_parallel_ssm_scan(a[0], b[0], h, "data",
                                        jax.lax.axis_index("data"))
            return out[None]
        hins = np.asarray(sp(jnp.asarray(A), jnp.asarray(B),
                             jnp.asarray(h0)))
        h = h0.copy(); want = []
        for i in range(4):
            want.append(h.copy()); h = A[i]*h + B[i]
        assert np.abs(hins - np.stack(want)).max() < 1e-5
        print("OK")
    """)


def test_pipeline_parallel_gpipe():
    run_in_subprocess("""
        import functools, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, PartitionSpec as P
        from jax import shard_map
        from repro.distributed import pipelined_apply
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        M, mb, dim = 6, 2, 8
        x = np.random.default_rng(6).normal(size=(M, mb, dim)).astype('f')
        W = np.random.default_rng(7).normal(size=(4, dim, dim)
                                            ).astype('f') * 0.3
        def stage(p, x): return jnp.tanh(x @ p[0])
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P("data", None, None), P(None, None, None)),
            out_specs=P(None, None, None))
        def pipe(w, xm):
            return pipelined_apply(stage, (w,), xm, axis_name="data")
        got = pipe(jnp.asarray(W), jnp.asarray(x))
        want = jnp.asarray(x)
        for i in range(4):
            want = jnp.tanh(want @ W[i])
        assert float(jnp.abs(got - want).max()) < 1e-5
        # differentiability (training through the pipeline)
        def loss(w): return jnp.sum(pipe(w, jnp.asarray(x)) ** 2)
        g = jax.grad(loss)(jnp.asarray(W))
        assert np.isfinite(np.asarray(g)).all() and float(
            jnp.abs(g).sum()) > 0
        print("OK")
    """)


def test_moe_ep_parity_and_elastic_mesh():
    run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ArchConfig
        import repro.models.moe as M
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        M.EXPERT_PAD_TO = 2
        cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=16,
                         n_heads=2, n_kv_heads=2, d_ff=0, vocab=64,
                         moe_experts=6, moe_top_k=2, moe_d_ff=32,
                         moe_capacity=8.0, dtype="float32")
        params = M.init_moe_params(cfg, jax.random.key(0), jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 8, 16)).astype('f'))
        y_dense = M.moe_ffn(x, params, cfg, mesh=None)
        ps = M.moe_param_pspecs(cfg, dp_axes=("pod", "data"))
        p_sh = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), ps,
            is_leaf=lambda s: isinstance(s, P)))
        x_sh = jax.device_put(x, NamedSharding(
            mesh, P(("pod", "data"), None, None)))
        y = jax.jit(lambda a, b: M.moe_ffn(a, b, cfg, mesh=mesh))(x_sh, p_sh)
        assert float(jnp.abs(y - y_dense).max()) < 1e-4
        # elastic: same model on a smaller mesh gives identical results
        from repro.ckpt.elastic import mesh_from_available_devices
        mesh2 = jax.make_mesh((2, 2), ("data", "model"))
        p2 = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh2, P(*[e if e != "pod" else None
                                               for e in s])),
            M.moe_param_pspecs(cfg, dp_axes=("data",)),
            is_leaf=lambda s: isinstance(s, P)))
        x2 = jax.device_put(x, NamedSharding(mesh2, P("data", None, None)))
        y2 = jax.jit(lambda a, b: M.moe_ffn(a, b, cfg, mesh=mesh2))(x2, p2)
        assert float(jnp.abs(y2 - y_dense).max()) < 1e-4
        print("OK")
    """)


def test_linear_model_distributed_step():
    """The paper's workload end-to-end on a (data, model) mesh: TP over
    k hash functions + DP over examples; loss matches single-device."""
    run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.rcv1_bbit import PaperConfig
        from repro.launch.steps import build_linear_train_step
        from repro.launch.mesh import make_test_mesh
        paper = PaperConfig(k=16, b=4, global_batch=32)
        mesh = make_test_mesh(4, 2)
        jitted, state_shapes, state_ps, _ = build_linear_train_step(
            paper, mesh)
        # real arrays
        from repro.models.linear import BBitLinearConfig, init_bbit_linear
        from repro.optim.optimizers import adamw, AdamWConfig
        from repro.train.steps import TrainState
        lcfg = BBitLinearConfig(k=16, b=4, use_kernel="never")
        opt = adamw(1e-2, AdamWConfig())
        params = init_bbit_linear(lcfg)
        state = TrainState(params, opt.init(params),
                           jnp.zeros((), jnp.int32))
        rng = np.random.default_rng(0)
        codes = jnp.asarray(rng.integers(0, 16, (32, 16)).astype('i4'))
        labels = jnp.asarray((rng.random(32) > .5).astype('i4'))
        # single-device reference BEFORE the step: the jitted step
        # donates the state, deleting the params buffers
        from repro.train.losses import mean_loss_fn
        from repro.models.linear import bbit_logits
        lf = mean_loss_fn(lambda p, c: bbit_logits(p, c, lcfg),
                          "logistic", l2=1e-7)
        ref_loss = float(lf(params, codes, labels))
        with mesh:
            new_state, loss = jitted(state, codes, labels)
        assert np.isfinite(float(loss))
        assert abs(float(loss) - ref_loss) < 1e-5
        print("OK")
    """)


def test_moe_weight_stationary_serving_parity():
    """§Perf dispatch: experts 2D-sharded, tokens travel — must equal
    the dense fallback exactly (ample capacity)."""
    run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs.base import ArchConfig
        import repro.models.moe as M
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        M.EXPERT_PAD_TO = 8
        cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=16,
                         n_heads=2, n_kv_heads=2, d_ff=0, vocab=64,
                         moe_experts=6, moe_top_k=2, moe_d_ff=32,
                         moe_capacity=8.0, dtype="float32",
                         moe_serving_dispatch="weight_stationary",
                         moe_pad_to=8)
        params = M.init_moe_params(cfg, jax.random.key(0), jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 8, 16)).astype('f'))
        y_dense = M.moe_ffn(x, params, cfg, mesh=None)
        ps = M.moe_param_pspecs(cfg, dp_axes=("data",))
        p_sh = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), ps,
            is_leaf=lambda s: isinstance(s, P)))
        x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None,
                                                       None)))
        y = jax.jit(lambda a, b: M.moe_ffn(a, b, cfg, mesh=mesh,
                                           serving=True))(x_sh, p_sh)
        assert float(jnp.abs(y - y_dense).max()) < 1e-4
        print("OK")
    """)


def test_kv_repeat_decode_parity():
    """§Perf: KV-head replication is an exact GQA transform."""
    run_in_subprocess("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import ArchConfig
        from repro.models import transformer as T
        cfg = ArchConfig(name="d", family="dense", n_layers=2, d_model=32,
                         n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                         dtype="float32", attn_q_chunk=8, attn_kv_chunk=8,
                         kv_repeat_to=4)
        p = T.init_decoder_params(cfg, jax.random.key(1))
        toks = jnp.asarray(np.random.default_rng(1).integers(
            0, 64, (2, 12)).astype(np.int32))
        logits = T.forward_train(p, toks, cfg)
        lg_p, cache = T.prefill(p, toks[:, :8], cfg)
        assert cache["k"].shape[3] == 4
        full = T.init_cache(cfg, 2, 12, dtype=jnp.float32)
        cache = jax.tree.map(
            lambda f, pre: jax.lax.dynamic_update_slice_in_dim(
                f, pre.astype(f.dtype), 0, axis=2), full, cache)
        errs = [float(jnp.abs(lg_p - logits[:, 7]).max())]
        c = cache
        for t in range(8, 12):
            lg, c = T.decode_step(p, toks[:, t:t+1], c,
                                  jnp.asarray(t, jnp.int32), cfg)
            errs.append(float(jnp.abs(lg - logits[:, t]).max()))
        assert max(errs) < 2e-3, errs
        print("OK")
    """, devices=1)
