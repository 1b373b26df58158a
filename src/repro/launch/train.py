"""Training launcher — the paper's end-to-end pipeline, production-shaped.

Three entry modes:

  * ``--mode linear`` (default; the paper's workload): synthetic
    expanded-rcv1 → one-time b-bit minwise hashing (cached on disk, the
    §6 economics) → distributed LR/SVM training with checkpoint/resume,
    failure injection, straggler watchdog, and optional b-bit gradient
    compression.
  * ``--mode stream``: the production path — ``fit_streaming`` over a
    sharded packed archive UNDER the supervised restart loop
    (``train.supervisor.run_supervised``): crashes restore from the
    newest valid checkpoint (torn/corrupt ones are quarantined) after a
    capped backoff, ``elastic`` folds the logical data-parallel world
    onto whatever devices are alive, and ``--fail-at`` injects a
    deterministic crash to watch it self-heal.  Its result line ends
    with the loop's counters (docs/DESIGN.md §2.7).
  * ``--mode lm``: trains a (reduced) LM-zoo arch on synthetic tokens
    through the same TrainState/checkpoint machinery (smoke-scale on
    CPU; the full configs are exercised by the dry-run).

Restart contract: the loader replays batches as a pure function of the
global step (streaming: of ``(seed, epoch, position)``), so kill →
relaunch produces bitwise-identical parameters (tested in
tests/test_checkpoint.py and tests/test_fault_tolerance.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import numpy as np


def run_linear(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.data import (
        SynthRcv1Config, generate_arrays, preprocess_and_save,
        load_hashed, HashedCodesLoader,
    )
    from repro.models.linear import (
        BBitLinearConfig, init_bbit_linear, bbit_logits, predict_classes,
    )
    from repro.optim.optimizers import make_optimizer
    from repro.train.losses import mean_loss_fn
    from repro.train.metrics import accuracy
    from repro.train.steps import init_state, build_train_step
    from repro.ckpt import checkpoint as ckpt
    from repro.ft.watchdog import StepWatchdog, FailureInjector

    hashed_dir = os.path.join(args.workdir, "hashed")
    if not os.path.exists(os.path.join(hashed_dir, "meta.json")):
        rows, labels = generate_arrays(
            args.n_docs, SynthRcv1Config(
                seed=args.seed, topic_tokens=150, background_frac=0.35,
                max_pairs_per_doc=8000, max_triples_per_doc=4000))
        stats = preprocess_and_save(hashed_dir, rows, labels,
                                    k=args.k, b=args.b, seed=args.seed,
                                    n_shards=4)
        print(f"preprocessed {stats['n']} docs in "
              f"{stats['seconds_hashing']:.1f}s (one-time cost)")
    codes, labels, meta = load_hashed(hashed_dir)
    n_test = len(labels) // 4
    codes_tr, y_tr = codes[:-n_test], labels[:-n_test]
    codes_te, y_te = codes[-n_test:], labels[-n_test:]

    lcfg = BBitLinearConfig(k=meta["k"], b=meta["b"])
    opt = make_optimizer("adamw", args.lr)
    loss_fn = mean_loss_fn(lambda p, c: bbit_logits(p, c, lcfg),
                           "logistic", l2=1e-6)
    step_fn = build_train_step(loss_fn, opt)
    loader = HashedCodesLoader(codes_tr, y_tr, args.batch_size,
                               seed=args.seed)

    ckpt_dir = os.path.join(args.workdir, "ckpt")
    state = init_state(init_bbit_linear(lcfg, jax.random.key(args.seed)),
                       opt)
    start_step = 0
    restored = ckpt.restore_if_exists(ckpt_dir, state)
    if restored is not None:
        state, start_step = restored
        print(f"resumed from step {start_step}")

    watchdog = StepWatchdog()
    injector = FailureInjector(args.fail_at)
    total_steps = args.steps
    losses = []
    for step, bc, by in loader.batches(start_step=start_step):
        if step >= total_steps:
            break
        injector.maybe_fail(step)
        watchdog.start_step()
        state, loss = step_fn(state, jnp.asarray(bc.astype(np.int32)),
                              jnp.asarray(by))
        watchdog.end_step(step)
        losses.append(float(loss))
        if (step + 1) % args.ckpt_every == 0:
            ckpt.save(ckpt_dir, step + 1, state)
    ckpt.save(ckpt_dir, min(total_steps, step + 1), state)

    te_acc = accuracy(
        predict_classes(state.params, jnp.asarray(codes_te.astype(np.int32)),
                        lcfg), y_te)
    from repro import perf
    rep = perf.dispatch_report()
    print(f"final loss={np.mean(losses[-10:]):.4f} test_acc={te_acc:.4f} "
          f"stragglers={len(watchdog.flagged_steps)} "
          f"dispatch_hits={rep['hits']} fallbacks={rep['fallbacks']}")
    return dict(test_acc=te_acc, final_loss=float(np.mean(losses[-10:])),
                steps=int(min(total_steps, step + 1)))


def run_stream(args) -> dict:
    """Supervised streaming training over a sharded packed archive:
    crash-safe checkpoints, quarantine-checked restore, elastic device
    folding, straggler watchdog — the single-host production loop.
    ``--procs N`` upgrades it to an N-process ``jax.distributed`` gang
    under gang-restart supervision (coordinated checkpoints, respawn
    from the latest committed step on any worker death)."""
    from repro.configs.rcv1_oph import CONFIG
    from repro.data import (SynthRcv1Config, generate_arrays,
                            preprocess_and_save, shard_row_counts)
    from repro.ft import FaultEvent, FaultPlan, StepWatchdog, faults
    from repro.models.linear import BBitLinearConfig
    from repro.train import run_supervised

    hashed_dir = os.path.join(args.workdir, "shards")
    if not os.path.exists(os.path.join(hashed_dir, "meta.json")):
        rows, labels = generate_arrays(
            args.n_docs, SynthRcv1Config(
                seed=args.seed, topic_tokens=150, background_frac=0.35,
                max_pairs_per_doc=8000, max_triples_per_doc=4000))
        stats = preprocess_and_save(hashed_dir, rows, labels,
                                    k=args.k, b=args.b, seed=args.seed,
                                    n_shards=4)
        print(f"preprocessed {stats['n']} docs into 4 shards in "
              f"{stats['seconds_hashing']:.1f}s (one-time cost)")

    if args.procs and args.procs > 1:
        from repro.train.supervisor import run_multiprocess_supervised
        fault_spec = None
        if args.fail_at is not None:
            fault_spec = FaultPlan([
                FaultEvent(site="proc_kill", step=args.fail_at,
                           rank=args.procs - 1, times=1)]).to_spec()
        run = run_multiprocess_supervised(
            hashed_dir, BBitLinearConfig(k=args.k, b=args.b),
            procs=args.procs,
            run_dir=os.path.join(args.workdir, "gang"),
            policy=CONFIG.restart_policy(),
            fault_spec=fault_spec,
            ckpt_dir=os.path.join(args.workdir, "ckpt_stream"),
            seed=args.seed,
            **CONFIG.stream_kwargs(
                epochs=args.epochs, batch_size=args.batch_size,
                lr=args.lr, ckpt_every_shards=1,
                data_parallel=args.data_parallel or args.procs))
        rec = run.result
        by_rank = [run.results[r]["counters"] for r in sorted(run.results)]
        print(f"gang of {args.procs} procs streamed "
              f"{rec['examples_seen']} rows x {args.epochs} epochs in "
              f"{rec['train_seconds']:.1f}s: progressive_acc="
              f"{rec['progressive_acc']:.4f} steps={rec['n_steps']} "
              f"gang_restarts={run.restarts} "
              f"topology={rec['lineage']} "
              f"counters_by_rank={by_rank}")
        return dict(progressive_acc=rec["progressive_acc"],
                    steps=rec["n_steps"], restarts=run.restarts,
                    crashes=[c.error for c in run.crashes],
                    counters_by_rank=by_rank)

    if args.fail_at is not None:
        faults.arm_plan(FaultPlan([
            FaultEvent(site="train_step", step=args.fail_at, times=1)]))
    watchdog = StepWatchdog()
    sup = run_supervised(
        hashed_dir, BBitLinearConfig(k=args.k, b=args.b),
        policy=CONFIG.restart_policy(), watchdog=watchdog,
        ckpt_dir=os.path.join(args.workdir, "ckpt_stream"),
        seed=args.seed,
        **CONFIG.stream_kwargs(epochs=args.epochs,
                               batch_size=args.batch_size, lr=args.lr,
                               ckpt_every_shards=1,
                               data_parallel=args.data_parallel))
    faults.disarm()
    res = sup.result
    n_rows = sum(shard_row_counts(hashed_dir))
    from repro import perf
    rep = perf.dispatch_report()
    print(f"streamed {n_rows} rows x {args.epochs} epochs in "
          f"{res.train_seconds:.1f}s: progressive_acc="
          f"{res.progressive_acc:.4f} steps={res.n_steps} "
          f"restarts={sup.restarts} "
          f"stragglers={sup.straggler_escalations} "
          f"topology={res.topology_lineage} "
          f"dispatch={res.dispatch} "
          f"(profile_hits={rep['hits']} fallbacks={rep['fallbacks']}) "
          f"counters={res.counters}")
    return dict(progressive_acc=res.progressive_acc,
                steps=res.n_steps, restarts=sup.restarts,
                crashes=[c.error for c in sup.crashes],
                counters=res.counters)


def run_lm(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.data.lm_synth import lm_example_stream
    from repro.launch.smoke_configs import reduced_config
    from repro.models.api import get_model_api
    from repro.launch.steps import make_optimizer_for
    from repro.train.steps import TrainState
    from repro.ckpt import checkpoint as ckpt

    cfg = reduced_config(get_config(args.arch))
    api = get_model_api(cfg)
    opt = make_optimizer_for(cfg)
    params = api.init_params(jax.random.key(args.seed))
    state = TrainState(params=params, opt_state=opt.init(params),
                       step=jnp.zeros((), jnp.int32))

    @jax.jit
    def step_fn(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, None))(state.params)
        new_p, new_o = opt.update(grads, state.opt_state, state.params,
                                  state.step)
        return TrainState(new_p, new_o, state.step + 1), loss

    ckpt_dir = os.path.join(args.workdir, f"ckpt_{args.arch}")
    start_step = 0
    restored = ckpt.restore_if_exists(ckpt_dir, state)
    if restored is not None:
        state, start_step = restored

    losses = []
    for step, toks, tgts in lm_example_stream(
            args.batch_size, args.seq_len, cfg.vocab, seed=args.seed):
        if step < start_step:
            continue
        if step >= args.steps:
            break
        batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgts)}
        shapes = api.batch_shapes(args.batch_size, args.seq_len)
        if "vision_embeds" in shapes:
            batch["vision_embeds"] = jnp.zeros(
                shapes["vision_embeds"].shape, shapes["vision_embeds"].dtype)
        if "frames" in shapes:
            batch["frames"] = jnp.zeros(
                shapes["frames"].shape, shapes["frames"].dtype)
        state, loss = step_fn(state, batch)
        losses.append(float(loss))
        if (step + 1) % args.ckpt_every == 0:
            ckpt.save(ckpt_dir, step + 1, state)
    first, last = losses[0], float(np.mean(losses[-5:]))
    print(f"{args.arch}: loss {first:.3f} -> {last:.3f} "
          f"over {len(losses)} steps")
    return dict(first_loss=first, last_loss=last)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="linear",
                    choices=["linear", "stream", "lm"])
    ap.add_argument("--workdir", default="artifacts/train")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--n-docs", type=int, default=2000)
    ap.add_argument("--k", type=int, default=200)
    ap.add_argument("--b", type=int, default=8)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (FT testing)")
    ap.add_argument("--epochs", type=int, default=1,
                    help="stream mode: passes over the archive")
    ap.add_argument("--data-parallel", type=int, default=None,
                    help="stream mode: logical data-parallel world "
                         "(elastic — folds onto available devices)")
    ap.add_argument("--procs", type=int, default=None,
                    help="stream mode: launch an N-process "
                         "jax.distributed gang (localhost) under "
                         "gang-restart supervision; CPU only (set "
                         "XLA_FLAGS for fake devices per rank) — on "
                         "chips use --data-parallel")
    ap.add_argument("--profile", default=None,
                    help="perf cost-model profile JSON (default: the "
                         "config's profile_path if it exists; missing/"
                         "mismatched files fall back to the static "
                         "dispatch heuristics)")
    args = ap.parse_args()
    os.makedirs(args.workdir, exist_ok=True)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro import perf
    from repro.configs.rcv1_oph import CONFIG
    profile = args.profile if args.profile is not None \
        else CONFIG.profile_path
    if perf.maybe_load_profile(profile):
        print(f"dispatch: cost-model profile {profile} "
              f"(table {perf.get_model().table.table_version})")
    else:
        print("dispatch: static heuristics (no usable profile; run "
              "python -m repro.launch.calibrate to measure this box)")
    if args.mode == "linear":
        run_linear(args)
    elif args.mode == "stream":
        run_stream(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
