"""Serving launcher.

  --mode classifier : train a small hashed classifier, stand up the
                      dynamically-batched engine, then either replay a
                      request stream in-process (default; reports
                      throughput/latency/accuracy) or — with --http —
                      serve it over the network front end
                      (``serving.server.ScoreServer``: POST /score,
                      GET /status, POST /reload, graceful drain on
                      SIGTERM) until terminated.
  --mode lm         : greedy-generate from a reduced LM-zoo arch via
                      prefill + KV-cache decode (the serve_step the
                      decode dry-run cells lower at full scale).

HTTP flags (classifier mode): ``--http --host H --port P`` (port 0
picks an ephemeral port), ``--drain-timeout-s`` bounds how long SIGTERM
waits for in-flight requests, ``--adapt-every N`` re-derives the nnz
lane grid from live traffic every N requests.  ``--dedup-cache`` puts
the band-keyed duplicate-traffic score cache (``serving/dedup.py``) in
front of the batcher (``--cache-entries`` caps it) and prints one
``DEDUP_CACHE ...`` line alongside the ``LISTENING <host> <port>`` line
once the socket is bound (machine-readable; the e2e smoke and examples
wait on it).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _build_classifier_engine(args):
    import jax  # noqa: F401 — device runtime init before training
    from repro.data import (SynthRcv1Config, generate_arrays,
                            preprocess_rows)
    from repro.models.linear import BBitLinearConfig
    from repro.serving import HashedClassifierEngine
    from repro.train import train_bbit_liblinear

    cfg = SynthRcv1Config(seed=args.seed, topic_tokens=150,
                          background_frac=0.35,
                          max_pairs_per_doc=3000,
                          max_triples_per_doc=1500)
    rows, labels = generate_arrays(args.n_docs, cfg)
    codes = preprocess_rows(rows, k=args.k, b=args.b, seed=1, chunk=256)
    n_tr = args.n_docs * 2 // 3
    lcfg = BBitLinearConfig(k=args.k, b=args.b)
    res = train_bbit_liblinear(codes[:n_tr], labels[:n_tr],
                               codes[n_tr:], labels[n_tr:], lcfg,
                               loss="logistic", C=1.0, max_iter=25)
    print(f"model ready: test acc {res.test_acc:.3f}")
    from repro.configs.rcv1_oph import CONFIG
    dedup_kw = {}
    if args.dedup_cache:
        dedup_kw = CONFIG.dedup_kwargs(dedup_cache=True,
                                       dedup_entries=args.cache_entries)
    eng = HashedClassifierEngine(
        res.params, lcfg, seed=1, max_batch=args.max_batch,
        nnz_buckets=(2048, 8192),
        row_buckets=(1, args.max_batch),
        adapt_every=args.adapt_every, **dedup_kw)
    if args.dedup_cache:
        print(f"DEDUP_CACHE entries={args.cache_entries} "
              f"rows_per_band={CONFIG.dedup_rows_per_band} "
              f"probe_bands={CONFIG.dedup_probe_bands}", flush=True)
    else:
        print("DEDUP_CACHE off", flush=True)
    return eng, rows, labels, n_tr


def serve_classifier(args) -> None:
    eng, rows, labels, n_tr = _build_classifier_engine(args)
    if args.http:
        from repro.serving import ScoreServer
        srv = ScoreServer(
            eng, host=args.host, port=args.port,
            drain_timeout_s=args.drain_timeout_s,
            on_started=lambda s: (
                print(f"LISTENING {s.host} {s.port}", flush=True)))
        try:
            srv.run()                # blocks until SIGTERM/SIGINT
        finally:
            print(f"drained clean={srv.drained_clean} after "
                  f"{srv.http_requests} requests", flush=True)
        return
    eng.submit(rows[0]).result(timeout=300)   # first-request sanity
    t0 = time.perf_counter()
    futs = [eng.submit(rows[n_tr + i % (args.n_docs - n_tr)])
            for i in range(args.requests)]
    preds = np.array([f.result(timeout=300) for f in futs]) > 0
    dt = time.perf_counter() - t0
    want = np.array([labels[n_tr + i % (args.n_docs - n_tr)]
                     for i in range(args.requests)])
    print(f"{args.requests} requests in {dt:.2f}s "
          f"({args.requests/dt:.0f} req/s, "
          f"{eng.batcher.batches_run} batches), "
          f"accuracy {float(np.mean(preds == want)):.3f}")
    eng.close()


def serve_lm(args) -> None:
    import jax
    from repro.configs.base import get_config
    from repro.launch.smoke_configs import reduced_config
    from repro.models.api import get_model_api
    from repro.serving import greedy_generate

    cfg = reduced_config(get_config(args.arch))
    api = get_model_api(cfg)
    params = api.init_params(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(1, cfg.vocab, size=(args.max_batch, 8)
                          ).astype(np.int32)
    extras = {}
    shapes = api.batch_shapes(args.max_batch, 8)
    import jax.numpy as jnp
    for key in ("vision_embeds", "frames"):
        if key in shapes:
            extras[key] = jnp.zeros(shapes[key].shape, shapes[key].dtype)
    t0 = time.perf_counter()
    toks = greedy_generate(api, params, prompt, max_new=args.tokens,
                           max_len=8 + args.tokens, extras=extras or None)
    dt = time.perf_counter() - t0
    total_new = args.max_batch * args.tokens
    print(f"{args.arch} (reduced): generated {total_new} tokens in "
          f"{dt:.1f}s ({total_new/dt:.1f} tok/s incl. compile)")
    print("sample:", toks[0].tolist())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="classifier",
                    choices=["classifier", "lm"])
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--n-docs", type=int, default=600)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--b", type=int, default=8)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP instead of replaying a "
                         "request stream in-process")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8077,
                    help="0 picks an ephemeral port")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0)
    ap.add_argument("--adapt-every", type=int, default=0,
                    help="re-derive nnz lane grid from live traffic "
                         "every N requests (0 = static grid)")
    ap.add_argument("--dedup-cache", action="store_true",
                    help="enable the band-keyed duplicate-traffic score "
                         "cache (serving/dedup.py) in front of the "
                         "batcher")
    ap.add_argument("--cache-entries", type=int, default=None,
                    help="dedup cache capacity (LRU entries; default: "
                         "the config's dedup_entries)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.cache_entries is None:
        from repro.configs.rcv1_oph import CONFIG
        args.cache_entries = CONFIG.dedup_entries
    if args.mode == "classifier":
        serve_classifier(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
