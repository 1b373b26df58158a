"""Quickstart: the paper's pipeline in ~60 seconds on CPU.

  synthetic expanded-rcv1 docs → k×b-bit minwise hashing (one-time)
  → LIBLINEAR-style TRON training (Eq. 9) → test accuracy
  → same hashed model served with dynamic batching
  → the same engine behind the network front end (HTTP).

Serve over HTTP (step 5 here, full tour in
examples/serve_classifier.py):

    srv = ScoreServer(eng, port=0)        # 0 → ephemeral port
    srv.start_in_thread()
    client = ScoreClient(srv.host, srv.port)
    client.score([[12, 99, 1024], ...])   # {"scores", "version", ...}
    client.status()                       # p50/p95/p99, rows/s, lanes
    client.reload(ckpt_dir)               # versioned weight hot-swap
    srv.request_drain()                   # SIGTERM path: finish, then stop

or from the command line:

    PYTHONPATH=src python -m repro.launch.serve \
        --mode classifier --http --port 8077
    curl -s localhost:8077/status | python -m json.tool

Duplicate traffic (step 6 here): repeat documents short-circuit
through the minhash-keyed score cache — band-signature probe, exact
packed-code guard, scores bitwise-identical to a fresh dispatch.  Full
HTTP tour (``GET /status`` dedup counters, hot-reload invalidation) in
examples/serve_classifier.py.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax

from repro.data import SynthRcv1Config, generate_arrays, preprocess_rows
from repro.models.linear import BBitLinearConfig
from repro.train import train_bbit_liblinear
from repro.serving import HashedClassifierEngine, ScoreClient, ScoreServer


def main() -> None:
    print("1) generating synthetic expanded-rcv1 corpus "
          "(unigrams + pairs + 1/30 triples)…")
    cfg = SynthRcv1Config(seed=11, topic_tokens=150, background_frac=0.35,
                          max_pairs_per_doc=4000, max_triples_per_doc=2000)
    rows, labels = generate_arrays(800, cfg)
    nnz = [len(r) for r in rows]
    print(f"   {len(rows)} docs; nnz median={int(np.median(nnz))} "
          f"mean={int(np.mean(nnz))}; D=2^30")

    k, b = 64, 8
    print(f"2) one-time preprocessing: k={k} min-hashes, lowest b={b} "
          f"bits each → {k*b} bits/doc…")
    codes = preprocess_rows(rows, k=k, b=b, seed=1, chunk=256)

    print("3) training logistic regression (TRON, the LIBLINEAR "
          "solver) on the hashed codes…")
    n_tr = 400
    lcfg = BBitLinearConfig(k=k, b=b)
    res = train_bbit_liblinear(codes[:n_tr], labels[:n_tr],
                               codes[n_tr:], labels[n_tr:],
                               lcfg, loss="logistic", C=1.0, max_iter=30)
    print(f"   test accuracy = {res.test_acc:.3f} "
          f"({res.n_iter} TRON iterations, {res.train_seconds:.1f}s)")

    print("4) serving the trained model (fused hash → score, batched)…")
    # buckets sized to this demo corpus so the startup precompile
    # stays snappy (defaults target production-scale nnz ranges)
    eng = HashedClassifierEngine(res.params, lcfg, seed=1,
                                 nnz_buckets=(2048, 8192),
                                 row_buckets=(1, 32))
    futs = [eng.submit(r) for r in rows[n_tr:n_tr + 32]]
    scores = np.array([f.result(timeout=60) for f in futs])
    pred = (scores > 0).astype(int)
    acc = float(np.mean(pred == labels[n_tr:n_tr + 32]))
    print(f"   served 32 requests in {eng.batcher.batches_run} batch(es); "
          f"accuracy {acc:.3f}")

    print("5) same engine over HTTP (batch scores + live /status)…")
    srv = ScoreServer(eng, port=0)
    srv.start_in_thread()
    client = ScoreClient(srv.host, srv.port)
    resp = client.score(rows[n_tr:n_tr + 8])
    st = client.status()
    print(f"   POST /score → 8 scores tagged {resp['version']!r}; "
          f"GET /status → health={st['health']} "
          f"p50={st['engine']['p50_ms']:.1f}ms")
    srv.request_drain()               # drains the engine too
    srv.wait_finished(timeout=30)

    print("6) duplicate traffic: the minhash-keyed score cache "
          "(full HTTP demo in examples/serve_classifier.py)…")
    dedup_eng = HashedClassifierEngine(res.params, lcfg, seed=1,
                                       nnz_buckets=(2048, 8192),
                                       row_buckets=(1, 32),
                                       dedup_cache=True,
                                       dedup_entries=128)
    viral = rows[n_tr]
    fresh = float(dedup_eng.submit(viral).result(timeout=60))
    repeats = [float(f.result(timeout=60))
               for f in dedup_eng.submit_many([viral] * 8)]
    d = dedup_eng.stats()["dedup"]
    dedup_eng.close()
    assert all(r == fresh for r in repeats)
    print(f"   8 repeats of one viral doc → {d['hits']} cache hits, "
          f"every score bitwise-equal to the fresh dispatch, no "
          f"device round-trip on a hit")

    assert res.test_acc > 0.85


if __name__ == "__main__":
    main()
