"""Expanded-rcv1 with One Permutation Hashing preprocessing.

Same learning problem as ``rcv1_bbit`` (b-bit hashed linear model over
the D≈2^30 expanded feature space, LR/L2-SVM at LIBLINEAR C) but the
one-time hashing pass uses densified OPH (arXiv:1208.1259 +
arXiv:1406.4784): ONE hash evaluation per nonzero instead of k, cutting
the paper's dominant preprocessing cost (Table 2) by ~k× while keeping
the same n·b·k-bit storage and statistically equivalent codes.

k=256 (power of two — OPH bins are lane-aligned top-bit ranges) at b=8
sits on the paper's accuracy plateau (Figures 1-4 show b=8, k≥200
within ~0.1% of the b=16 ceiling) at a quarter of the storage of the
k=500/b=16 minwise config.
"""
import dataclasses
from typing import Optional

from repro.models.linear import BBitLinearConfig


@dataclasses.dataclass(frozen=True)
class OPHPaperConfig:
    name: str = "rcv1-oph"
    scheme: str = "oph"          # densified; 'oph_zero' for zero-coding
    k: int = 256                 # bins — must be a power of two
    b: int = 8
    n_classes: int = 2
    loss: str = "logistic"       # or 'squared_hinge' (Eq. 8)
    C: float = 1.0
    ambient_dim: int = 1 << 30   # expanded rcv1: D ≈ 1.01e9
    global_batch: int = 65536    # examples per distributed step
    seed: int = 0
    # streaming preprocessing (PR 2): rows per fused-encode chunk and
    # shards per hashed dataset — peak preprocessing memory is
    # O(pipeline depth · chunk + one shard), never the (n, k) matrix
    preprocess_chunk: int = 4096
    preprocess_shards: int = 16
    # streaming training (PR 3): train.streaming.fit_streaming over the
    # v3 shards — one-pass SGD + Polyak tail averaging, packed bytes to
    # the device, shard-boundary checkpoints.  avg_start_frac opens the
    # tail-averaging window after that fraction of planned steps.
    stream_batch: int = 1024
    stream_lr: float = 1e-2
    stream_epochs: int = 1       # one pass — the VW-online comparison
    avg_start_frac: float = 0.5
    ckpt_every_shards: int = 4
    # overlapped hot path (PR 4): async producer→queue→device pipeline
    # depth (0 = inline; any depth is bit-identical) and the data-
    # parallel world size (None = single device; N shards the epoch's
    # shard groups over N devices with psum_mean gradient all-reduce)
    stream_prefetch: int = 2
    stream_data_parallel: Optional[int] = None
    # serving hot path (PR 5): fused encode→score engine — per-bucket
    # micro-batching lanes (nnz pad widths), replica count over a 1-D
    # mesh, and the batcher's dispatch/resolve overlap depth
    serve_max_batch: int = 64
    serve_max_wait_ms: float = 2.0
    serve_replicas: int = 1
    serve_nnz_buckets: tuple = (128, 512, 2048, 8192, 32768)
    serve_pipeline_depth: int = 2
    # network serving tier (PR 6): the asyncio HTTP front end over the
    # engine — bind address, graceful-drain budget, rolling stats
    # window, adaptive-bucket cadence (0 = static lane grid), and the
    # in-flight row budget (None = derive from the engine's real
    # pipeline concurrency, AdmissionController.for_engine)
    serve_host: str = "127.0.0.1"
    serve_port: int = 8077
    serve_drain_timeout_s: float = 30.0
    serve_stats_window: int = 4096
    serve_adapt_every: int = 0
    serve_inflight_limit: Optional[int] = None
    # fault tolerance (PR 7): the supervised restart loop around
    # fit_streaming — restart budget + capped exponential backoff
    # between restarts — the checkpoint ring depth (fallback set when
    # the newest checkpoint is torn/corrupt), and elastic resume
    # (fold the logical data-parallel world onto however many devices
    # are alive; power-of-two counts stay bit-identical)
    ft_max_restarts: int = 3
    ft_backoff_base_s: float = 1.0
    ft_backoff_cap_s: float = 60.0
    ft_ckpt_keep_last: int = 3
    ft_elastic: bool = True
    # multi-host gang training (PR 10): process count for
    # ``train.supervisor.run_multiprocess_supervised`` (1 = classic
    # single-process), the coordinated-checkpoint barrier budget, and
    # the optional error-feedback gradient compression over the gang's
    # all-reduce (None = exact fp32; 8 = int8 blockwise-absmax, 1 =
    # sign+scale — the paper's b-bit storage argument applied to the
    # gradient wire format)
    stream_procs: int = 1
    ft_barrier_timeout_s: float = 120.0
    stream_grad_compress: Optional[int] = None
    # duplicate-traffic dedup cache + LSH retrieval (PR 9): the serving
    # engine's band-keyed score cache (serving/dedup.py — probe on
    # dedup_probe_bands band keys, guard on exact packed-code equality,
    # invalidated per WeightSet swap) and the banded retrieval index's
    # geometry.  rows_per_band=4 at b=8 gives 32-bit band keys, 64
    # bands at k=256 — collision probability ~R^4, steep enough that
    # near-duplicates probe the same bucket while unrelated docs don't.
    dedup_cache: bool = True
    dedup_entries: int = 65536
    dedup_rows_per_band: int = 4
    dedup_probe_bands: int = 4
    retrieval_rows_per_band: int = 4
    retrieval_top_k: int = 10

    def linear_config(self) -> BBitLinearConfig:
        return BBitLinearConfig(k=self.k, b=self.b,
                                n_classes=self.n_classes)

    def stream_kwargs(self, **overrides) -> dict:
        """Keyword arguments for ``train.streaming.fit_streaming`` at
        this config's paper scale; pass overrides for scaled-down runs
        (examples/benchmarks shrink batch/epochs, keep the averaging
        and checkpoint cadence)."""
        kw = dict(epochs=self.stream_epochs, batch_size=self.stream_batch,
                  lr=self.stream_lr, avg_start_frac=self.avg_start_frac,
                  ckpt_every_shards=self.ckpt_every_shards,
                  prefetch=self.stream_prefetch,
                  data_parallel=self.stream_data_parallel,
                  elastic=self.ft_elastic,
                  ckpt_keep_last=self.ft_ckpt_keep_last,
                  grad_compress=self.stream_grad_compress,
                  ckpt_barrier_timeout_s=self.ft_barrier_timeout_s)
        kw.update(overrides)
        return kw

    def restart_policy(self):
        """The ``train.supervisor.RestartPolicy`` for production runs
        at this config (``launch/train.py --supervise``): a restart
        budget with capped exponential backoff — long waits, because a
        real crash usually means the box needs a moment."""
        from repro.ft.retry import BackoffPolicy
        from repro.train.supervisor import RestartPolicy
        return RestartPolicy(
            max_restarts=self.ft_max_restarts,
            backoff=BackoffPolicy(base_s=self.ft_backoff_base_s,
                                  factor=2.0,
                                  cap_s=self.ft_backoff_cap_s,
                                  jitter_frac=0.1, seed=self.seed))

    def serve_kwargs(self, **overrides) -> dict:
        """Keyword arguments for ``serving.HashedClassifierEngine`` at
        this config's scale; examples/benches override buckets and
        batch size for scaled-down corpora."""
        kw = dict(scheme=self.scheme, max_batch=self.serve_max_batch,
                  max_wait_ms=self.serve_max_wait_ms,
                  replicas=self.serve_replicas,
                  nnz_buckets=self.serve_nnz_buckets,
                  pipeline_depth=self.serve_pipeline_depth,
                  stats_window=self.serve_stats_window,
                  adapt_every=self.serve_adapt_every)
        kw.update(overrides)
        return kw

    def dedup_kwargs(self, **overrides) -> dict:
        """Keyword arguments enabling the engine's duplicate-traffic
        score cache — merge into ``serve_kwargs()``'s dict (kept
        separate so batching knobs and cache knobs stay independently
        overridable)."""
        kw = dict(dedup_cache=self.dedup_cache,
                  dedup_entries=self.dedup_entries,
                  dedup_rows_per_band=self.dedup_rows_per_band,
                  dedup_probe_bands=self.dedup_probe_bands)
        kw.update(overrides)
        return kw

    def http_kwargs(self, **overrides) -> dict:
        """Keyword arguments for ``serving.ScoreServer`` — the HTTP
        front end around an engine built with ``serve_kwargs``."""
        kw = dict(host=self.serve_host, port=self.serve_port,
                  drain_timeout_s=self.serve_drain_timeout_s)
        kw.update(overrides)
        return kw


CONFIG = OPHPaperConfig()
