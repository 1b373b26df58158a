"""Supervised restart loop: run ``fit_streaming`` until it finishes.

The single-host half of the ROADMAP's fault-tolerant training story:
a crash (injected or real) kills the fit mid-shard; the supervisor
waits out a capped exponential backoff (deterministic jitter,
``repro.ft.retry.BackoffPolicy``) and calls ``fit_streaming`` again
with ``resume=True`` — the trainer restores from the newest VALID
checkpoint (torn/corrupt ones are quarantined, see ``ckpt.checkpoint``)
and replays the stream from that boundary.  Because batch replay is a
pure function of ``(seed, epoch, position)``, the supervised run's
final parameters are bit-identical to an uninterrupted run — the
crash-equivalence property (tests/test_fault_tolerance.py) that makes
"the run survives production reality" a testable claim rather than a
hope.

What counts as a crash: any exception EXCEPT

  * ``ValueError`` — a configuration/compatibility error
    (archive/config mismatch, incompatible checkpoint): retrying can
    only fail identically, so it propagates immediately;
  * ``KeyboardInterrupt`` / ``SystemExit`` — the operator, not a
    fault.

A shared ``StepWatchdog`` (``repro.ft.watchdog``) rides along across
restarts, so straggler escalations accumulate over the whole supervised
run; its counters are surfaced on the returned ``SupervisedRun``.

``run_multiprocess_supervised`` is the multi-HOST half: it launches a
``procs``-wide gang of ``repro.train.worker`` subprocesses (each a real
OS process joined through ``jax.distributed``), watches them, and
**gang-restarts** on any worker death — a single rank cannot rejoin a
live gloo gang, so the whole gang is SIGKILLed and respawned from the
latest valid *coordinated* checkpoint (``ckpt.coordinated``).  Restart
spawns are staggered per rank through ``BackoffPolicy.for_rank`` so a
gang restart does not reproduce the thundering herd the jitter exists
to break.  Exit code 64 from any worker is the config-error protocol
(``train.worker``): deterministic, raised as ``ValueError``, never
retried.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from repro.ft.retry import BackoffPolicy
from repro.ft.watchdog import StepWatchdog
from repro.train.streaming import StreamFitResult, fit_streaming
from repro.train.worker import CONFIG_ERROR_EXIT

__all__ = ["RestartPolicy", "CrashRecord", "SupervisedRun",
           "run_supervised", "MultiProcessRun",
           "run_multiprocess_supervised"]

log = logging.getLogger("repro.train.supervisor")


@dataclasses.dataclass(frozen=True)
class RestartPolicy:
    """How hard to try: at most ``max_restarts`` restarts, waiting out
    ``backoff.delay_s(attempt)`` before each one."""
    max_restarts: int = 3
    backoff: BackoffPolicy = BackoffPolicy(base_s=0.05, factor=2.0,
                                           cap_s=5.0, jitter_frac=0.1)


@dataclasses.dataclass
class CrashRecord:
    """One supervised crash: which restart followed it, what died, and
    how long the recovery (backoff + restore + replay to the crash
    point) took."""
    restart: int
    error: str
    backoff_s: float
    recover_s: float = 0.0


@dataclasses.dataclass
class SupervisedRun:
    result: StreamFitResult
    restarts: int
    crashes: List[CrashRecord]
    watchdog: StepWatchdog

    @property
    def straggler_escalations(self) -> int:
        return len(self.watchdog.escalations)


def run_supervised(
    root: str,
    cfg: Any,
    *,
    policy: Optional[RestartPolicy] = None,
    watchdog: Optional[StepWatchdog] = None,
    **fit_kwargs,
) -> SupervisedRun:
    """Runs ``fit_streaming(root, cfg, **fit_kwargs)`` under restart
    supervision; returns the finished result plus crash accounting.

    ``ckpt_dir`` is required — without checkpoints every restart would
    silently start over, which is exactly the failure mode this loop
    exists to prevent.  ``resume`` is forced True on every attempt
    (including the first: picking up a previous supervised run's
    checkpoints is the intended behavior).
    """
    if not fit_kwargs.get("ckpt_dir"):
        raise ValueError(
            "run_supervised requires ckpt_dir: without checkpoints a "
            "restart cannot resume and would retrain from scratch")
    if fit_kwargs.get("resume") is False:
        raise ValueError(
            "run_supervised forces resume=True — a supervised restart "
            "that refuses its own checkpoints cannot recover")
    fit_kwargs["resume"] = True
    policy = RestartPolicy() if policy is None else policy
    watchdog = StepWatchdog() if watchdog is None else watchdog
    crashes: List[CrashRecord] = []
    attempt = 0
    while True:
        t_try = time.perf_counter()
        try:
            result = fit_streaming(root, cfg, watchdog=watchdog,
                                   **fit_kwargs)
        except (KeyboardInterrupt, SystemExit):
            raise
        except ValueError:
            # config/compatibility error — deterministic, not a crash
            raise
        except Exception as e:  # noqa: BLE001 — the supervised surface
            if crashes:
                crashes[-1].recover_s += time.perf_counter() - t_try
            if attempt >= policy.max_restarts:
                log.error(
                    "giving up after %d restarts (%d crashes); last "
                    "error: %r", attempt, len(crashes) + 1, e)
                raise
            delay = policy.backoff.delay_s(attempt)
            log.warning(
                "training attempt %d crashed (%r) — restarting from "
                "the latest valid checkpoint in %.3fs "
                "(restart %d/%d)", attempt + 1, e, delay, attempt + 1,
                policy.max_restarts)
            crashes.append(CrashRecord(restart=attempt + 1,
                                       error=repr(e), backoff_s=delay))
            time.sleep(delay)
            attempt += 1
            continue
        if crashes:
            crashes[-1].recover_s += time.perf_counter() - t_try
        return SupervisedRun(result=result, restarts=attempt,
                             crashes=crashes, watchdog=watchdog)


# ------------------------------------------------ multi-process gang ----

@dataclasses.dataclass
class MultiProcessRun:
    """A finished gang run: per-rank result records (rank → the dict
    ``train.worker`` dumped), restart/crash accounting, and where each
    rank left its final params (``params_paths[rank]``)."""
    results: Dict[int, dict]
    params_paths: Dict[int, str]
    restarts: int
    crashes: List[CrashRecord]
    run_dir: str

    @property
    def result(self) -> dict:
        return self.results[0]


def _free_port() -> int:
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def _src_root() -> str:
    # <src>/repro/train/supervisor.py → <src>, so spawned workers
    # import the same tree regardless of the caller's cwd
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _kill_gang(children) -> None:
    for p in children:
        if p.poll() is None:
            try:
                p.send_signal(signal.SIGKILL)
            except OSError:
                pass
    for p in children:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


def _tail(path: str, n: int = 12) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "<no log>"


def run_multiprocess_supervised(
    root: str,
    cfg: Any,
    *,
    procs: int,
    run_dir: str,
    policy: Optional[RestartPolicy] = None,
    fault_spec: Optional[dict] = None,
    attempt_timeout_s: float = 600.0,
    **fit_kwargs,
) -> MultiProcessRun:
    """Runs ``fit_streaming(root, cfg, **fit_kwargs)`` as a
    ``procs``-process ``jax.distributed`` gang under gang-restart
    supervision.

    Each attempt binds a fresh coordinator port, writes one JSON spec
    per rank under ``run_dir`` and execs ``python -m
    repro.train.worker`` per rank; workers inherit this process's
    environment, so its ``JAX_PLATFORMS`` and ``XLA_FLAGS`` (e.g. fake
    CPU devices per rank) set what each rank sees.  All ranks run on
    this host, so a backend with chips is refused: this process already
    holds them, and one process drives every local chip through
    ``data_parallel`` instead.  The first non-zero worker exit kills
    the WHOLE gang (a dead rank cannot rejoin live collectives) and —
    within ``policy.max_restarts`` — respawns it; every worker resumes
    from the latest valid coordinated checkpoint, so the finished
    gang's params are bit-identical to an uninterrupted run's.
    ``fault_spec`` (``FaultPlan.to_spec``) ships a rank-targeted fault
    plan to every worker; fired counts persist per rank under
    ``run_dir`` so ``times=1`` kills do not re-fire after a respawn.
    """
    if procs < 1:
        raise ValueError(f"procs must be >= 1, got {procs}")
    from repro import perf
    backend = perf.device_fingerprint()["backend"]
    if procs > 1 and backend != "cpu":
        raise ValueError(
            f"a {procs}-process gang on one {backend} host cannot share "
            f"its chips, and this process already holds them: run one "
            f"process with data_parallel={procs} (--data-parallel "
            f"{procs}) instead")
    if not fit_kwargs.get("ckpt_dir"):
        raise ValueError(
            "run_multiprocess_supervised requires ckpt_dir: a gang "
            "restart without checkpoints would retrain from scratch")
    if fit_kwargs.get("resume") is False:
        raise ValueError(
            "run_multiprocess_supervised forces resume=True — a gang "
            "restart that refuses its own checkpoints cannot recover")
    fit_kwargs["resume"] = True
    policy = RestartPolicy() if policy is None else policy
    os.makedirs(run_dir, exist_ok=True)

    import dataclasses as _dc
    cfg_dict = _dc.asdict(cfg)

    env_base = dict(os.environ)
    env_base["PYTHONPATH"] = (
        _src_root() + os.pathsep + env_base.get("PYTHONPATH", ""))

    crashes: List[CrashRecord] = []
    attempt = 0
    while True:
        coordinator = f"127.0.0.1:{_free_port()}"
        children, logs = [], []
        for r in range(procs):
            spec = {"root": root, "cfg": cfg_dict, "fit": fit_kwargs,
                    "procs": procs, "rank": r,
                    "coordinator": coordinator, "run_dir": run_dir,
                    "fault_spec": fault_spec,
                    "fault_state": os.path.join(
                        run_dir, f"fault_state_rank{r}.json"),
                    "result_path": os.path.join(
                        run_dir, f"result_rank{r}.json"),
                    "params_path": os.path.join(
                        run_dir, f"params_rank{r}.npz")}
            spec_path = os.path.join(run_dir, f"spec_rank{r}.json")
            with open(spec_path, "w") as f:
                json.dump(spec, f)
            if attempt > 0:
                # per-rank de-correlated stagger: a gang restart must
                # not relaunch every rank at the same instant
                time.sleep(policy.backoff.for_rank(r)
                           .delay_s(attempt - 1))
            log_path = os.path.join(run_dir,
                                    f"log_rank{r}_try{attempt}.txt")
            logs.append(log_path)
            lf = open(log_path, "w")
            # exec the worker FILE, not ``-m repro.train.worker``: -m
            # would import repro.train.__init__ (and with it the whole
            # jax training stack) before the worker can call
            # jax.distributed.initialize, which must precede any jax
            # computation
            worker_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "worker.py")
            children.append(subprocess.Popen(
                [sys.executable, worker_path, "--spec", spec_path],
                env=env_base, stdout=lf, stderr=subprocess.STDOUT,
                close_fds=True))
            lf.close()

        t_try = time.perf_counter()
        failure: Optional[str] = None
        while True:
            codes = [p.poll() for p in children]
            bad = [(r, c) for r, c in enumerate(codes)
                   if c not in (None, 0)]
            if bad:
                r, c = bad[0]
                if c == CONFIG_ERROR_EXIT:
                    _kill_gang(children)
                    raise ValueError(
                        f"gang rank {r} reported a configuration "
                        f"error (exit {c}):\n{_tail(logs[r])}")
                failure = (f"rank {r} died with "
                           + (f"signal {-c}" if c < 0 else f"exit {c}"))
                break
            if all(c == 0 for c in codes):
                break
            if time.perf_counter() - t_try > attempt_timeout_s:
                failure = (f"gang attempt timed out after "
                           f"{attempt_timeout_s:.0f}s")
                break
            time.sleep(0.02)
        if failure is None and all(p.poll() == 0 for p in children):
            if crashes:
                crashes[-1].recover_s += time.perf_counter() - t_try
            results, params = {}, {}
            for r in range(procs):
                with open(os.path.join(run_dir,
                                       f"result_rank{r}.json")) as f:
                    results[r] = json.load(f)
                params[r] = os.path.join(run_dir,
                                         f"params_rank{r}.npz")
            return MultiProcessRun(results=results, params_paths=params,
                                   restarts=attempt, crashes=crashes,
                                   run_dir=run_dir)
        _kill_gang(children)
        if crashes:
            crashes[-1].recover_s += time.perf_counter() - t_try
        if attempt >= policy.max_restarts:
            raise RuntimeError(
                f"gang gave up after {attempt} restarts: {failure}\n"
                + _tail(logs[0]))
        delay = policy.backoff.delay_s(attempt)
        log.warning("gang attempt %d failed (%s) — restarting in "
                    "%.3fs (restart %d/%d)", attempt + 1, failure,
                    delay, attempt + 1, policy.max_restarts)
        crashes.append(CrashRecord(restart=attempt + 1, error=failure,
                                   backoff_s=delay))
        time.sleep(delay)
        attempt += 1
