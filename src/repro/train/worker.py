"""Per-rank worker entrypoint for multi-process gang training.

``python -m repro.train.worker --spec <path>`` is what
``train.supervisor.run_multiprocess_supervised`` (and
``launch.train --procs N``) execs once per rank.  The spec is a JSON
file fully describing one rank's run::

    {"root": ..., "cfg": {...BBitLinearConfig fields...},
     "fit": {...fit_streaming kwargs...},
     "procs": 2, "rank": 0, "coordinator": "127.0.0.1:12345",
     "run_dir": ..., "fault_spec": {...}, "fault_state": ...,
     "result_path": ..., "params_path": ...}

Order of operations matters and is the whole point of this module:

  1. **arm the fault plan** (``ft.faults.FaultPlan.from_spec`` — the
     per-rank ``fault_state`` file restores fired counts, so a
     ``times=1`` process kill does not re-fire after a gang respawn);
  2. **bootstrap the runtime** (``distributed.runtime.init_runtime``:
     gloo + ``jax.distributed.initialize`` + ``faults.set_rank`` —
     before any jax computation);
  3. train (``fit_streaming(..., runtime=rt)``);
  4. dump this rank's result record + final/averaged params.

Exit codes are the supervisor protocol: 0 = finished, **64** =
``ValueError`` (a configuration/compatibility error — deterministic,
the supervisor must NOT retry it), anything else (including signal
deaths) = a crash the supervisor may restart.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

CONFIG_ERROR_EXIT = 64


def _dump_params(path: str, result) -> None:
    import jax
    import numpy as np

    arrs = {}
    for i, leaf in enumerate(jax.tree.leaves(result.params)):
        arrs[f"p{i}"] = np.asarray(jax.device_get(leaf))
    if result.avg_params is not None:
        for i, leaf in enumerate(jax.tree.leaves(result.avg_params)):
            arrs[f"a{i}"] = np.asarray(jax.device_get(leaf))
    np.savez(path, **arrs)


def run_spec(spec: dict) -> int:
    """Executes one rank's spec; returns the process exit code."""
    from repro.ft import faults

    if spec.get("fault_spec"):
        plan = faults.FaultPlan.from_spec(spec["fault_spec"],
                                          spec.get("fault_state"))
        faults.arm_plan(plan)

    from repro.distributed.runtime import heartbeat, init_runtime

    rt = init_runtime(procs=int(spec.get("procs", 1)),
                      rank=int(spec.get("rank", 0)),
                      coordinator=spec.get("coordinator"),
                      run_dir=spec.get("run_dir"))

    from repro.models.linear import BBitLinearConfig
    from repro.train.streaming import fit_streaming

    cfg = BBitLinearConfig(**spec["cfg"])
    try:
        result = fit_streaming(spec["root"], cfg, runtime=rt,
                               **spec.get("fit", {}))
    except ValueError:
        traceback.print_exc()
        return CONFIG_ERROR_EXIT

    if spec.get("params_path"):
        _dump_params(spec["params_path"], result)
    if spec.get("result_path"):
        rec = {"rank": rt.rank, "procs": rt.procs,
               "n_steps": result.n_steps,
               "examples_seen": result.examples_seen,
               "shards_processed": result.shards_processed,
               "progressive_acc": result.progressive_acc,
               "completed": result.completed,
               "train_seconds": result.train_seconds,
               "lineage": result.topology_lineage,
               "counters": result.counters}
        with open(spec["result_path"], "w") as f:
            json.dump(rec, f)
    heartbeat(rt, step=result.n_steps,
              shards_done=result.shards_processed, phase="done")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.train.worker",
        description="one rank of a multi-process streaming training "
                    "gang (spawned by train.supervisor)")
    ap.add_argument("--spec", required=True,
                    help="path to this rank's JSON spec")
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    return run_spec(spec)


if __name__ == "__main__":
    sys.exit(main())
