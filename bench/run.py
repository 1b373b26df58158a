"""Runs one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up from the seed (inputs, weights, warm-up of every shape
the window uses), measures for ``--seconds``, checks what the window
produced against the plain reference, and prints one JSON object as
the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device`` and ``checks`` (each compared number with its
limit).  Earlier lines record set-up, the dispatch arms the program
chose and the counts that are not metrics.

It runs only on a TPU with at least the chips the cell asks for, and
exits non-zero without a result anywhere else.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the system under test is missing ({src})",
              file=sys.stderr)
        return 2
    for path in (ROOT, src):
        if path not in sys.path:
            sys.path.insert(0, path)

    from bench import harness
    cell = harness.find_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program goes to the persistent cache, however quick its
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log({"phase": "start", "workload": args.workload,
                 "seed": args.seed, "jax": jax.__version__,
                 "compile_cache": cache})
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except Exception:  # noqa: BLE001 — report, then fail without a result
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
