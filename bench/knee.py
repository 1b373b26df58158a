"""Sweep of open-loop rates against the scoring service: the knee.

Sets the ``oph-serve`` cell up once (weights, engine, HTTP server, every
lane warm), then offers each rate for ``--seconds`` with a fresh load
generator and prints, per rate, the 95th percentile from due time to
response, its value over the first and the second half of the requests,
the requests still unanswered when the last one was due (backlog), and
how late the generator sent.  The knee is the highest rate whose backlog
does not grow through the window; the cell runs at 4/5 of it.  Run once
on the chip when the cell is defined:

    python3 bench/knee.py --seed 5 --seconds 10 --rates 100 200 300 400
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="oph-serve")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from bench.runners import serve
    bench = harness.benchmark()
    cell = harness.find_cell(args.workload, bench)
    config, traffic = harness.cell_files(cell, bench)
    ctx = harness.Context(cell, config, dict(traffic, rate=max(args.rates)),
                          args.seed, cell["chips"],
                          os.path.join(harness.WORK_DIR, cell["name"]),
                          args.seconds)
    t0 = time.perf_counter()
    state = serve.setup(ctx)          # warms the lanes of the top rate
    harness.log({"phase": "setup", "setup_s": time.perf_counter() - t0})
    state["child"].kill()
    state["child"].wait()
    try:
        for rate in args.rates:
            ctx.traffic = dict(traffic, rate=rate)
            p = serve.load_params(ctx)
            child = subprocess.Popen(
                [sys.executable, serve.LOADGEN, json.dumps(p)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                cwd=harness.ROOT)
            if child.stdout.readline().strip() != "READY":
                raise RuntimeError("load generator failed to start")
            state.update(child=child, load=p,
                         before=state["engine"].stats())
            out = serve.window(ctx, state, args.seconds)
            harness.log({"rate": rate, **out.metrics, "failed": out.failed,
                         **out.counters, **{k: v for k, v in
                                            out.notes.items()
                                            if k != "dedup"}})
    finally:
        serve.release(ctx, state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
