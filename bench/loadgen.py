"""Open-loop HTTP load generator for the scoring service.

Runs as a child process of the benchmark and never imports JAX (the
parent holds the chip).  Reads its parameters as JSON from argv[1],
builds every request body from the seed, prints ``READY``, waits for the
server's port on standard input, then sends request i at ``offsets[i]``
seconds after that line, whether or not earlier requests have been answered.
One ``POST /score`` carries one document.  When every request has been
answered, or ``grace_s`` after the last was due, it prints one JSON line:
per request its due time, send lateness, completion time (or null), HTTP
status and score.

    python3 bench/loadgen.py '{"host": "127.0.0.1", "seed": 1, "rate": 200, ...}'
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from bench import corpus  # noqa: E402


def plan(p: dict):
    """(send offsets, document lengths) of the run: a pure function of
    the parameters, shared with the checker."""
    offsets = corpus.arrival_offsets(p["seed"], p["rate"], p["seconds"])
    lengths = corpus.shuffled(
        corpus.length_set(len(offsets), p["nnz_median"], p["nnz_mean"],
                          p["nnz_max"]), p["seed"], 2)
    return offsets, lengths


def body(p: dict, i: int, length: int) -> bytes:
    doc = corpus.serve_doc(p["seed"], i, length)
    return b'{"docs":[[' + corpus.ids_json(doc) + b']]}'


class Pool:
    """Keep-alive connections: a request takes an idle one or opens a
    new one, so a slow answer never holds back a later request."""

    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.idle: list = []
        self.opened = 0

    async def get(self):
        if self.idle:
            return self.idle.pop()
        self.opened += 1
        return await asyncio.open_connection(self.host, self.port)

    def put(self, conn) -> None:
        self.idle.append(conn)


async def _one(pool: Pool, payload: bytes, rec: dict, clock) -> None:
    head = (f"POST /score HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode()
    conn = None
    try:
        conn = await pool.get()
        reader, writer = conn
        writer.write(head + payload)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        length = 0
        keep = True
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b""):
                break
            key, _, val = line.decode("latin-1").partition(":")
            key = key.strip().lower()
            if key == "content-length":
                length = int(val)
            elif key == "connection" and val.strip().lower() == "close":
                keep = False
        data = await reader.readexactly(length)
        rec["done"] = clock()
        rec["status"] = status
        if status == 200:
            rec["score"] = json.loads(data)["scores"][0]
        if keep:
            pool.put(conn)
            conn = None
    except (OSError, ValueError, IndexError, asyncio.IncompleteReadError) \
            as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if conn is not None:
            conn[1].close()


async def drive(p: dict, bodies: list, offsets: np.ndarray) -> list:
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    clock = lambda: loop.time() - t0  # noqa: E731
    pool = Pool(p["host"], p["port"])
    recs = [{"due": float(o), "sent": None, "done": None, "status": None,
             "score": None} for o in offsets]
    tasks = []
    for i, off in enumerate(offsets):
        wait = off - clock()
        if wait > 0:
            await asyncio.sleep(wait)
        recs[i]["sent"] = clock()
        tasks.append(asyncio.ensure_future(
            _one(pool, bodies[i], recs[i], clock)))
    deadline = float(offsets[-1]) + p["grace_s"]
    pending = [t for t in tasks if not t.done()]
    if pending:
        await asyncio.wait(pending, timeout=max(0.0, deadline - clock()))
    for t in tasks:
        if not t.done():
            t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for conn in pool.idle:
        conn[1].close()
    return recs, pool.opened


def main() -> int:
    p = json.loads(sys.argv[1])
    offsets, lengths = plan(p)
    bodies = [body(p, i, int(n)) for i, n in enumerate(lengths)]
    print("READY", flush=True)
    line = sys.stdin.readline()        # the server's port: go
    if not line.strip():
        return 1
    p["port"] = int(line)
    t = time.perf_counter()
    recs, opened = asyncio.run(drive(p, bodies, offsets))
    print(json.dumps({"records": recs, "connections": opened,
                      "elapsed_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
