"""Open-loop HTTP load generator; runs as a child process and imports no JAX.

    python bench/loadgen.py   (driven by bench.loops.OpenHTTPLoop over stdin/stdout)

Protocol, one JSON object per line:

1. in: the set-up — the edge's port, a ``Workload.to_dict`` template with
   ``y`` left out, N, the run's seed, ``rate_per_s``, ``seconds``,
   ``connections`` and ``warm_requests``. The generator encodes every
   request body of the window up front (request i carries
   :func:`bench.labels.random_split` (seed, i)), opens its keep-alive
   connections, sends the warm requests one at a time, and answers
   ``{"ready": true}``.
2. in: ``{"go": true, "sample": k}``. Requests are sent when due
   (:func:`bench.labels.arrival_offsets`), whether or not earlier ones
   have finished, on an idle connection or a new one. The answer holds,
   per request and in seconds from the window's start, when it was due,
   sent and done, whether it succeeded, and the decision values of ``k``
   requests drawn from the seed. Each request is waited for until a
   minute past the window's close.
3. in: ``{"quit": true}`` or end of input: close and exit.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.labels import arrival_offsets, random_split  # noqa: E402

_GRACE_S = 60.0
_OK_PREFIX = b'{"results": [{"ok": true'


def _body(template: dict, y: np.ndarray) -> bytes:
    payload = dict(template)
    payload["y"] = {"__array__": y.tolist(), "dtype": "float32"}
    data = json.dumps(payload).encode("utf-8")
    head = (f"POST /v1/workloads HTTP/1.1\r\nHost: localhost\r\n"
            f"Content-Type: application/json\r\nContent-Length: {len(data)}\r\n\r\n")
    return head.encode("latin-1") + data


class Pool:
    def __init__(self, port: int):
        self.port = port
        self.idle: list = []

    async def get(self):
        if self.idle:
            return self.idle.pop()
        return await asyncio.open_connection("127.0.0.1", self.port)

    def put(self, conn) -> None:
        self.idle.append(conn)

    def close(self) -> None:
        for _, writer in self.idle:
            writer.close()
        self.idle = []


async def _exchange(conn, request: bytes) -> bytes:
    reader, writer = conn
    writer.write(request)
    await writer.drain()
    status = await reader.readline()
    if not status:
        raise ConnectionError("connection closed")
    length = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        name, _, value = line.decode("latin-1").partition(":")
        if name.strip().lower() == "content-length":
            length = int(value.strip())
    body = await reader.readexactly(length)
    if b" 200 " not in status:
        raise RuntimeError(f"HTTP status {status!r}")
    return body


async def _one(pool: Pool, request: bytes, t0: float, due: float, rec: dict, i: int):
    delay = t0 + due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["sent"][i] = time.perf_counter() - t0
    try:
        conn = await pool.get()
        body = await _exchange(conn, request)
        pool.put(conn)
        rec["ok"][i] = body.startswith(_OK_PREFIX)
        if i in rec["keep"]:
            rec["keep"][i] = body
    except (OSError, ConnectionError, RuntimeError, asyncio.IncompleteReadError):
        rec["ok"][i] = False
    rec["done"][i] = time.perf_counter() - t0


async def _window(pool, bodies, dues, sample, seconds):
    count = len(bodies)
    rec = {"sent": [0.0] * count, "done": [None] * count, "ok": [False] * count,
           "keep": {int(i): None for i in sample}}
    t0 = time.perf_counter() + 0.02
    tasks = [asyncio.ensure_future(_one(pool, bodies[i], t0, float(dues[i]), rec, i))
             for i in range(count)]
    _, pending = await asyncio.wait(tasks, timeout=seconds + _GRACE_S + 1.0)
    for task in pending:
        task.cancel()
    now = time.perf_counter() - t0
    done = [now if d is None else d for d in rec["done"]]
    sampled = []
    for i in sorted(rec["keep"]):
        body = rec["keep"][i]
        values = None
        if body is not None and rec["ok"][i]:
            values = json.loads(body)["results"][0]["response"]["values"]["__array__"]
        sampled.append([i, values])
    return {"due": [float(d) for d in dues], "sent": rec["sent"], "done": done,
            "ok": rec["ok"], "sampled": sampled}


async def main() -> None:
    loop = asyncio.get_running_loop()
    reader = asyncio.StreamReader()
    await loop.connect_read_pipe(lambda: asyncio.StreamReaderProtocol(reader), sys.stdin)

    async def recv():
        line = await reader.readline()
        return json.loads(line) if line else {"quit": True}

    def send(msg):
        sys.stdout.write(json.dumps(msg) + "\n")
        sys.stdout.flush()

    cfg = await recv()
    seed, n = int(cfg["seed"]), int(cfg["n"])
    dues = arrival_offsets(seed, float(cfg["rate_per_s"]), float(cfg["seconds"]))
    bodies = [_body(cfg["template"], random_split(seed, i, n)) for i in range(len(dues))]
    pool = Pool(int(cfg["url_port"]))
    conns = [await pool.get() for _ in range(int(cfg["connections"]))]
    for conn in conns:
        pool.put(conn)
    warm_rng = np.random.default_rng([seed, 8])
    for _ in range(int(cfg["warm_requests"])):
        y = warm_rng.permutation(np.repeat(np.array([-1.0, 1.0], np.float32), [n // 2, n - n // 2]))
        conn = await pool.get()
        body = await _exchange(conn, _body(cfg["template"], y))
        pool.put(conn)
        if not body.startswith(_OK_PREFIX):
            send({"ready": False, "error": body[:500].decode("utf-8", "replace")})
            return
    send({"ready": True, "requests": len(dues)})
    while True:
        msg = await recv()
        if msg.get("quit"):
            break
        if msg.get("go"):
            k = min(int(msg["sample"]), len(dues))
            sample = np.random.default_rng([seed, 9]).choice(len(dues), k, replace=False)
            send(await _window(pool, bodies, dues, sample, float(cfg["seconds"])))
    pool.close()


if __name__ == "__main__":
    asyncio.run(main())
