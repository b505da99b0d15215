"""The served path, from outside: the server child, its HTTP client, the
roaring encoder.  Copied from chip_smoke.py (proven on the chip, PR 21);
nothing here imports pilosa_tpu or JAX."""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SHARD_WIDTH = 1 << 20
W64 = SHARD_WIDTH // 64  # uint64 words per shard row
PLANE_BYTES = SHARD_WIDTH // 8  # one row-plane of one shard, resident
T0 = time.monotonic()


class BenchFailure(Exception):
    """The run cannot produce a result: exit non-zero, no last line."""


def log(msg):
    print(f"[{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def roaring(rows: dict) -> bytes:
    """{row id: uint64[W64]} -> Pilosa roaring bytes, one bitmap container
    per occupied 2^16-bit chunk, encoded straight from the words (format:
    pilosa_tpu/roaring/codec.py header)."""
    keys, counts, chunks = [], [], []
    for r, words in sorted(rows.items()):
        c = words.reshape(16, 1024)
        n = np.bitwise_count(c).sum(axis=1)
        for k in np.nonzero(n)[0]:
            keys.append(r * 16 + int(k))
            counts.append(int(n[k]))
            chunks.append(c[k].tobytes())
    hdr = np.zeros(len(keys), dtype=[("key", "<u8"), ("typ", "<u2"), ("n1", "<u2")])
    hdr["key"], hdr["typ"], hdr["n1"] = keys, 2, np.asarray(counts) - 1
    first = 8 + 16 * len(keys)
    offsets = (first + 8192 * np.arange(len(keys))).astype("<u4")
    head = np.array([12348, len(keys)], "<u4").tobytes()
    return b"".join([head, hdr.tobytes(), offsets.tobytes(), *chunks])


def pack_rows(column: np.ndarray, n_rows: int) -> dict:
    """A categorical column (one value per column id of the shard) ->
    {row id: uint64[W64]}, one row per value."""
    onehot = column[None, :] == np.arange(n_rows, dtype=column.dtype)[:, None]
    words = np.packbits(onehot, axis=1, bitorder="little").view(np.uint64)
    return {r: words[r] for r in range(n_rows)}


def pack_planes(values: np.ndarray, depth: int) -> dict:
    """An integer column declared with min 0 (every column has a value) ->
    the BSI view's rows: plane k is bit k of the value, row ``depth`` the
    not-null row."""
    planes = {
        k: np.packbits((values >> k) & 1, bitorder="little").view(np.uint64)
        for k in range(depth)
    }
    planes[depth] = np.full(W64, np.uint64(0xFFFFFFFFFFFFFFFF))
    return planes


class Client:
    """One persistent connection to the server child."""

    def __init__(self, port: int, child: subprocess.Popen, timeout: float = 600):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        self.child = child

    def call(self, method: str, path: str, body: bytes = None) -> bytes:
        if self.child.poll() is not None:
            raise BenchFailure(f"server child exited with {self.child.returncode}")
        self.conn.request(method, path, body=body)
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status != 200:
            raise BenchFailure(f"{method} {path}: HTTP {resp.status} {payload[:300]!r}")
        return payload

    def metrics(self) -> dict:
        out = {}
        for line in self.call("GET", "/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
        return out

    def debug_vars(self) -> dict:
        return json.loads(self.call("GET", "/debug/vars"))

    def close(self):
        self.conn.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(client: Client, timeout: float = 300.0):
    end = time.monotonic() + timeout
    while True:
        try:
            client.call("GET", "/readyz")
            return
        except (OSError, http.client.HTTPException, BenchFailure):
            if client.child.poll() is not None:
                raise BenchFailure(f"server child exited with {client.child.returncode}")
            if time.monotonic() > end:
                raise BenchFailure("server not ready in time")
            client.conn.close()
            time.sleep(0.25)


def on_connections(port, child, jobs, work, connections: int):
    """Run ``work(client, job)`` for every job on ``connections``
    connections (one per pool thread); returns results in job order.  The
    first failure is re-raised and the jobs not yet started are dropped."""
    local = threading.local()

    def run(job):
        if not hasattr(local, "client"):
            local.client = Client(port, child)
        return work(local.client, job)

    pool = ThreadPoolExecutor(connections)
    try:
        return list(pool.map(run, jobs))
    finally:
        pool.shutdown(wait=True, cancel_futures=True)


CACHE_MIN_COMPILE_S = 10


def start_server(argv: list, root: str, data_dir: str, port: int, log_path: str,
                 cpu: bool) -> subprocess.Popen:
    """The server child, the only process of the run that touches JAX.
    Default configuration, with two things set in its environment.  JAX's
    persistent compilation cache lies at ``<checkout>/.jaxcache``, whatever
    directory the machine offers: a fixed path inside the checkout, shared
    with no other checkout.  And only a program that took CACHE_MIN_COMPILE_S
    or more to compile is written to it (JAX's default is 1 s).  On the chip
    the solo ``jit_count_tree``, read back from the cache, is handed the
    engine's row-major-pinned shard mask in a layout it does not expect and
    every request fails with HTTP 500 (PERF.md, Open questions 00: seen with
    the threshold at 0; at 1 s it compiled just under the threshold and the
    second run passed).  The threshold keeps every program of a few seconds
    out and lets the one that costs most, the tier-64 Count (~30 s), in."""
    env = dict(os.environ, PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jaxcache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=str(CACHE_MIN_COMPILE_S))
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    with open(log_path, "wb") as out:
        return subprocess.Popen(
            [*argv, "-d", data_dir, "-b", f"127.0.0.1:{port}"],
            cwd=root, env=env, stdout=out, stderr=subprocess.STDOUT,
            start_new_session=True,
        )


def stop_server(child: subprocess.Popen):
    """End the child's whole process group and wait until it is gone."""
    if child.poll() is None:
        os.killpg(child.pid, signal.SIGTERM)
        try:
            child.wait(30)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(child.pid, signal.SIGKILL)  # stragglers of the group
    except ProcessLookupError:
        pass
    child.wait(30)
