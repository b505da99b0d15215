"""The served path, from outside: the server child, its HTTP client, the
roaring encoder.  Copied from chip_smoke.py (proven on the chip, PR 21);
nothing here imports pilosa_tpu or JAX."""

import collections
import ctypes
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
    first failure is re-raised at once and the jobs not yet started are
    dropped; those under way end with the server, which the caller stops."""
    local = threading.local()

    def run(job):
        if not hasattr(local, "client"):
            local.client = Client(port, child)
        return work(local.client, job)

    pool = ThreadPoolExecutor(connections)
    try:
        return list(pool.map(run, jobs))
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


CACHE_MIN_COMPILE_S = 10
TERM_WAIT_S = 30  # what the run's processes get to go after SIGTERM
KILL_WAIT_S = 60  # and after SIGKILL, before the run fails naming who stayed
PR_SET_PDEATHSIG, PR_SET_CHILD_SUBREAPER = 1, 36  # <linux/prctl.h>
_libc = ctypes.CDLL(None, use_errno=True)


def start_server(argv: list, root: str, data_dir: str, port: int, log_path: str,
                 cpu_devices: int = 0) -> subprocess.Popen:
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
    out and lets the one that costs most, the tier-64 Count (~30 s), in.
    ``cpu_devices`` (the rehearsal) puts the server on that many devices
    of the CPU backend.

    No process outlives the run.  The child leads a session of its own, so
    that nothing aimed at the harness's group hits it half-way through a
    request, and is sent SIGKILL by the kernel when the harness ends without
    having stopped it (call this from the main thread: the signal follows
    the thread that forked).  The harness adopts whatever the child leaves
    behind (it is its descendants' subreaper), so ``stop_server`` finds a
    process that detached from the child under its own pid."""
    harness = os.getpid()

    def die_with_harness():  # in the child, between fork and exec; the flag lasts through exec
        _libc.prctl(PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)
        if os.getppid() != harness:  # it was gone before the flag was set
            os._exit(1)

    _libc.prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)
    env = dict(os.environ, PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jaxcache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=str(CACHE_MIN_COMPILE_S))
    if cpu_devices:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cpu_devices}"
    with open(log_path, "wb") as out:
        return subprocess.Popen(
            [*argv, "-d", data_dir, "-b", f"127.0.0.1:{port}"],
            cwd=root, env=env, stdout=out, stderr=subprocess.STDOUT,
            start_new_session=True, preexec_fn=die_with_harness,
        )


class Proc(collections.namedtuple("Proc", "pid ppid pgid sid state cmd")):
    """One line of the process table, as /proc had it when we looked."""

    def __str__(self):
        return (f"pid {self.pid} ppid {self.ppid} sid {self.sid} state {self.state} "
                f"cmd {self.cmd[:200]!r}")


def process_table() -> dict:
    table = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                stat = f.read()
            with open(f"/proc/{entry}/cmdline", "rb") as f:  # empty for a zombie
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
        except OSError:  # gone while we looked
            continue
        state, ppid, pgid, sid = stat[stat.rindex(")") + 2:].split()[:4]  # comm may hold spaces
        table[int(entry)] = Proc(int(entry), int(ppid), int(pgid), int(sid), state, cmd)
    return table


def run_processes(child_pid: int) -> list:
    """Every process the run started that still exists: whatever descends
    from this process (a descendant whose parent has gone is re-parented to
    us, the subreaper), and whatever sits in the child's group or session."""
    table, me = process_table(), os.getpid()

    def descends(p):
        seen = set()
        while p.pid not in seen and p.ppid in table:
            if p.ppid == me:
                return True
            seen.add(p.pid)
            p = table[p.ppid]
        return False

    return [p for p in table.values()
            if p.pid != me and (descends(p) or child_pid in (p.pgid, p.sid))]


def accepts(port: int) -> bool:
    with socket.socket() as s:
        s.settimeout(1)
        return s.connect_ex(("127.0.0.1", port)) == 0


def reap(child: subprocess.Popen) -> bool:
    """Reap whatever of ours has ended, the child and the orphans we
    adopted; True while this process still has a child of any kind.  That
    is the kernel's word and not a look at /proc, which a process that
    changes its pid every millisecond slips through."""
    child.poll()
    try:
        while True:
            pid, status = os.waitpid(-1, os.WNOHANG)
            if pid == 0:
                return True
            if pid == child.pid:  # ended between the poll and here
                child.returncode = os.waitstatus_to_exitcode(status)
    except ChildProcessError:
        return False


def stop_server(child: subprocess.Popen, port: int):
    """End every process the run started and wait until none exists and
    nothing accepts on the run's port; a BenchFailure names what stayed.
    SIGTERM to all, TERM_WAIT_S for them to go, then SIGKILL to whatever is
    there each time we look (a process may have been started meanwhile),
    for KILL_WAIT_S.  Call it when no other child of this process runs.
    A sweep that has come to its end is not made again: the port it saw
    refuse may be another process's by then."""
    if getattr(child, "swept", False):
        return
    t0 = time.monotonic()
    signalled, last_seen, child_s, killed = set(), [], None, False
    while True:
        children = reap(child)
        if child_s is None and child.returncode is not None:
            child_s = time.monotonic() - t0
        alive = [p for p in run_processes(child.pid) if p.state != "Z" or p.ppid != os.getpid()]
        last_seen = alive or last_seen
        waited = time.monotonic() - t0
        if not alive and not children and not accepts(port):
            break
        if waited > TERM_WAIT_S + KILL_WAIT_S:
            raise BenchFailure(
                f"the run leaves a process that {KILL_WAIT_S} s of SIGKILL did not end"
                + (f", and port {port} still accepts" if accepts(port) else "")
                + "; seen last: " + "; ".join(map(str, last_seen)))
        killed = killed or waited > TERM_WAIT_S
        for p in alive:
            if p.state != "Z" and (killed or p.pid not in signalled):
                try:
                    os.kill(p.pid, signal.SIGKILL if killed else signal.SIGTERM)
                except ProcessLookupError:
                    pass
            signalled.add(p.pid)
        time.sleep(0.05)
    child.swept = True
    if signalled:
        log(f"end of run: {len(signalled)} process(es) ended in {time.monotonic() - t0:.2f} s; "
            f"the server child went {child_s:.2f} s after SIGTERM; "
            f"SIGKILL {'needed' if killed else 'not needed'}; port {port} refuses")
