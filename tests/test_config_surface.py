"""One configuration surface: ``pilosa_tpu/config.py`` is the only
reader of the process environment for settings (TOML, ``PILOSA_TPU_*``
through ``load_env``, flags) and ``Server`` hands its values down.  The
names below were once read a second time — or only — by ``os.environ``
inside the engine, the batcher and the front ends; a value set there
must now change nothing."""

import ast
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from pilosa_tpu import native
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.net import procserver
from pilosa_tpu.net.admission import AdmissionController
from pilosa_tpu.net.aserver import AsyncHTTPServer
from pilosa_tpu.net.procserver import ProcessHTTPServer
from pilosa_tpu.net.server import bind_http
from pilosa_tpu.parallel import MeshEngine, make_mesh
from pilosa_tpu.parallel.batcher import CountBatcher
from pilosa_tpu.parallel.engine import DEFAULT_RESULT_MEMO
from pilosa_tpu.util import fanout
from pilosa_tpu.util.heat import HeatRecorder

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "pilosa_tpu"


def _closed(srv, read):
    try:
        return read(srv)
    finally:
        srv.server_close()


def _async(read):
    return lambda: _closed(AsyncHTTPServer("localhost", 0), read)


def _engine(read):
    def build():
        holder = Holder()
        holder.open()
        eng = MeshEngine(holder, make_mesh(8))
        try:
            return read(eng)
        finally:
            eng.close()

    return build


# Values fixed when their module is imported: read in a fresh
# interpreter with all three names set (``fresh_import``).
_AT_IMPORT = """
import json
from pilosa_tpu.parallel.batcher import CountBatcher
from pilosa_tpu.util import plans
print(json.dumps({
    "PILOSA_BATCH_WINDOW": CountBatcher.ACCUM_WINDOW,
    "PILOSA_BATCH_POLL": CountBatcher.QUIET_MAX,
    "PILOSA_PLANS": plans.ENABLED,
}))
"""


@pytest.fixture(scope="module")
def fresh_import():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PILOSA_BATCH_WINDOW="0.5",
        PILOSA_BATCH_POLL="0.05",
        PILOSA_PLANS="0",
    )
    out = subprocess.run(
        [sys.executable, "-c", _AT_IMPORT],
        env=env, cwd=str(PACKAGE.parent), capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.splitlines()[-1])


# (name, a non-default value, how to build the object and read the
# setting, the default it must still have).  ``None`` as the builder
# means the value is read from ``fresh_import``.
RETIRED = [
    # -- ad hoc names no config ever knew ---------------------------------
    ("PILOSA_SPARSE", "0", _engine(lambda e: e.sparse_enabled), True),
    ("PILOSA_SPARSE_PALLAS", "0", _engine(lambda e: e._sparse_pallas), True),
    ("PILOSA_SPARSE_THRESHOLD", "0.9",
     _engine(lambda e: e.sparse_threshold), 0.25),
    ("PILOSA_TOPN_DEVICE", "0", _engine(lambda e: e.topn_device_trim), True),
    ("PILOSA_TOPN_SLAB", "0", _engine(lambda e: e.topn_slab_enabled), True),
    ("PILOSA_RESULT_MEMO", "0",
     _engine(lambda e: e.result_memo.maxsize), DEFAULT_RESULT_MEMO),
    ("PILOSA_BATCH_WINDOW", "0.5", None, 0.15),
    ("PILOSA_BATCH_POLL", "0.05", None, 0.005),
    ("PILOSA_PIPELINE_DEPTH", "9",
     _engine(lambda e: CountBatcher(e).max_inflight),
     CountBatcher.DEFAULT_INFLIGHT),
    ("PILOSA_HEAT", "0", lambda: HeatRecorder().enabled, True),
    ("PILOSA_PLANS", "0", None, True),
    ("PILOSA_NATIVE_MERGE", "0",
     lambda: native.load_merge()
     is native._load("sparse_merge", native._configure_merge), True),
    ("PILOSA_IMPORT_FANOUT", "1",
     lambda: fanout.fanout_width(64),
     min(fanout.DEFAULT_IMPORT_FANOUT, os.cpu_count() or 1)),
    # -- second readers of what config.py owns and Server passes down -----
    ("PILOSA_TPU_SERVER_BACKEND", "threaded",
     lambda: _closed(bind_http("localhost", 0), lambda s: type(s)),
     AsyncHTTPServer),
    ("PILOSA_TPU_SERVER_WORKERS", "2",
     lambda: _closed(bind_http("localhost", 0), lambda s: type(s)),
     AsyncHTTPServer),
    ("PILOSA_TPU_FAIR_START", "0.9",
     lambda: AdmissionController().fair_start, 0.5),
    ("PILOSA_TPU_TENANT_WEIGHTS", "gold=4",
     lambda: AdmissionController().weights, {}),
    ("PILOSA_TPU_MAX_INFLIGHT", "7",
     lambda: AdmissionController().max_inflight, 1024),
    ("PILOSA_TPU_SERVER_REACTORS", "3", _async(lambda s: s.n_reactors), 1),
    ("PILOSA_TPU_SERVER_POOL_WORKERS", "5",
     _async(lambda s: s.pool.max_workers), 256),
    ("PILOSA_TPU_SUBMIT_QUEUE", "9",
     _async(lambda s: s.pool._q.maxsize), 1024),
    ("PILOSA_TPU_MAX_BODY_BYTES", "1024",
     _async(lambda s: s.max_body_bytes), 256 * 1024 * 1024),
    ("PILOSA_TPU_READ_TIMEOUT", "7", _async(lambda s: s.read_timeout), 120.0),
    ("PILOSA_TPU_IDLE_TIMEOUT", "7", _async(lambda s: s.idle_timeout), 120.0),
    # -- in no config and no document -------------------------------------
    ("PILOSA_TPU_RESPONSE_TIMEOUT", "7",
     _async(lambda s: s.response_timeout), 330.0),
    ("PILOSA_TPU_STATS_TIMEOUT", "9",
     lambda: _closed(
         ProcessHTTPServer("localhost", 0),
         lambda s: vars(s).get("_stats_timeout", procserver.STATS_TIMEOUT),
     ), 2.0),
]


@pytest.mark.parametrize(
    "name,value,build,default", RETIRED, ids=[r[0] for r in RETIRED]
)
def test_retired_environment_is_ignored(
    name, value, build, default, monkeypatch, request
):
    monkeypatch.setenv(name, value)
    if name == "PILOSA_SPARSE_PALLAS":
        # The Pallas form is chosen from the backend alone; on this CPU
        # that is False whatever the variable says, so stand in a TPU.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if build is None:
        got = request.getfixturevalue("fresh_import")[name]
    else:
        got = build()
    assert got == default


# Where the package may touch the process environment, and how often:
# config.load_env; compile_cache's JAX_COMPILATION_CACHE_DIR (a path, a
# deployment setting); process mode's parent-to-child hand-over
# (procserver builds the child's environment, worker reads its spec).
ENVIRONMENT_READERS = {
    "config.py": 1,
    "compile_cache.py": 1,
    "net/procserver.py": 1,
    "net/worker.py": 1,
}


def _environment_uses(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in (
            "environ", "environb", "getenv", "getenvb", "putenv",
        ):
            yield node.lineno
        elif isinstance(node, ast.ImportFrom) and node.module == "os":
            for alias in node.names:
                if alias.name in ("environ", "environb", "getenv", "putenv"):
                    yield node.lineno


def test_environment_is_read_only_by_config():
    found = {}
    for path in sorted(PACKAGE.rglob("*.py")):
        lines = list(_environment_uses(ast.parse(path.read_text())))
        if lines:
            found[path.relative_to(PACKAGE).as_posix()] = lines
    assert {k: len(v) for k, v in found.items()} == ENVIRONMENT_READERS, found
