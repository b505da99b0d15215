"""chip_smoke.py cannot rot: its CPU rehearsal runs in tier-1, and so
do the ways it must refuse to pass."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_ENABLE_COMPILATION_CACHE")}
    return subprocess.run(
        [sys.executable, SMOKE, *args], env={**base, **env}, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )


def test_cpu_rehearsal_passes_and_names_the_cpu(tmp_path):
    """--allow-cpu --shards 8: every query family answers bit-exact from
    the device path, the result names platform cpu and the cut, and the
    compile cache goes where JAX_COMPILATION_CACHE_DIR says."""
    cache = str(tmp_path / "cache")
    proc = _run("--allow-cpu", "--shards", "8", JAX_COMPILATION_CACHE_DIR=cache)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    # Last line: the verdict, exactly the keys the chip check reads.
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert len(lines) == 2
    result = json.loads(lines[0])
    assert result["ok"] is True and result["platform"] == "cpu"
    assert result["reduced"] == ["shards 8 < 960"]
    assert result["host_fallbacks"] == 0 and result["psum_dispatches"] > 0
    assert result["native"] == "built"
    assert result["compile_cache_dir"] == cache
    assert all(q["ok"] for q in result["queries"].values())
    assert result["queries"]["count_sparse"]["path"] == ["sparse"]
    assert result["queries"]["write_readback"]["stack_rebuilds"] == 0


def test_refuses_a_cpu_server_without_the_flag():
    proc = _run("--shards", "8", JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "not tpu" in proc.stderr


def test_fails_when_the_server_child_dies():
    """An engine that cannot be built fails Server.open() (no host-loop
    node behind a 200 /readyz), and a dead child fails the smoke."""
    proc = _run("--allow-cpu", "--shards", "8", PILOSA_TPU_MESH_DEVICES="64")
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "server child exited" in proc.stderr
    assert "requested 64 devices" in proc.stderr


def test_fails_on_a_host_fallback():
    """A correct answer served from the host tier is still a failure:
    a budget too small for one stack makes the first Count fall back."""
    proc = _run("--allow-cpu", "--shards", "8",
                PILOSA_TPU_ENGINE_DEVICE_BUDGET_BYTES="400000")
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "host_fallback" in proc.stderr


def test_fails_alone_in_a_directory(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(SMOKE, "rb").read())
    proc = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""
