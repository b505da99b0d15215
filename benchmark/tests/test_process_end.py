"""No process outlives a run, however the run ends; and a run that cannot
end one fails and names it.

Not collected by ``pytest tests/``: run ``python -m pytest benchmark/tests -q``.
No JAX here: the server is tests/stand_in_server.py, which answers the
harness up to the end of ingest and fails every query, so each case lasts
seconds.  Every process of a run carries the case's token on its command
line; after the run none of them exists and nothing accepts on its port.
"""

import os
import signal
import socket
import subprocess
import sys
import time
import uuid

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
STAND_IN = os.path.join(HERE, "stand_in_server.py")


# A host with thousands of processes: 20 ms between a look at /proc and the signal
BUSY_HOST = ("import time; look = served.process_table; "
             "served.process_table = lambda: (look(), time.sleep(0.02))[0]; ")


def start_run(fault, token, tmp_path, term_wait=30, kill_wait=60, steer=""):
    code = (
        "import sys; sys.path.insert(0, 'benchmark'); import run; from lib import served; "
        f"served.TERM_WAIT_S, served.KILL_WAIT_S = {term_wait}, {kill_wait}; {steer}"
        "sys.exit(run.main(['--workload', 'ssb.flight1_stream', '--seed', '2147483659', '--seconds', "
        f"'3', '--trace', '0', '--rehearse', '--shards', '4'], "
        f"server_argv={[sys.executable, STAND_IN, fault, token, 'server']!r}))"
    )
    return subprocess.Popen([sys.executable, "-c", code], cwd=ROOT, text=True,
                            env=dict(os.environ, TMPDIR=str(tmp_path)),
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def with_token(token):
    """[(pid, command line)] of every process that carries the token."""
    found = []
    for entry in os.listdir("/proc"):
        if entry.isdigit() and int(entry) != os.getpid():
            try:
                with open(f"/proc/{entry}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            except OSError:
                continue
            if token in cmd:
                found.append((int(entry), cmd))
    return found


def port_of(token, timeout=20):
    """The run's port, from the stand-in's command line, once it is up."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        for _, cmd in with_token(token):
            if " -b 127.0.0.1:" in cmd:
                return int(cmd.split(" -b 127.0.0.1:")[1].split()[0])
        time.sleep(0.05)
    raise AssertionError("the stand-in server never started")


def refuses(port):
    with socket.socket() as s:
        s.settimeout(1)
        return s.connect_ex(("127.0.0.1", port)) != 0


def gone(token, port, within=5.0):
    end = time.monotonic() + within
    while with_token(token) or not refuses(port):
        if time.monotonic() >= end:
            return False
        time.sleep(0.05)
    return True


@pytest.mark.parametrize("fault,term_wait,sigkill", [
    ("plain", 30, "not needed"),
    ("grandchild", 30, "not needed"),  # a session of its own: no killpg of the child reaches it
    ("deaf", 1, "needed"),
])
def test_a_failed_run_ends_every_process(fault, term_wait, sigkill, tmp_path):
    token = uuid.uuid4().hex
    p = start_run(fault, token, tmp_path, term_wait=term_wait)
    port = port_of(token)
    _, err = p.communicate(timeout=120)
    assert p.returncode == 1 and "warm-up: HTTP 500" in err, err[-2000:]
    ended = [ln for ln in err.splitlines() if "end of run:" in ln]
    assert len(ended) == 1 and f"SIGKILL {sigkill}" in ended[0], err[-2000:]
    assert f"{1 if fault == 'plain' else 2} process(es) ended" in ended[0]
    assert gone(token, port, within=0), with_token(token)


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGKILL])
def test_a_run_ended_from_outside_mid_ingest_leaves_nothing(sig, tmp_path):
    token = uuid.uuid4().hex
    p = start_run("slow", token, tmp_path)
    port = port_of(token)
    time.sleep(1.5)  # schema done, the first imports under way
    assert not refuses(port)
    p.send_signal(sig)
    _, err = p.communicate(timeout=60)
    if sig == signal.SIGKILL:  # nobody is left to stop the child: the kernel ends it
        assert p.returncode == -signal.SIGKILL
        assert gone(token, port), with_token(token)
    else:
        assert p.returncode == 1 and f"ended by {sig.name}" in err, err[-2000:]
        assert "end of run: 1 process(es) ended" in err
        assert gone(token, port, within=0), with_token(token)


def test_a_process_that_cannot_be_ended_fails_the_run_and_is_named(tmp_path):
    token = uuid.uuid4().hex
    p = start_run("respawn", token, tmp_path, term_wait=1, kill_wait=2, steer=BUSY_HOST)
    port = port_of(token)
    out, err = p.communicate(timeout=120)
    try:
        assert p.returncode == 1 and not out.strip()  # no result line
        failed = [ln for ln in err.splitlines() if "BenchFailure: the run leaves" in ln]
        assert len(failed) == 1, err[-2000:]
        for word in ("pid ", "ppid ", "sid ", "state ", f"respawn {token}"):
            assert word in failed[0], failed[0]
    finally:  # the respawner gives up by itself (RESPAWN_S): the test leaves nothing either
        assert gone(token, port, within=20), with_token(token)



def test_a_sweep_that_has_ended_is_not_made_again(tmp_path):
    """The run's last ``stop_server`` comes minutes after the first (the
    comparison and the trace's reduction lie between): the port may be
    another process's by then, and that is not the run's leftover."""
    token = uuid.uuid4().hex
    code = (
        "import socket, sys, time; sys.path.insert(0, 'benchmark'); from lib import served; "
        "port = served.free_port(); "
        f"child = served.start_server({[sys.executable, STAND_IN, 'plain', token, 'server']!r}, "
        f"{ROOT!r}, {str(tmp_path)!r}, port, {str(tmp_path / 'server.log')!r}); "
        "served.wait_ready(served.Client(port, child)); served.stop_server(child, port); "
        "other = socket.create_server(('127.0.0.1', port)); t0 = time.monotonic(); "
        "served.stop_server(child, port); print(time.monotonic() - t0)"
    )
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
                       timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stderr.count("end of run: 1 process(es) ended") == 1
    assert float(p.stdout) < 1.0
    assert not with_token(token)
