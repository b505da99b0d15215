"""A stand-in for ``python -m pilosa_tpu server`` with no JAX and no index
in it, for tests/test_process_end.py: it answers what the harness asks up
to the end of ingest (readiness, /debug/vars, schema, imports) and fails
every query with HTTP 500, so a run against it ends in a BenchFailure a
few seconds in.  Usage:

    stand_in_server.py <fault> <token> server -d <dir> -b 127.0.0.1:<port>

``token`` marks the command line of every process of the run, so that a
test finds them in /proc.  Faults:

  plain       none: ends on SIGTERM
  grandchild  starts a process in a session of its own that sleeps
  deaf        ignores SIGTERM, and so does its grandchild
  slow        answers an import after half a second (ingest lasts)
  respawn     starts a process that forks a successor and exits, over and
              over, each in a session of its own: no signal catches it.
              It gives up by itself after RESPAWN_S so a test leaves nothing.
"""

import json
import os
import signal
import subprocess
import sys
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

RESPAWN_S = 12


def respawn_forever(until: float):
    """Fork a successor and exit, until ``until`` (time.time())."""
    while time.time() < until:
        try:
            if os.fork():
                os._exit(0)
            os.setsid()
        except OSError:  # a busy host refused a fork: the same process tries again
            time.sleep(0.001)
    os._exit(0)


def sleeper(deaf: bool):
    if deaf:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
    time.sleep(120)


def serve(fault: str, token: str, port: int):
    devices = int(os.environ.get("XLA_FLAGS", "=1").rpartition("=")[2])
    mesh = {"platform": "cpu", "deviceKind": "cpu", "devices": devices, "perDevice": []}

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def reply(self, status: int, body: bytes):
            self.send_response(status)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/debug/vars":
                self.reply(200, json.dumps({"mesh": mesh}).encode())
            else:  # /readyz, /metrics
                self.reply(200, b"")

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            if "/import-roaring/" in self.path:
                if fault == "slow":
                    time.sleep(0.5)
                self.reply(200, b"{}")
            elif self.path.endswith("/query") or "/query?" in self.path:
                self.reply(500, b'{"error": "a stand-in answers no query"}')
            else:  # the schema
                self.reply(200, b"{}")

    if fault in ("grandchild", "deaf", "respawn"):
        kind = "respawn" if fault == "respawn" else "deaf" if fault == "deaf" else "sleeper"
        subprocess.Popen([sys.executable, os.path.abspath(__file__), kind, token],
                         start_new_session=True)
    if fault == "deaf":
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
    else:
        signal.signal(signal.SIGTERM, lambda *a: os._exit(0))
    ThreadingHTTPServer(("127.0.0.1", port), Handler).serve_forever()


if __name__ == "__main__":
    fault, token = sys.argv[1], sys.argv[2]
    if fault == "respawn" and len(sys.argv) == 3:
        respawn_forever(time.time() + RESPAWN_S)
    elif fault in ("sleeper", "deaf") and len(sys.argv) == 3:
        sleeper(fault == "deaf")
    else:
        args = sys.argv[3:]
        serve(fault, token, int(args[args.index("-b") + 1].rpartition(":")[2]))
