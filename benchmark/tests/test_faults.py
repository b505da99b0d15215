"""The comparison that decides ``correct`` has been shown to fail.

Not collected by ``pytest tests/``: run ``python -m pytest benchmark/tests -q``
(CPU, ~25 s a case).  Each case skips the harness's look for a chip
(``--rehearse``: CPU server, 2 shards) and drives the rest of a run.

* the control: the reference put in the program's place with one
  guarantee of the configuration broken — one acknowledged import is not
  read back — comes out not correct, while the program's own replies in
  the same window are all right;
* a fault planted under the timed path — an answer altered where it is
  produced; half of the shards left out — comes out not correct.

A step that returns its state unchanged and an exchange between chips left
out are not faults these cells can have: neither writes in the window,
and both run on one chip.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = ("taxi.segment_count", "ssb.flight1_stream")


def run_cell(workload, *extra, server=None):
    code = (
        "import sys; sys.path.insert(0, 'benchmark'); import run; "
        f"sys.exit(run.main({['--workload', workload, '--seed', '2147483659', '--seconds', '3', '--trace', '0', '--rehearse', '--shards', '2', *extra]!r}, "
        f"server_argv={server!r}))"
    )
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1]), p.stderr


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result, _ = run_cell(workload)
    assert result["correct"] is True
    assert result["checks"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert result["attempted"] > 50 and result["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    result, err = run_cell(workload, "--control")
    assert result["correct"] is False
    assert result["checks"]["wrong_answers"]["value"] > 0
    assert result["notes"]["program_wrong_answers"] == 0
    assert "check wrong_answers:" in err.splitlines()[-3]


@pytest.mark.parametrize("fault", ("answer", "half"))
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault):
    server = [sys.executable, os.path.join(HERE, "faulty_server.py"), fault, "server"]
    result, _ = run_cell(workload, server=server)
    assert result["correct"] is False
    wrong = result["checks"]["wrong_answers"]["value"] + \
        result["checks"]["device_lane_misses"]["value"]
    assert wrong > 0
