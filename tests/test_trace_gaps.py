"""scripts/trace_gaps.py: the arithmetic that gives an idle gap its
owners (the trace reading itself is exercised on the chip; PERF.md)."""

import importlib.util
import os

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "trace_gaps",
    os.path.join(os.path.dirname(__file__), "..", "scripts", "trace_gaps.py"),
)
trace_gaps = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(trace_gaps)


def test_busy_is_the_union_and_gaps_lie_between():
    busy, span, gaps = trace_gaps.busy_and_gaps([(0, 10), (5, 12), (20, 30), (30, 31)])
    assert (busy, span) == (12 + 11, 31)
    assert gaps == [(12, 20)]


def test_innermost_gives_each_moment_to_the_latest_started_annotation():
    # lower_dispatch [0, 100] holds lower [10, 40] and dispatch [40, 90].
    events = [(0, 100, "lower_dispatch"), (10, 40, "lower"), (40, 90, "dispatch")]
    assert trace_gaps.innermost(events, 0, 100) == {
        "lower_dispatch": (20, 1), "lower": (30, 1), "dispatch": (50, 1)}
    # Clipped to the gap; two annotations of one stage are counted as two.
    events = [(0, 10, "parse"), (20, 30, "parse")]
    assert trace_gaps.innermost(events, 5, 25) == {"parse": (10, 2)}


@pytest.mark.parametrize("stages, want_owner, want_uncovered", [
    ({"pq-dispatch": [(100, 160, "lower")], "http-reactor-0": [(150, 200, "parse")]},
     ("pq-dispatch", "lower"), 0.0),
    ({"pq-dispatch": [(100, 125, "lower")]}, ("pq-dispatch", "lower"), 0.75),
])
def test_owners_rank_by_cover_and_report_what_no_stage_holds(
        stages, want_owner, want_uncovered):
    doc = trace_gaps.owners(stages, {}, 100, 200)
    top = doc["owners"][0]
    assert (top["thread"], top["stage"]) == want_owner
    assert doc["uncovered"] == pytest.approx(want_uncovered)


def test_a_gap_without_any_stage_names_the_threads_that_were_active():
    doc = trace_gaps.owners({}, {"pq-drain": [(110, 120)], "idle": [(0, 50)]}, 100, 200)
    assert doc == {"owners": [], "no_span_on": ["pq-drain"]}
