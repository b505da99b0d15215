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


# -- every moment of the idle time to one owner ------------------------------

REACTOR, DRAIN, COLLECT = "http-reactor-0", "pq-drain", "pq-collect-1"


@pytest.mark.parametrize("stages, want", [
    # Work beats a wait beats the reactor's sleep; the rest is nobody's.
    ({REACTOR: [(100, 200, "select_wait:0")], DRAIN: [(120, 160, "accum_wait")],
      COLLECT: [(140, 150, "complete")]},
     {("client", "", ""): 60, ("wait", DRAIN, "accum_wait"): 30,
      ("work", COLLECT, "complete"): 10}),
    # A reactor asleep while the server holds a request owns nothing.
    ({REACTOR: [(100, 150, "select_wait"), (150, 200, "select_wait:0")]},
     {("unowned", "", ""): 50, ("client", "", ""): 50}),
    # Two threads at work: the moment is his whose innermost annotation began last.
    ({COLLECT: [(100, 200, "complete"), (120, 140, "encode")],
      REACTOR: [(130, 180, "read")]},
     {("work", COLLECT, "complete"): 40, ("work", COLLECT, "encode"): 10,
      ("work", REACTOR, "read"): 50}),
    # A wait read off the annotation it ended in is a wait like a sleep.
    ({"http-pool": [(100, 130, "handoff:wait"), (130, 140, "handoff")],
      REACTOR: [(100, 200, "select_wait")]},
     {("wait", "http-pool", "handoff:wait"): 30, ("work", "http-pool", "handoff"): 10,
      ("unowned", "", ""): 60}),
    # A collector pass is the innermost stage of what it interrupts.
    ({REACTOR: [(100, 200, "parse"), (110, 190, "gc")]},
     {("work", REACTOR, "parse"): 20, ("work", REACTOR, "gc"): 80}),
])
def test_partition_gives_every_moment_one_owner(stages, want):
    parts = trace_gaps.partition(stages, 100, 200)
    unowned_at = parts.pop("unowned_at")
    assert parts == want
    assert sum(parts.values()) == 100
    assert sum(b - a for a, b in unowned_at) == want.get(("unowned", "", ""), 0)


def test_totals_add_up_to_the_idle_time_and_name_who_was_active_unowned():
    stages = {REACTOR: [(0, 40, "select_wait:0"), (60, 90, "write")],
              DRAIN: [(100, 130, "accum_wait")]}
    others = {"pq-dispatch": [(42, 45)], "idle": [(500, 600)]}
    tot = trace_gaps.totals(stages, others, [(0, 100), (100, 150)])
    assert tot["idle_s"] == pytest.approx(150e-9) and tot["gaps"] == 2
    assert [(r["thread"], r["stage"], round(r["share"], 4)) for r in tot["work"]] == [
        (REACTOR, "write", 0.2)]
    assert [(r["thread"], r["stage"]) for r in tot["waits"]] == [(DRAIN, "accum_wait")]
    assert tot["client"]["share"] == pytest.approx(40 / 150)
    assert tot["unowned"]["share"] == pytest.approx(50 / 150)
    assert tot["unowned"]["no_span_on"] == ["pq-dispatch"]
    owned = (sum(r["seconds"] for r in tot["work"] + tot["waits"])
             + tot["client"]["seconds"] + tot["unowned"]["seconds"])
    assert owned == pytest.approx(tot["idle_s"])


def test_a_gap_reports_the_clients_share_and_lists_waits_after_work():
    stages = {REACTOR: [(100, 150, "select_wait:0"), (150, 175, "read")]}
    doc = trace_gaps.owners(stages, {}, 100, 200)
    assert [(o["stage"], o["wait"]) for o in doc["owners"]] == [
        ("read", False), ("select_wait:0", True)]
    assert doc["client"] == pytest.approx(0.5) and doc["uncovered"] == pytest.approx(0.25)


def test_stage_means_count_every_annotation_of_the_trace():
    stages = {REACTOR: [(0, 10, "handoff"), (20, 40, "handoff")], "http-pool": [(5, 6, "handoff")]}
    assert trace_gaps.stage_means(stages) == [  # sorted by thread, then stage
        {"thread": "http-pool", "stage": "handoff", "annotations": 1, "mean_ms": 1e-6},
        {"thread": REACTOR, "stage": "handoff", "annotations": 2, "mean_ms": 15e-6}]
