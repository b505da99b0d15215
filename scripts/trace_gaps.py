#!/usr/bin/env python3
"""Who owned the device's idle gaps: the operator's reading of a trace
taken through ``POST /debug/pprof/trace`` (docs/observability.md).

    JAX_PLATFORMS=cpu python scripts/trace_gaps.py <traceDir> [--json] [--allow-host]

Prints the device's busy share (the union of the ``XLA Ops`` intervals
over first operation -> last operation, per device plane) and, for each
of the ten longest gaps between operations, the ``pilosa.<stage>`` host
annotations (util/tracing.stage and util/tracing.mark, switched on for
the length of a capture) that cover it: per thread the innermost stage
at every moment of the gap, summed (with the number of annotations that
add up to it: one long one is a stall, many short ones are the work),
the largest first, and the shares of the gap that were the client's and
that nothing owned.

Then the totals over **all** gaps of the trace.  Every moment of the idle
time has one owner: the thread that is inside a working stage (where
several are, the one whose innermost annotation began last); else a
thread asleep in a named wait (``accum_wait``; ``handoff:wait`` and
``respond_wake:wait``, a job's time in the pool's queue and a reply's
until the reactor's turn, which are read off the ``waited_us`` of the
annotation each ends in); else, where the
reactor sleeps in ``select_wait`` holding no query request (``open=0``)
or waiting for a socket to take the rest of a reply (``writing>0``),
the ``client`` (its turnaround and the socket); else nobody: ``unowned``,
with the host threads that did anything in that time (that thread's work
is the next span to add).  Work, waits, client and unowned add up to the
idle time.

Run it where no server holds the chip (it only reads the file, but it
imports jax for the reader): ``JAX_PLATFORMS=cpu``.  ``--allow-host``
reads the host plane's XLA executions in the device's place (a trace
taken on a CPU server)."""

import glob
import json
import os
import sys
from bisect import bisect_left

OPS_LINE = "XLA Ops"
TOP = 10
# Annotations inside which a thread sleeps; every other one is work.
# select_wait is the reactor's sleep: the client's where the server
# holds no query request, and owns nothing where it holds one.
WAITS = {"accum_wait", "select_wait", "collect_wait", "queue_wait"}
SELECT = "select_wait"


def load(trace_dir: str, allow_host: bool):
    """(device op intervals per device plane, host stage events per thread,
    all host events per thread), times in ns.  A stage event is (start,
    end, stage); a ``select_wait`` that held no request, or waited for a
    socket to take the rest of a reply, is named ``select_wait:0``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(sorted(paths)[-1])
    devices, stages, others = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE for ev in line.events]
            if ops:
                devices.append(sorted(ops))
        elif plane.name.startswith("/host:"):
            host_ops = []
            for line in plane.lines:
                for ev in line.events:
                    iv = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    if ev.name.startswith("pilosa."):
                        name, stats = ev.name[7:], dict(ev.stats)
                        if name == SELECT and (str(stats.get("open")) == "0"
                                               or str(stats.get("writing", "0")) != "0"):
                            name = SELECT + ":0"
                        stages.setdefault(line.name, []).append(iv + (name,))
                        if "waited_us" in stats:
                            # A wait no thread performed, written on the
                            # annotation it ended in (an annotation cannot
                            # be written after the fact).
                            stages[line.name].append(
                                (iv[0] - 1000 * int(stats["waited_us"]), iv[0],
                                 f"{stats.get('waited', name)}:wait"))
                    elif allow_host and line.name.startswith("tf_XLAPjRtCpuClient"):
                        host_ops.append(iv)
                    else:
                        others.setdefault(line.name, []).append(iv)
            if allow_host and host_ops:
                devices.append(sorted(host_ops))
    if not devices:
        raise SystemExit(f"no device operations in {paths[-1]}")
    return devices, stages, others


def busy_and_gaps(ops: list):
    """(busy ns, span ns, [(gap start, gap end)]) of sorted intervals."""
    busy, gaps, end = 0, [], None
    for start, stop in ops:
        if end is None or start > end:
            if end is not None:
                gaps.append((end, start))
            busy += stop - start
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy, end - ops[0][0], gaps


def innermost(events: list, a: int, b: int) -> dict:
    """{stage: (ns, annotations)} of [a, b] covered on one thread, each
    moment given to the innermost annotation (the latest started of those
    that hold it)."""
    out = {}
    for lo, hi, ev in _timeline(events, a, b):
        ns, seen = out.setdefault(ev[2], (0, set()))
        seen.add(ev[:2])
        out[ev[2]] = (ns + hi - lo, seen)
    return {name: (ns, len(seen)) for name, (ns, seen) in out.items()}


def _timeline(events: list, a: int, b: int):
    """[(lo, hi, innermost event)] over the parts of [a, b] that one
    thread's events hold."""
    cut = sorted({a, b} | {t for s, e, _ in events for t in (s, e) if a < t < b})
    for lo, hi in zip(cut, cut[1:]):
        holders = [ev for ev in events if ev[0] <= lo and ev[1] >= hi]
        if holders:
            yield lo, hi, max(holders, key=lambda ev: ev[0])


def _is_wait(stage: str) -> bool:
    return stage.endswith(":wait") or stage.split(":")[0] in WAITS


def partition(stages: dict, a: int, b: int) -> dict:
    """Every moment of [a, b] to one owner: {(kind, thread, stage): ns},
    kind one of work, wait, client, unowned (thread and stage empty for
    the last two), and under "unowned_at" the unowned intervals."""
    lines = {}
    for thread, events in stages.items():
        near = [ev for ev in events if ev[1] > a and ev[0] < b]
        if near:
            lines[thread] = list(_timeline(near, a, b))
    cut = sorted({a, b} | {t for tl in lines.values() for lo, hi, _ in tl for t in (lo, hi)})
    out, unowned_at = {}, []
    for lo, hi in zip(cut, cut[1:]):
        inside = [(thread, ev) for thread, tl in lines.items()
                  for s, e, ev in tl if s <= lo and e >= hi]
        work = [te for te in inside if not _is_wait(te[1][2])]
        naps = [te for te in inside if _is_wait(te[1][2]) and not te[1][2].startswith(SELECT)]
        if work or naps:
            thread, ev = max(work or naps, key=lambda te: te[1][0])
            key = ("work" if work else "wait", thread, ev[2])
        elif any(ev[2] == SELECT + ":0" for _, ev in inside):
            key = ("client", "", "")
        else:
            key = ("unowned", "", "")
            unowned_at.append((lo, hi))
        out[key] = out.get(key, 0) + hi - lo
    out["unowned_at"] = unowned_at
    return out


def owners(stages: dict, others: dict, a: int, b: int) -> dict:
    """The stages covering gap [a, b], largest first, with the shares of
    the gap that were the client's and nobody's; with no stage at all,
    the threads that did anything in it."""
    found = []
    for thread, events in stages.items():
        near = [ev for ev in events if ev[1] > a and ev[0] < b]
        for stage, (ns, n) in innermost(near, a, b).items():
            found.append({"thread": thread, "stage": stage, "annotations": n,
                          "wait": _is_wait(stage),
                          "covers": ns / (b - a), "seconds": ns / 1e9})
    found.sort(key=lambda o: (o["wait"], -o["seconds"]))
    if found:
        parts = partition(stages, a, b)
        return {"owners": found[:6],
                "client": parts.get(("client", "", ""), 0) / (b - a),
                "uncovered": parts.get(("unowned", "", ""), 0) / (b - a)}
    return {"owners": [], "no_span_on": _active(others, [(a, b)])}


def _active(others: dict, intervals: list) -> list:
    """The host threads with any event inside any of ``intervals``."""
    out = set()
    for thread, evs in others.items():
        evs = sorted(evs)
        starts = [s for s, _ in evs]
        for a, b in intervals:
            # An event that starts inside, or the last one that began before.
            i = bisect_left(starts, a)
            if (i < len(evs) and evs[i][0] < b) or (i and evs[i - 1][1] > a):
                out.add(thread)
                break
    return sorted(out)


def totals(stages: dict, others: dict, gaps: list) -> dict:
    """The owners of all the idle time: seconds and share of each
    (thread, stage) at work, of each named wait, of the client, and of
    nobody."""
    idle = sum(b - a for a, b in gaps)
    summed, unowned_at = {}, []
    for a, b in gaps:
        parts = partition(stages, a, b)
        unowned_at.extend(parts.pop("unowned_at"))
        for key, ns in parts.items():
            summed[key] = summed.get(key, 0) + ns

    def rows(kind):
        return sorted(({"thread": t, "stage": s, "seconds": ns / 1e9, "share": ns / idle}
                       for (k, t, s), ns in summed.items() if k == kind),
                      key=lambda r: -r["seconds"])

    def one(kind):
        ns = summed.get((kind, "", ""), 0)
        return {"seconds": ns / 1e9, "share": ns / idle if idle else 0.0}

    return {"idle_s": idle / 1e9, "gaps": len(gaps), "work": rows("work"),
            "waits": rows("wait"), "client": one("client"),
            "unowned": dict(one("unowned"), no_span_on=_active(others, unowned_at))}


def stage_means(stages: dict) -> list:
    """Count and mean of every (thread, stage) annotation of the whole
    trace, whatever the device did meanwhile."""
    out = {}
    for thread, events in stages.items():
        for s, e, stage in events:
            n, ns = out.get((thread, stage), (0, 0))
            out[(thread, stage)] = (n + 1, ns + e - s)
    return [{"thread": t, "stage": s, "annotations": n, "mean_ms": ns / n / 1e6}
            for (t, s), (n, ns) in sorted(out.items())]


def report(trace_dir: str, allow_host: bool = False) -> dict:
    devices, stages, others = load(trace_dir, allow_host)
    planes, gaps = [], []
    for ops in devices:
        busy, span, g = busy_and_gaps(ops)
        planes.append({"busy_s": busy / 1e9, "span_s": span / 1e9,
                       "busy_share": busy / span if span else 0.0})
        gaps.extend(g)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "device_planes": planes,
        "busy_share": sum(p["busy_share"] for p in planes) / len(planes),
        "stage_threads": {t: len(evs) for t, evs in sorted(stages.items())},
        "gaps": [dict(seconds=(b - a) / 1e9, **owners(stages, others, a, b))
                 for a, b in longest],
        "totals": totals(stages, others, gaps),
        "stages": stage_means(stages),
    }


def main(argv) -> int:
    args = [a for a in argv if not a.startswith("--")]
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    doc = report(args[0], "--allow-host" in argv)
    if "--json" in argv:
        print(json.dumps(doc))
        return 0
    print(f"device busy {100 * doc['busy_share']:.1f} % of first op -> last op "
          f"({len(doc['device_planes'])} plane(s)); pilosa.* events on {doc['stage_threads']}")
    for i, gap in enumerate(doc["gaps"], 1):
        if gap["owners"]:
            who = "; ".join(f"{o['thread']} in {o['stage']} {100 * o['covers']:.0f} % "
                            f"({o['annotations']})" for o in gap["owners"])
            who += (f"; client {100 * gap['client']:.0f} %"
                    f"; no thread in any stage {100 * gap['uncovered']:.0f} %")
        else:
            who = f"no pilosa.* span; host threads active: {gap['no_span_on'] or 'none'}"
        print(f"gap {i:2d}: {gap['seconds'] * 1e3:9.3f} ms  {who}")
    tot = doc["totals"]
    print(f"idle {tot['idle_s'] * 1e3:.1f} ms in {tot['gaps']} gaps, owned by:")
    for kind in ("work", "waits"):
        for r in tot[kind]:
            print(f"  {kind[:4]:4s} {r['thread']:>16s} in {r['stage']:<16s} "
                  f"{r['seconds'] * 1e3:9.3f} ms {100 * r['share']:5.1f} %")
    print(f"  client (select_wait, open=0 or writing) "
          f"{tot['client']['seconds'] * 1e3:9.3f} ms {100 * tot['client']['share']:5.1f} %")
    print(f"  unowned                                "
          f"{tot['unowned']['seconds'] * 1e3:9.3f} ms {100 * tot['unowned']['share']:5.1f} %"
          f"  host threads active in it: {tot['unowned']['no_span_on'] or 'none'}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
