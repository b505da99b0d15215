#!/usr/bin/env python3
"""Who owned the device's idle gaps: the operator's reading of a trace
taken through ``POST /debug/pprof/trace`` (docs/observability.md).

    JAX_PLATFORMS=cpu python scripts/trace_gaps.py <traceDir> [--json] [--allow-host]

Prints the device's busy share (the union of the ``XLA Ops`` intervals
over first operation -> last operation, per device plane) and, for each
of the ten longest gaps between operations, the ``pilosa.<stage>`` host
annotations (util/tracing.stage, switched on for the length of a
capture) that cover it: per thread the innermost stage at every moment
of the gap, summed (with the number of annotations that add up to it:
one long one is a stall, many short ones are the work), the largest
first, and the share of the gap in which no thread was inside any stage.
A gap that no stage covers is reported with the host threads that did
anything at all in it: that thread's work is the next span to add.

Run it where no server holds the chip (it only reads the file, but it
imports jax for the reader): ``JAX_PLATFORMS=cpu``.  ``--allow-host``
reads the host plane's XLA executions in the device's place (a trace
taken on a CPU server)."""

import glob
import json
import os
import sys

OPS_LINE = "XLA Ops"
TOP = 10


def load(trace_dir: str, allow_host: bool):
    """(device op intervals per device plane, host stage events per thread,
    all host events per thread), times in ns."""
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
                        stages.setdefault(line.name, []).append(iv + (ev.name[7:],))
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
    cut = sorted({a, b} | {t for s, e, _ in events for t in (s, e) if a < t < b})
    out = {}
    for lo, hi in zip(cut, cut[1:]):
        holders = [ev for ev in events if ev[0] <= lo and ev[1] >= hi]
        if holders:
            ev = max(holders, key=lambda ev: ev[0])
            ns, seen = out.setdefault(ev[2], (0, set()))
            seen.add(ev[:2])
            out[ev[2]] = (ns + hi - lo, seen)
    return {name: (ns, len(seen)) for name, (ns, seen) in out.items()}


def owners(stages: dict, others: dict, a: int, b: int) -> dict:
    """The stages covering gap [a, b], largest first; else the threads
    that did anything in it."""
    found, held = [], []
    for thread, events in stages.items():
        near = [ev for ev in events if ev[1] > a and ev[0] < b]
        held.extend((max(s, a), min(e, b)) for s, e, _ in near)
        for stage, (ns, n) in innermost(near, a, b).items():
            found.append({"thread": thread, "stage": stage, "annotations": n,
                          "covers": ns / (b - a), "seconds": ns / 1e9})
    found.sort(key=lambda o: -o["seconds"])
    if found:
        covered = busy_and_gaps(sorted(held))[0]
        return {"owners": found[:5], "uncovered": 1.0 - covered / (b - a)}
    active = sorted(t for t, evs in others.items() if any(e > a and s < b for s, e in evs))
    return {"owners": [], "no_span_on": active}


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
            who += f"; no thread in any stage {100 * gap['uncovered']:.0f} %"
        else:
            who = f"no pilosa.* span; host threads active: {gap['no_span_on'] or 'none'}"
        print(f"gap {i:2d}: {gap['seconds'] * 1e3:9.3f} ms  {who}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
