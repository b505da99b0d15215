#!/usr/bin/env python3
"""Reduce a jax.profiler trace directory to the numbers the benchmark
reads: device busy seconds (the union of the intervals in which an
operation ran, averaged over the device planes), the span from the first
operation's start to the last one's end, the summed run time of the
programs (XLA modules), the seconds of cross-chip collectives among the
operations, the device operations by total time, the longest idle gaps,
and each program's executions.  Runs as a short-lived child with
JAX_PLATFORMS=cpu after the server has exited; prints one JSON line.

An empty device plane is an error: there is no fallback to a host clock.
``--allow-host`` (the CPU rehearsal only) reads the host plane's XLA
executions in the device's place so that the rehearsal reaches the end."""

import glob
import json
import os
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# HLO's cross-chip operations, by the start of an op's own name, which the
# TPU's trace writes as "%all-reduce" (an asynchronous one is a "-start"
# and a "-done" event: both count)
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter", "all-to-all")


def union_seconds(intervals: list) -> tuple:
    """(busy seconds, [(gap seconds, name of the event before the gap)])."""
    busy, gaps, end, last = 0.0, [], None, None
    for start, stop, name in sorted(intervals):
        if end is None or start > end:
            if end is not None:
                gaps.append(((start - end) / 1e9, last))
            busy += (stop - start) / 1e9
            end, last = stop, name
        elif stop > end:
            busy += (stop - end) / 1e9
            end, last = stop, name
    return busy, gaps


def reduce(trace_dir: str, allow_host: bool) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(sorted(paths)[-1])
    planes, seen = [], {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            if line.name == "python":  # millions of host events nobody reads
                continue
            lines.setdefault(line.name, []).extend(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 ev.name.split(" = ")[0][:64])  # an op's own name, not its whole HLO text
                for ev in line.events)
        seen[plane.name] = {k: len(v) for k, v in lines.items()}
        if plane.name.startswith("/device:TPU:"):
            planes.append(lines)
        elif allow_host and plane.name.startswith("/host:"):
            host = [iv for name, evs in lines.items() for iv in evs
                    if name.startswith("tf_XLAPjRtCpuClient")]
            planes.append({OPS_LINE: host, MODULES_LINE: host})
    busy, spans, totals, gaps, modules, collective_s = [], [], {}, [], {}, 0.0
    for lines in planes:
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if not ops:
            continue
        b, g = union_seconds(ops)
        busy.append(b)
        spans.append((max(e for _, e, _ in ops) - min(s for s, _, _ in ops)) / 1e9)
        gaps.extend(g)
        for s, e, name in ops:
            totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
            if name.lstrip("%").startswith(COLLECTIVES) and OPS_LINE in lines:
                collective_s += (e - s) / 1e9
        for s, e, name in lines.get(MODULES_LINE, []):
            modules.setdefault(name.split("(")[0], []).append((e - s) / 1e9)
    if not busy or sum(busy) <= 0:
        raise SystemExit(f"empty device plane in {paths[-1]}: planes and lines {seen}")
    n = len(busy)
    programs = sorted(((f"program {k}, {len(v)} runs", sum(v)) for k, v in modules.items()),
                      key=lambda kv: -kv[1])[:3]
    top = programs + sorted(totals.items(), key=lambda kv: -kv[1])[:10 - len(programs)]
    return {
        "busy_s": sum(busy) / n,
        "span_s": max(spans),
        "device_planes": n,
        "program_s": (sum(sum(v) for v in modules.values()) or sum(busy)) / n,
        "modules": {k: {"n": len(v), "seconds": sum(v)} for k, v in modules.items()},
        "collective_s": collective_s / n,
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top],  # the three longest programs first
            "idle_gaps": [[f"unattributed, after {name}", g]
                          for g, name in sorted(gaps, key=lambda t: -t[0])[:10]],
        },
        "seen": seen,
    }


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1], "--allow-host" in sys.argv[2:])))
