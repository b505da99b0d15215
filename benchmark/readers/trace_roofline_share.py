"""The device programs' share of the HBM roofline, in %: the least time
the chip could have taken to read the row-planes that the traced requests'
calls name, over the time its programs ran.

Bytes: every request of the traced part of the window, every PQL call of
it (a request of three Sums reads the measure's planes three times), each
call's distinct row-planes (the template's ``planes(key)``) x shards x
128 KiB.  Seconds: the summed run time of every program (XLA module) in
the trace, averaged over the chips.  The traced part runs between two
pauses with nothing in flight, so every program run of the trace serves
one of exactly those requests: both sides count the same work, and no
clock is matched with another.

Nothing caps the share.  A program that reads a plane once for several
calls does less than this counts and can read above 100 %: then this
count, not the program, is what a later PR has to correct (PERF.md)."""


def read(ctx, params):
    t = ctx.get("trace")
    if not t or not ctx.get("traced") or not ctx["peaks"] or t["program_s"] <= 0:
        return None
    planes = sum(len(call) for request in ctx["traced"] for call in request)
    least_s = planes * ctx["plane_shard_bytes"] / (ctx["peaks"]["hbm_bytes_per_s"] * ctx["chips"])
    return 100.0 * least_s / t["program_s"]
