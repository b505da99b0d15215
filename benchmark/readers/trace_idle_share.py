"""Share of the traced part of the window in which no operation ran on
the device, in %: 1 - (union of the device-op intervals) / (first
operation's start to the last one's end), averaged over the chips used.
The traced part runs between two pauses with nothing in flight, so the
span is that part and holds no profiler start-up or wind-down."""


def read(ctx, params):
    t = ctx.get("trace")
    if not t or t["span_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["span_s"])
