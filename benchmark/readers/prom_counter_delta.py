"""Rise of one or more Prometheus counters over the window, summed.
params: {"series": [full sample names, labels included]}.  A counter the
server never exported reads as nothing; one that stood still reads 0."""


def read(ctx, params):
    present = [s for s in params["series"] if s in ctx["m1"]]
    if not present:
        return None
    return sum(ctx["m1"][s] - ctx["m0"].get(s, 0.0) for s in present)
