"""Mean of a Prometheus histogram over the window, in ms: the rise of
its _sum over the rise of its _count, summed over the label sets given.
params: {"series": name, "labels": ['{stage="queue_wait"}', ...]}."""


def read(ctx, params):
    total = n = 0.0
    for labels in params.get("labels", [""]):
        s, c = f"{params['series']}_sum{labels}", f"{params['series']}_count{labels}"
        if c in ctx["m1"]:
            n += ctx["m1"][c] - ctx["m0"].get(c, 0.0)
            total += ctx["m1"][s] - ctx["m0"].get(s, 0.0)
    if n <= 0:
        return None
    return total / n * 1e3
