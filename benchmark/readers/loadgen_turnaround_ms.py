"""Mean gap between a reply's last byte and the same connection's next
send, on the harness's clock, in ms: what the load generator itself adds
to every closed-loop cycle."""


def read(ctx, params):
    gaps = ctx["turnaround_s"]
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e3
