"""Rise of the summed numerator series over the rise of the summed
denominator series over the window, times ``scale``: time per request
from a histogram's ``_sum`` and another's ``_count``, the fill of a batch
tier from two counters, a share of the window from a seconds counter and
the uptime gauge.  params: {"numerator": [full sample names, labels
included], "denominator": [...], "scale": 1}.  A series the server never
exported counts as absent; nothing to read (no numerator series at all, or
a denominator that did not rise) reads as nothing, never as 0."""


def read(ctx, params):
    m0, m1 = ctx["m0"], ctx["m1"]

    def rise(names):
        present = [s for s in names if s in m1]
        return sum(m1[s] - m0.get(s, 0.0) for s in present) if present else None

    num, den = rise(params["numerator"]), rise(params["denominator"])
    if num is None or den is None or den <= 0:
        return None
    return num / den * params.get("scale", 1)
