"""The segment filter F the taxi templates share (ISSUE 25): an Intersect
of leaves over cab type, passengers, year, a band of adjacent distance
rows and an amount range, values drawn by row popularity."""

import numpy as np

_P_CACHE = {}


def _popularity(schema, name):
    if name not in _P_CACHE:
        d = schema["distributions"][name]
        _P_CACHE[name] = np.cumsum(d) / np.sum(d)
    return _P_CACHE[name]


def _pick(rng, schema, name) -> int:
    cum = _popularity(schema, name)
    return min(int(np.searchsorted(cum, rng.random())), len(cum) - 1)


KINDS = ("cab", "pc", "year", "band", "amount")


def draw_filter(rng, schema, kinds, band_width):
    """key: a tuple (cab|None, pc|None, year|None, (m1, m2)|None,
    (lo, hi)|None) with exactly the leaves of ``kinds``; row ids by
    popularity, the band ``band_width`` adjacent distance rows."""
    cab = _pick(rng, schema, "cab_type") if "cab" in kinds else None
    pc = _pick(rng, schema, "passenger_count") if "pc" in kinds else None
    year = _pick(rng, schema, "pickup_year") if "year" in kinds else None
    band = amount = None
    if "band" in kinds:
        m1 = min(_pick(rng, schema, "dist_miles"), 51 - band_width)
        band = (m1, m1 + band_width - 1)
    if "amount" in kinds:
        lo = int(rng.integers(1, 80))
        hi = min(lo + int(rng.integers(1, 400)), 1022)
        amount = (lo, hi)
    return (cab, pc, year, band, amount)


def draw_free(rng, schema, allowed, at_least):
    """The issue's segment filter: each leaf of ``allowed`` present or
    absent by a fair draw (drawn again until ``at_least`` are present), the
    band 1 to 8 adjacent rows."""
    while True:
        kinds = [k for k in allowed if rng.random() < 0.5]
        if len(kinds) >= at_least:
            return draw_filter(rng, schema, kinds, int(rng.integers(1, 9)))


def leaves(key) -> list:
    cab, pc, year, band, amount = key
    out = []
    if cab is not None:
        out.append(f"Row(cab_type={cab})")
    if pc is not None:
        out.append(f"Row(passenger_count={pc})")
    if year is not None:
        out.append(f"Row(pickup_year={year})")
    if band is not None:
        rows = [f"Row(dist_miles={d})" for d in range(band[0], band[1] + 1)]
        out.append(rows[0] if len(rows) == 1 else "Union(" + ", ".join(rows) + ")")
    if amount is not None:
        out.append(f"Range(total_amount >< [{amount[0]}, {amount[1]}])")
    return out


def pql(key) -> str:
    ls = leaves(key)
    return ls[0] if len(ls) == 1 else "Intersect(" + ", ".join(ls) + ")"


def select(cube, key):
    """Reduce int64[cab, passengers, year] to the filter's categorical
    leaves, keeping the (passengers, year) axes: int64[10, 7]."""
    cab, pc, year, _, _ = key
    c = cube[cab] if cab is not None else cube.sum(axis=0)
    if pc is not None:
        keep = np.zeros_like(c)
        keep[pc] = c[pc]
        c = keep
    if year is not None:
        keep = np.zeros_like(c)
        keep[:, year] = c[:, year]
        c = keep
    return c


def filter_planes(key) -> set:
    cab, pc, year, band, amount = key
    out = set()
    if cab is not None:
        out.add(("cab_type", cab))
    if pc is not None:
        out.add(("passenger_count", pc))
    if year is not None:
        out.add(("pickup_year", year))
    if band is not None:
        out.update(("dist_miles", d) for d in range(band[0], band[1] + 1))
    if amount is not None:
        out.update(("total_amount", k) for k in range(11))
    return out
