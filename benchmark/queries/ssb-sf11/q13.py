"""Q1.3: one week of one year, a discount band, a quantity band (SSB: week 6
of 1994, 5-7, 26-35)."""

from ssb_flight1 import answers, calls, day_range, draw_bands, measure_planes


def draw(rng, schema):
    w, y = int(rng.integers(0, 52)), int(rng.integers(0, 7))
    lo, q = draw_bands(rng)
    return calls(f"Row(d_weeknuminyear={w}), Row(d_year={y})", lo, q), (w, y, lo, q)


def answer(table, key):
    w, y, lo, q = key
    return answers(table, day_range((table.WEEK == w) & (table.YEAR == y)), lo, q)


def planes(key):
    return [measure_planes() | {("d_weeknuminyear", key[0]), ("d_year", key[1])}] * 3
