"""Q1.1: one year, a discount band, a quantity band (SSB: 1993, 1-3, < 25)."""

from ssb_flight1 import answers, calls, day_range, draw_bands, measure_planes


def draw(rng, schema):
    y = int(rng.integers(0, 7))
    lo, q = draw_bands(rng)
    return calls(f"Row(d_year={y})", lo, q), (y, lo, q)


def answer(table, key):
    y, lo, q = key
    return answers(table, day_range(table.YEAR == y), lo, q)


def planes(key):
    return [measure_planes() | {("d_year", key[0])}] * 3
