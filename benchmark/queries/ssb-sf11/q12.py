"""Q1.2: one month, a discount band, a quantity band (SSB: 199401, 4-6, 26-35)."""

from ssb_flight1 import answers, calls, day_range, draw_bands, measure_planes


def draw(rng, schema):
    m = int(rng.integers(0, 84))
    lo, q = draw_bands(rng)
    return calls(f"Row(d_yearmonthnum={m})", lo, q), (m, lo, q)


def answer(table, key):
    m, lo, q = key
    return answers(table, day_range(table.MONTH == m), lo, q)


def planes(key):
    return [measure_planes() | {("d_yearmonthnum", key[0])}] * 3
