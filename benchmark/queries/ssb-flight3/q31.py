"""Q3.1: revenue by customer nation, supplier nation and year under a
region pair (SSB: ASIA, ASIA): 5 x 5 x 6 = 150 combinations, 50 planes."""

import numpy as np

from ssb_flight3 import YEARS, call, draw_band, groups, measure_planes


def draw(rng, schema):
    r1, r2 = int(rng.integers(0, 5)), int(rng.integers(0, 5))
    band = draw_band(rng)
    leaves = f"Row(c_region={r1 + 1}), Row(s_region={r2 + 1})"
    return [call("nation", 5 * r1, 5 * r2, 5, leaves, band)], (r1, r2, band)


def answer(table, key):
    r1, r2, band = key
    n = np.zeros((5, 5 * YEARS), np.int64)
    v = np.zeros((5, 5 * YEARS), np.int64)
    for i in range(5):  # a customer nation's five buckets of the supplier region lie together
        b = (5 * r1 + i) * 25 + 5 * r2
        _, s_city, months, quantity, revenue = table.rows(b, b + 5)
        year = months // 12
        keep = (quantity >= band[0]) & (quantity <= band[1]) & (year < YEARS)
        cell = (s_city // 10 - 5 * r2).astype(np.int64) * YEARS + year
        n[i], v[i] = table.grouped(cell, keep, revenue, 5 * YEARS)
    return groups("nation", 5 * r1 + 1, 5 * r2 + 1, 5, n, v)


def planes(key):
    r1, r2, _ = key
    return [measure_planes() | {("c_region", r1 + 1), ("s_region", r2 + 1)}
            | {("c_nation", 5 * r1 + k + 1) for k in range(5)}
            | {("s_nation", 5 * r2 + k + 1) for k in range(5)}]
