"""GroupBy(Rows(passenger_count), Rows(pickup_year), Rows(dist_miles),
filter=Range(total_amount >< [lo, hi])): taxi query 4 (the rides by
passengers, year and distance) under a fare range: 10 x 7 x 51 = 3,570
combinations over every column.  One filter structure; lo 1-79 and hi
lo + 1..399 (at most 1022) give ~31,000 texts."""

import numpy as np

FIELDS = ("passenger_count", "pickup_year", "dist_miles")


def draw(rng, schema):
    # taxi_segment.draw_filter's amount leaf, draw for draw
    lo = int(rng.integers(1, 80))
    hi = min(lo + int(rng.integers(1, 400)), 1022)
    return [f"GroupBy(Rows(field=passenger_count), Rows(field=pickup_year), "
            f"Rows(field=dist_miles), filter=Range(total_amount >< [{lo}, {hi}]))"], (lo, hi)


def answer(table, key):
    groups = table.by_pc_year_miles(key)  # int64[passengers, year, miles]
    return [[{"group": [{"field": f, "rowID": int(r)} for f, r in zip(FIELDS, combo)],
              "count": int(groups[combo])}
             for combo in zip(*np.nonzero(groups))]]


def planes(key):
    return [{("passenger_count", p) for p in range(10)}
            | {("pickup_year", y) for y in range(7)}
            | {("dist_miles", d) for d in range(51)}
            | {("total_amount", k) for k in range(11)}]
