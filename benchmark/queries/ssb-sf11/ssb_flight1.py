"""What the three flight-1 templates share.  SUM(lo_extendedprice *
lo_discount) over a date selection, a discount band [lo, lo+2] and a
quantity band [a, b] is one request of three Sum calls, one per discount
value d of the band; revenue = sum of d x value."""

import numpy as np


def day_range(mask) -> tuple:
    days = np.nonzero(mask)[0]
    return int(days[0]), int(days[-1])


def draw_bands(rng):
    lo = int(rng.integers(0, 9))
    a = int(rng.integers(1, 51))
    b = int(rng.integers(a, 51))
    return lo, (a, b)


def calls(date_leaves: str, lo: int, quantities) -> list:
    a, b = quantities
    return [
        (f"Sum(Intersect({date_leaves}, Range(lo_discount == {d}), "
         f"Range(lo_quantity >< [{a}, {b}])), field=lo_extendedprice)")
        for d in range(lo, lo + 3)
    ]


def answers(table, days, lo, quantities) -> list:
    return [table.sum(days, d, quantities) for d in range(lo, lo + 3)]


def measure_planes() -> set:
    return ({("lo_discount", k) for k in range(5)} | {("lo_quantity", k) for k in range(7)}
            | {("lo_extendedprice", k) for k in range(25)})
