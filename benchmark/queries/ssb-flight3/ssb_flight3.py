"""What the four flight-3 templates share.  select c_X, s_X, d_year,
sum(lo_revenue) ... group by c_X, s_X, d_year is one GroupBy call with
aggregate=Sum(field=lo_revenue): three Rows children (the customer
places, the supplier places, the six years 1992-1997) cut to their rows
by previous/limit, under a filter that names the places and a drawn
quantity band.  Place row ids are from 1: nation = 5 x region + k + 1,
city = 10 x (nation - 1) + d + 1; d_year and d_yearmonthnum ids are
from 0."""

import numpy as np

YEARS = 6  # Rows(field=d_year, limit=6): 1992-1997


def draw_band(rng):
    a = int(rng.integers(1, 51))
    return a, int(rng.integers(a, 51))


def draw_nation_pair(rng):
    return int(rng.integers(1, 26)), int(rng.integers(1, 26))


def draw_cities(rng, nation: int):
    """Two distinct cities of the nation, ascending row ids."""
    d1, d2 = sorted(rng.choice(10, 2, replace=False).tolist())
    return 10 * (nation - 1) + d1 + 1, 10 * (nation - 1) + d2 + 1


def call(level: str, prev1: int, prev2: int, width: int, leaves: str, band) -> str:
    return (f"GroupBy(Rows(field=c_{level}, previous={prev1}, limit={width}), "
            f"Rows(field=s_{level}, previous={prev2}, limit={width}), "
            f"Rows(field=d_year, limit={YEARS}), "
            f"filter=Intersect({leaves}, Range(lo_quantity >< [{band[0]}, {band[1]}])), "
            f"aggregate=Sum(field=lo_revenue))")


def city_leaves(cities) -> str:
    (x1, x2), (y1, y2) = cities
    return (f"Union(Row(c_city={x1}), Row(c_city={x2})), "
            f"Union(Row(s_city={y1}), Row(s_city={y2}))")


def city_call(n1, n2, leaves, band) -> str:
    return call("city", 10 * (n1 - 1), 10 * (n2 - 1), 10, leaves, band)


def groups(level: str, first1: int, first2: int, width: int, n, v) -> list:
    """The reply for count and sum tensors [width, width, YEARS] whose
    axes start at row ids first1 / first2 / 0: the groups with a count,
    in row-major order."""
    n = n.reshape(width, width, YEARS)
    v = v.reshape(width, width, YEARS)
    return [[{"group": [{"field": f"c_{level}", "rowID": first1 + int(i)},
                        {"field": f"s_{level}", "rowID": first2 + int(j)},
                        {"field": "d_year", "rowID": int(y)}],
              "count": int(n[i, j, y]), "sum": int(v[i, j, y])}
             for i, j, y in zip(*np.nonzero(n))]]


def city_answer(table, n1, n2, band, cities=None, month=None) -> list:
    """Q3.2-Q3.4 over the one bucket of the nation pair: the rows in the
    quantity band (and, Q3.3, in the two named cities a side; Q3.4, in
    the month), by (customer city, supplier city, year)."""
    b = (n1 - 1) * 25 + (n2 - 1)
    c_city, s_city, months, quantity, revenue = table.rows(b, b + 1)
    year = months // 12
    keep = (quantity >= band[0]) & (quantity <= band[1]) & (year < YEARS)
    if cities is not None:
        (x1, x2), (y1, y2) = cities
        keep &= ((c_city == x1 - 1) | (c_city == x2 - 1)) & ((s_city == y1 - 1) | (s_city == y2 - 1))
    if month is not None:
        keep &= months == month
    cell = ((c_city % 10).astype(np.int64) * 10 + s_city % 10) * YEARS + year
    n, v = table.grouped(cell, keep, revenue, 100 * YEARS)
    return groups("city", 10 * (n1 - 1) + 1, 10 * (n2 - 1) + 1, 10, n, v)


def measure_planes() -> set:
    return ({("lo_quantity", k) for k in range(7)} | {("lo_revenue", k) for k in range(25)}
            | {("d_year", y) for y in range(YEARS)})


def city_planes(n1, n2) -> set:
    return (measure_planes()
            | {("c_city", 10 * (n1 - 1) + d + 1) for d in range(10)}
            | {("s_city", 10 * (n2 - 1) + d + 1) for d in range(10)})
