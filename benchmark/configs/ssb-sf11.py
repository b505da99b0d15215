"""ssb-sf11: the generator of one shard of lineorder and the joint table
that is the cell's plain reference: per (order day, discount, quantity)
the row count and the summed extended price.  It never sees a bitmap or
any code of pilosa_tpu."""

import datetime

import numpy as np

from lib.served import SHARD_WIDTH, pack_planes, pack_rows, roaring

DAYS, DISCOUNTS, QUANTITIES = 2556, 11, 51


def _calendar():
    first = datetime.date(1992, 1, 1)
    days = [first + datetime.timedelta(d) for d in range(DAYS)]
    year = np.array([d.year - 1992 for d in days], np.int32)
    month = np.array([(d.year - 1992) * 12 + d.month - 1 for d in days], np.int32)
    week = np.array([(d.timetuple().tm_yday - 1) // 7 for d in days], np.int32)
    return year, month, week


YEAR, MONTH, WEEK = _calendar()


def make_shard(seed: int, shard: int, cfg: dict):
    rng = np.random.default_rng([seed, shard])
    n = SHARD_WIDTH
    day = rng.integers(0, DAYS, n, dtype=np.int32)
    discount = rng.integers(0, DISCOUNTS, n, dtype=np.int32)
    quantity = rng.integers(1, QUANTITIES, n, dtype=np.int32)
    price = quantity * rng.integers(90000, 209900, n, dtype=np.int32)
    imports = [
        ("d_year", "", roaring(pack_rows(YEAR[day], 7))),
        ("d_yearmonthnum", "", roaring(pack_rows(MONTH[day], 84))),
        ("d_weeknuminyear", "", roaring(pack_rows(WEEK[day], 53))),
    ]
    for field, values, depth in (("lo_discount", discount, 4), ("lo_quantity", quantity, 6),
                                 ("lo_extendedprice", price, 24)):
        imports.append((field, f"?view=bsig_{field}", roaring(pack_planes(values, depth))))
    flat = (day * DISCOUNTS + discount) * QUANTITIES + quantity
    return imports, (flat, price)


class Table:
    """count and summed price per (day, discount, quantity), prefix-summed
    over days and quantities so that a date range x quantity band is four
    corners."""

    SHAPE = (DAYS, DISCOUNTS, QUANTITIES)
    YEAR, MONTH, WEEK = YEAR, MONTH, WEEK  # the calendar the templates select days by

    def __init__(self, cfg: dict):
        size = int(np.prod(self.SHAPE))
        self.n = np.zeros(size, np.int64)
        self.v = np.zeros(size, np.int64)

    def add(self, contribution):
        flat, price = contribution
        self.n += np.bincount(flat, minlength=self.n.size)
        # float64 weights are exact here: a shard's cell sums stay far below 2**53
        self.v += np.bincount(flat, weights=price, minlength=self.v.size).astype(np.int64)

    def finish(self):
        def prefix(cells):
            p = np.zeros((DAYS + 1, DISCOUNTS, QUANTITIES + 1), np.int64)
            p[1:, :, 1:] = cells.reshape(self.SHAPE).cumsum(axis=0).cumsum(axis=2)
            return p

        self.N, self.V = prefix(self.n), prefix(self.v)
        del self.n, self.v

    @staticmethod
    def _box(p, days, discount, quantities):
        (d1, d2), (a, b) = days, quantities
        return int(p[d2 + 1, discount, b + 1] - p[d1, discount, b + 1]
                   - p[d2 + 1, discount, a] + p[d1, discount, a])

    def sum(self, days, discount, quantities) -> dict:
        """What Sum(..., field=lo_extendedprice) must answer for rows with
        order day in [d1, d2], this discount, quantity in [a, b]."""
        return {"value": self._box(self.V, days, discount, quantities),
                "count": self._box(self.N, days, discount, quantities)}
