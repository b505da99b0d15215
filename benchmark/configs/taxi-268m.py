"""taxi-268m: the generator of one shard and the joint table that is the
cell's plain reference.  The table never sees a bitmap or any code of
pilosa_tpu: it is the histogram of the raw columns."""

import numpy as np

from lib.served import SHARD_WIDTH, pack_planes, pack_rows, roaring

DIMS = (2, 10, 7, 51, 1024)  # cab, passengers, year, miles, dollars
FIELDS = ("cab_type", "passenger_count", "pickup_year", "dist_miles")


def make_shard(seed: int, shard: int, cfg: dict):
    """The raw columns of one shard, drawn from (seed, shard): returns
    ([(field, query string, roaring body)], the shard's flat joint index)."""
    rng = np.random.default_rng([seed, shard])
    dist = cfg["distributions"]
    n = SHARD_WIDTH
    cols = [
        rng.choice(DIMS[0], n, p=dist["cab_type"]).astype(np.int32),
        rng.choice(DIMS[1], n, p=dist["passenger_count"]).astype(np.int32),
        rng.choice(DIMS[2], n, p=dist["pickup_year"]).astype(np.int32),
        rng.choice(DIMS[3], n, p=dist["dist_miles"]).astype(np.int32),
    ]
    a = dist["total_amount"]
    amount = a["base"] + a["per_mile"] * cols[3] + rng.exponential(a["noise_scale"], n)
    amount += (rng.random(n) < a["surcharge_share"]) * rng.exponential(a["surcharge_scale"], n)
    amount = np.clip(np.rint(amount), 0, DIMS[4] - 1).astype(np.int32)
    imports = [
        (f, "", roaring(pack_rows(c, k))) for f, c, k in zip(FIELDS, cols, DIMS)
    ]
    imports.append(("total_amount", "?view=bsig_total_amount",
                    roaring(pack_planes(amount, 10))))
    flat = np.ravel_multi_index((*cols, amount), DIMS)
    return imports, flat


class Table:
    """count[cab, passengers, year, miles, dollars] over every shard added,
    then prefix-summed over miles and dollars so that a band x range is
    four corners."""

    def __init__(self, cfg: dict):
        self.cells = np.zeros(int(np.prod(DIMS)), np.int64)

    def add(self, flat):
        self.cells += np.bincount(flat, minlength=self.cells.size)

    def finish(self):
        c = self.cells.reshape(DIMS)
        self.rides = int(c.sum())
        dollars = np.arange(DIMS[4], dtype=np.int64)
        self.P = np.zeros((*DIMS[:3], DIMS[3] + 1, DIMS[4] + 1), np.int64)
        self.P[..., 1:, 1:] = c.cumsum(axis=3).cumsum(axis=4)
        self.W = np.zeros((*DIMS[:3], DIMS[3] + 1), np.int64)
        self.W[..., 1:] = (c * dollars).sum(axis=4).cumsum(axis=3)
        del self.cells

    def counts(self, miles=None, amount=None):
        """int64[cab, passengers, year]: rides with miles in [m1, m2] and
        dollars in [lo, hi] (inclusive; None = all)."""
        m1, m2 = miles if miles else (0, DIMS[3] - 1)
        lo, hi = amount if amount else (0, DIMS[4] - 1)
        P = self.P
        return (P[..., m2 + 1, hi + 1] - P[..., m1, hi + 1]
                - P[..., m2 + 1, lo] + P[..., m1, lo])

    def dollars(self, miles=None):
        """int64[cab, passengers, year]: summed total_amount."""
        m1, m2 = miles if miles else (0, DIMS[3] - 1)
        return self.W[..., m2 + 1] - self.W[..., m1]
