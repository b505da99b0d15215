"""ssb-flight3: the generator of one shard of lineorder with the customer
and supplier places denormalised, and the table that is the cell's plain
reference.  The day, discount, quantity and price of a row are drawn
exactly as ``ssb-sf11.py`` draws them (loaded by path: its calendar and
constants), then, from the same generator, a customer city and a
supplier city, uniform over 250; nation = city // 10, region = nation //
5 (values from 0; a place's row id is its value + 1), revenue = price x
(100 - discount) // 100.

The table never sees a bitmap or any code of pilosa_tpu.  It keeps five
raw columns a row (customer city, supplier city, month, quantity: one
byte each; revenue: four), 8 B a row, 0.54 GB at 64 shards, ordered by
(customer nation, supplier nation), so that an answer is a mask and two
``np.bincount``s over one of the 625 buckets (~107,000 rows at 64
shards) or over one region pair's 25 (~2.7 M rows, five buckets at a
time); a run's ~1,750 answers take ~20 s after the window.  ``finish``
orders the rows once (a radix argsort of 67 M bucket ids and five
gathers: ~8 s on the chip's host, ~1.7 GB at its peak as reckoned)."""

import importlib.util
import os

import numpy as np

from lib.served import SHARD_WIDTH, pack_planes, roaring

_spec = importlib.util.spec_from_file_location(
    "bench_configs_ssb_sf11",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ssb-sf11.py"))
_sf11 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_sf11)

DAYS, DISCOUNTS, QUANTITIES = _sf11.DAYS, _sf11.DISCOUNTS, _sf11.QUANTITIES
YEAR, MONTH = _sf11.YEAR, _sf11.MONTH
CITIES, NATIONS, REGIONS = 250, 25, 5
ARRAY_MAX = 4096  # a container of at most this many bits is an array (codec.py)


def roaring_column(values: np.ndarray, first_id: int = 0) -> bytes:
    """A categorical column (one value per column id of the shard; the
    row of value v has id v + first_id) -> Pilosa roaring bytes, one
    container per occupied (row, 2^16-column chunk): a sorted u16 array
    up to 4,096 bits, a 1,024-word bitmap above (the format: the header
    of pilosa_tpu/roaring/codec.py).  A 250-row field is 2 MB a shard
    this way and 32 MB as bitmaps."""
    order = np.argsort(values.astype(np.int16), kind="stable").astype(np.int64)  # a radix sort
    keys = (values[order].astype(np.int64) + first_id) * 16 + (order >> 16)
    cut = np.flatnonzero(np.diff(keys)) + 1
    starts = np.concatenate(([0], cut))
    counts = np.diff(np.concatenate((starts, [len(keys)])))
    lows = (order & 0xFFFF).astype("<u2")
    if counts.max() <= ARRAY_MAX:
        payloads, sizes = [lows.tobytes()], 2 * counts
    else:
        payloads, sizes = [], np.where(counts <= ARRAY_MAX, 2 * counts, 8192)
        for s, n in zip(starts.tolist(), counts.tolist()):
            if n <= ARRAY_MAX:
                payloads.append(lows[s:s + n].tobytes())
            else:
                bits = np.zeros(1 << 16, np.uint8)
                bits[lows[s:s + n]] = 1
                payloads.append(np.packbits(bits, bitorder="little").tobytes())
    hdr = np.zeros(len(starts), dtype=[("key", "<u8"), ("typ", "<u2"), ("n1", "<u2")])
    hdr["key"], hdr["n1"] = keys[starts], counts - 1
    hdr["typ"] = np.where(counts <= ARRAY_MAX, 1, 2)
    first = 8 + 16 * len(starts)
    offsets = (first + np.concatenate(([0], np.cumsum(sizes)[:-1]))).astype("<u4")
    head = np.array([12348, len(starts)], "<u4").tobytes()
    return b"".join([head, hdr.tobytes(), offsets.tobytes(), *payloads])


def make_shard(seed: int, shard: int, cfg: dict):
    rng = np.random.default_rng([seed, shard])
    n = SHARD_WIDTH
    day = rng.integers(0, DAYS, n, dtype=np.int32)  # ssb-sf11's four draws, in its order
    discount = rng.integers(0, DISCOUNTS, n, dtype=np.int32)
    quantity = rng.integers(1, QUANTITIES, n, dtype=np.int32)
    price = quantity * rng.integers(90000, 209900, n, dtype=np.int32)
    c_city = rng.integers(0, CITIES, n, dtype=np.int32)
    s_city = rng.integers(0, CITIES, n, dtype=np.int32)
    revenue = (price.astype(np.int64) * (100 - discount) // 100).astype(np.int32)
    imports = [
        ("d_year", "", roaring_column(YEAR[day])),
        ("d_yearmonthnum", "", roaring_column(MONTH[day])),
    ]
    for side, city in (("c", c_city), ("s", s_city)):
        imports += [
            (f"{side}_region", "", roaring_column(city // 50, 1)),
            (f"{side}_nation", "", roaring_column(city // 10, 1)),
            (f"{side}_city", "", roaring_column(city, 1)),
        ]
    for field, values, depth in (("lo_quantity", quantity, 6), ("lo_revenue", revenue, 24)):
        imports.append((field, f"?view=bsig_{field}", roaring(pack_planes(values, depth))))
    rows = (c_city.astype(np.uint8), s_city.astype(np.uint8), MONTH[day].astype(np.uint8),
            quantity.astype(np.uint8), revenue.astype(np.uint32))
    return imports, rows


class Table:
    """The raw columns of every row added, ordered by bucket = customer
    nation x 25 + supplier nation; ``bucket(b)`` is the rows of one."""

    def __init__(self, cfg: dict):
        self.parts = []

    def add(self, rows):
        self.parts.append(rows)

    def finish(self):
        c_city, s_city, month, quantity, revenue = (
            np.concatenate([p[k] for p in self.parts]) for k in range(5))
        del self.parts
        bucket = (c_city // 10).astype(np.int16) * NATIONS + s_city // 10
        order = np.argsort(bucket, kind="stable")
        self.starts = np.concatenate(
            ([0], np.cumsum(np.bincount(bucket, minlength=NATIONS * NATIONS))))
        del bucket
        self.c_city, self.s_city, self.month = c_city[order], s_city[order], month[order]
        self.quantity, self.revenue = quantity[order], revenue[order]

    def rows(self, lo: int, hi: int) -> tuple:
        """The columns of buckets lo..hi-1 (contiguous): customer city,
        supplier city, month, quantity, revenue."""
        s = slice(int(self.starts[lo]), int(self.starts[hi]))
        return self.c_city[s], self.s_city[s], self.month[s], self.quantity[s], self.revenue[s]

    @staticmethod
    def grouped(cell: np.ndarray, keep: np.ndarray, revenue: np.ndarray, cells: int):
        """(count, summed revenue) a cell over the rows kept: int64[cells]
        each.  float64 weights are exact here: a cell's sum stays far
        below 2**53 (2.7 M rows x 10.5 M at most)."""
        cell = cell[keep]
        n = np.bincount(cell, minlength=cells)
        v = np.bincount(cell, weights=revenue[keep], minlength=cells).astype(np.int64)
        return n, v
