"""taxi-q4-268m: ``taxi-268m``'s generator and joint table, loaded by path
(the same data for a seed, the same 5-D histogram, which never sees a
bitmap or any code of pilosa_tpu), plus the lookup taxi query 4 needs:
the rides by passengers, year and distance row within a fare range."""

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_configs_taxi_268m",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "taxi-268m.py"))
_taxi = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_taxi)

DIMS = _taxi.DIMS
FIELDS = _taxi.FIELDS
make_shard = _taxi.make_shard


class Table(_taxi.Table):
    def by_pc_year_miles(self, amount):
        """int64[passengers, year, miles]: rides with dollars in
        [lo, hi], both cab types: ``counts((d, d), amount)`` a distance
        row."""
        return np.stack(
            [self.counts((d, d), amount).sum(axis=0) for d in range(DIMS[3])], axis=-1)
