"""GroupBy(Rows(passenger_count), Rows(pickup_year), filter=F), F one or
more of cab type, distance band and amount range: taxi query 3 under a
filter, 70 groups (ISSUE 25, ``by_pc_year``)."""

from taxi_segment import draw_free, filter_planes, pql, select


def draw(rng, schema):
    key = draw_free(rng, schema, ("cab", "band", "amount"), 1)
    return [f"GroupBy(Rows(field=passenger_count), Rows(field=pickup_year), "
            f"filter={pql(key)})"], key


def answer(table, key):
    groups = select(table.counts(key[3], key[4]), key)  # int64[passengers, year]
    return [[{"group": [{"field": "passenger_count", "rowID": p},
                        {"field": "pickup_year", "rowID": y}], "count": int(groups[p, y])}
             for p in range(groups.shape[0]) for y in range(groups.shape[1])
             if groups[p, y]]]


def planes(key):
    return [filter_planes(key) | {("passenger_count", p) for p in range(10)}
            | {("pickup_year", y) for y in range(7)}]
