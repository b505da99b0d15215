"""Sum(F, field=total_amount), F at least two leaves and no amount leaf:
taxi query 2 (average amount) under a drill-down; average = value / count
(ISSUE 25, ``avg_amount``)."""

from taxi_segment import KINDS, draw_free, filter_planes, pql, select


def draw(rng, schema):
    key = draw_free(rng, schema, KINDS[:4], 2)
    return [f"Sum({pql(key)}, field=total_amount)"], key


def answer(table, key):
    return [{"value": int(select(table.dollars(key[3]), key).sum()),
             "count": int(select(table.counts(key[3]), key).sum())}]


def planes(key):
    return [filter_planes(key) | {("total_amount", k) for k in range(11)}]
