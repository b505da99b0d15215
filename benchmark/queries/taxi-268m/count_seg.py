"""Count(F), F at least two of the five leaves (ISSUE 25, ``count_seg``)."""

from taxi_segment import KINDS, draw_free, filter_planes, pql, select


def draw(rng, schema):
    key = draw_free(rng, schema, KINDS, 2)
    return [f"Count({pql(key)})"], key


def answer(table, key):
    return [int(select(table.counts(key[3], key[4]), key).sum())]


def planes(key):
    return [filter_planes(key)]
