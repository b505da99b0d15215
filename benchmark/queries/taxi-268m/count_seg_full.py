"""Count(F) with every leaf of F present and the band at its widest, 8
rows: ``count_seg``'s widest filter, 22 row-planes, and one filter
structure, so the server compiles one program per batch tier."""

from taxi_segment import KINDS, draw_filter, filter_planes, pql, select


def draw(rng, schema):
    key = draw_filter(rng, schema, KINDS, 8)
    return [f"Count({pql(key)})"], key


def answer(table, key):
    return [int(select(table.counts(key[3], key[4]), key).sum())]


def planes(key):
    return [filter_planes(key)]
