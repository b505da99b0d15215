"""Q3.3: the same by two named cities a side (SSB: UNITED KI1 / UNITED
KI5 on both sides): the nation pair's 600 combinations are evaluated, 24
groups have rows; 58 planes."""

from ssb_flight3 import (city_answer, city_call, city_leaves, city_planes, draw_band, draw_cities,
                         draw_nation_pair)


def draw(rng, schema):
    n1, n2 = draw_nation_pair(rng)
    cities = draw_cities(rng, n1), draw_cities(rng, n2)
    band = draw_band(rng)
    return [city_call(n1, n2, city_leaves(cities), band)], (n1, n2, cities, band)


def answer(table, key):
    n1, n2, cities, band = key
    return city_answer(table, n1, n2, band, cities)


def planes(key):
    return [city_planes(key[0], key[1])]
