"""Q3.4: Q3.3 in one month (SSB: Dec1997), drawn from the 72 months of
1992-1997: at most 4 groups; 59 planes."""

from ssb_flight3 import (city_answer, city_call, city_leaves, city_planes, draw_band, draw_cities,
                         draw_nation_pair)


def draw(rng, schema):
    n1, n2 = draw_nation_pair(rng)
    cities = draw_cities(rng, n1), draw_cities(rng, n2)
    m = int(rng.integers(0, 72))
    band = draw_band(rng)
    text = city_call(n1, n2, f"{city_leaves(cities)}, Row(d_yearmonthnum={m})", band)
    return [text], (n1, n2, cities, m, band)


def answer(table, key):
    n1, n2, cities, m, band = key
    return city_answer(table, n1, n2, band, cities, m)


def planes(key):
    return [city_planes(key[0], key[1]) | {("d_yearmonthnum", key[3])}]
