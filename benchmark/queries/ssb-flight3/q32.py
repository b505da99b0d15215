"""Q3.2: revenue by customer city, supplier city and year under a nation
pair (SSB: UNITED STATES, UNITED STATES): 10 x 10 x 6 = 600 combinations,
60 planes."""

from ssb_flight3 import city_answer, city_call, city_planes, draw_band, draw_nation_pair


def draw(rng, schema):
    n1, n2 = draw_nation_pair(rng)
    band = draw_band(rng)
    return [city_call(n1, n2, f"Row(c_nation={n1}), Row(s_nation={n2})", band)], (n1, n2, band)


def answer(table, key):
    n1, n2, band = key
    return city_answer(table, n1, n2, band)


def planes(key):
    n1, n2, _ = key
    return [city_planes(n1, n2) | {("c_nation", n1), ("s_nation", n2)}]
