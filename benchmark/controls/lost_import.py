"""Control ``lost_import``: the reference put in the program's place with
one guarantee of the configuration broken: one shard's acknowledged
imports are not read back (what a PR that defers or drops ingest work
would do).  A control is a module of this directory that run.py finds by
the configuration's ``control``: ``lost_shards(seed, shards)`` names the
shards whose contribution the control's table never sees."""

import numpy as np


def lost_shards(seed: int, shards: int) -> set:
    return {int(np.random.default_rng([seed, 0x6C6F7374]).integers(0, shards))}
