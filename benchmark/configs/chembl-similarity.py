"""chembl-similarity: the generator of the one shard of molecule rows and
the table that is the cell's plain reference.

A molecule is a row, a fingerprint bit a column (Pilosa's chemical
similarity walkthrough).  Molecules come in analog series: a series
parent is ``n`` distinct positions, n = 25 + Binomial(65, 23/65) (25-90,
mean 48), drawn one after another over a Zipf popularity of the 4,096
positions (weight 1/rank over a seeded order of them), so that the
common-substructure bits sit in most molecules; a member is its parent
with k flips, k uniform in 2-10, each a dropped parent bit or an added
position drawn over the same popularity (half and half).  Series sizes
are Zipf(2) capped at 500 and sum to the molecule count; the members of
all series are dealt to the row ids in a seeded order.  A query's hits
at thresholds 70-90 are then its series' close members: ~1 to ~400.

The table never sees a bitmap or any code of pilosa_tpu.  It keeps each
molecule's positions (uint16, id order) and answers a search exactly:
the shared bits c of every molecule with the query are the popcount of
one uint64 word that holds the 64 commonest positions, plus the rise of
the query's other positions' posting lists, over the molecules that can
reach the threshold.  A hit has c > s·t/100 (the window r > s·t/100 with
100·c > t·(r + s − c)), so a molecule whose rarer positions share fewer
than floor(s·t/100) + 1 − popcount(query's common word) with the query is
no hit, and only the others are counted and gated (``answer_dense`` is
the same search over every molecule's words, for the tests).

A rehearsal holds ``rehearsal.molecules`` molecules: where the harness
runs with JAX held to the CPU (``JAX_PLATFORMS=cpu``: no chip, and a run
of the cell itself would fail its platform check there)."""

import os

import numpy as np

BITS = 4096  # fingerprint positions: the columns 0..4095 of shard 0
HEAD = 64  # the commonest positions, one uint64 word a molecule
CHUNK = 1 << 19  # molecules an import-roaring body (the server snapshots the fragment after each)
SERIES_MAX = 500
ARRAY_MAX = 4096  # a container of at most this many bits is an array


def molecules(cfg: dict) -> int:
    """The molecule count this run holds: the field's rows, or the
    rehearsal's in a harness held to the CPU."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return cfg["rehearsal"]["molecules"]
    return cfg["fields"][0]["rows"]


def fingerprints(seed: int, m: int):
    """(offsets int64[m + 1], positions uint16[...]): molecule i's sorted
    positions are positions[offsets[i]:offsets[i + 1]]."""
    rng = np.random.default_rng([seed, 0x6368656D])
    order = rng.permutation(BITS)
    cdf = np.cumsum(1.0 / np.arange(1, BITS + 1))
    cdf /= cdf[-1]

    def popular(n):
        return order[np.minimum(np.searchsorted(cdf, rng.random(n), side="right"), BITS - 1)]

    sizes = np.minimum(rng.zipf(2.0, m), SERIES_MAX)
    sizes = sizes[: int(np.searchsorted(np.cumsum(sizes), m)) + 1]
    sizes[-1] -= int(sizes.sum()) - m
    n_series = sizes.size

    # Parents: successive draws over the popularity, a repeat skipped,
    # until n are distinct (the first n distinct of 3n draws).
    want = 25 + rng.binomial(65, 23 / 65, n_series)
    draws = 3 * want
    keys = (np.repeat(np.arange(n_series, dtype=np.int64), draws) << 12) | popular(int(draws.sum()))
    _, first = np.unique(keys, return_index=True)
    first.sort()
    kept = keys[first]
    series = kept >> 12
    start = np.searchsorted(series, np.arange(n_series))
    rank = np.arange(kept.size) - start[series]
    kept = np.sort(kept[rank < want[series]])
    par_off = np.searchsorted(kept >> 12, np.arange(n_series + 1))
    par_pos = (kept & (BITS - 1)).astype(np.uint16)
    par_len = np.diff(par_off)

    # Members: dealt to row ids in a seeded order; a parent's positions
    # less the drops, then the adds merged in.
    parent = rng.permutation(np.repeat(np.arange(n_series), sizes))
    mem_len = par_len[parent]
    total = int(mem_len.sum())
    mem_start = np.concatenate(([0], np.cumsum(mem_len)))
    gather = np.repeat(par_off[parent] - mem_start[:-1], mem_len) + np.arange(total)
    member = np.repeat(np.arange(m, dtype=np.int64), mem_len)
    flips = rng.integers(2, 11, m)
    drops = np.minimum(rng.binomial(flips, 0.5), mem_len - 1)
    adds = flips - drops
    keep = np.ones(total, dtype=bool)
    dropped = np.repeat(np.arange(m), drops)
    keep[mem_start[dropped] + (rng.random(dropped.size) * mem_len[dropped]).astype(np.int64)] = False
    base = (member[keep] << 12) | par_pos[gather[keep]]
    extra = np.unique((np.repeat(np.arange(m, dtype=np.int64), adds) << 12)
                      | popular(int(adds.sum())))
    at = np.searchsorted(base, extra)
    fresh = at >= base.size
    fresh[~fresh] = base[at[~fresh]] != extra[~fresh]
    keys = np.insert(base, at[fresh], extra[fresh])
    offsets = np.searchsorted(keys >> 12, np.arange(m + 1))
    return offsets, (keys & (BITS - 1)).astype(np.uint16)


def roaring_rows(first: int, lens: np.ndarray, positions: np.ndarray) -> bytes:
    """Rows first, first + 1, ... holding ``positions`` (uint16, sorted
    a row, ``lens`` a row) -> Pilosa roaring bytes: one array container a
    row (every position lies under 2^16 and a row holds at most 4,096),
    the format of pilosa_tpu/roaring/codec.py's header."""
    rows = np.flatnonzero(lens)
    n = lens[rows]
    if n.size and int(n.max()) > ARRAY_MAX:
        raise ValueError("a fingerprint of more than 4,096 bits")
    hdr = np.zeros(rows.size, dtype=[("key", "<u8"), ("typ", "<u2"), ("n1", "<u2")])
    hdr["key"], hdr["typ"], hdr["n1"] = (rows + first) * 16, 1, n - 1
    at = 8 + 16 * rows.size
    offs = (at + 2 * np.concatenate(([0], np.cumsum(n)[:-1]))).astype("<u4")
    head = np.array([12348, rows.size], "<u4").tobytes()
    return b"".join([head, hdr.tobytes(), offs.tobytes(), positions.astype("<u2").tobytes()])


def make_shard(seed: int, shard: int, cfg: dict):
    """Every molecule lies in shard 0; another shard (a rehearsal's) is
    empty.  The bodies are made as they are sent, CHUNK molecules each."""
    if shard != 0:
        return [], None
    offsets, positions = fingerprints(seed, molecules(cfg))
    field = cfg["fields"][0]["name"]

    def bodies():
        lens = np.diff(offsets)
        for lo in range(0, lens.size, CHUNK):
            hi = min(lo + CHUNK, lens.size)
            yield field, "", roaring_rows(lo, lens[lo:hi], positions[offsets[lo]:offsets[hi]])

    return bodies(), (offsets, positions)


def gate(c, r, s, t):
    """The molecules ``fragment.top``'s Tanimoto walk keeps, in integers:
    r > s·t/100, r < s·100/t, c > 0 and ceil(100·c / (r + s − c)) > t."""
    return (r * 100 > s * t) & (r * t < s * 100) & (c > 0) & (c * 100 > t * (r + s - c))


def pairs(ids, c) -> list:
    """The reply's pairs: count down, then id down."""
    order = np.lexsort((-ids, -c))
    return [{"id": int(i), "count": int(n)} for i, n in zip(ids[order], c[order])]


class Table:
    """Each molecule's positions; ``search(q, t)`` is the reply's pairs."""

    def __init__(self, cfg: dict):
        self.parts = []

    def add(self, contribution):
        if contribution is not None:
            self.parts.append(contribution)

    def finish(self):
        offsets, positions = self.parts[0] if self.parts else (np.zeros(1, np.int64),
                                                                 np.zeros(0, np.uint16))
        del self.parts
        self.offsets, self.positions = offsets, positions
        self.counts = np.diff(offsets)
        m = self.counts.size
        member = np.repeat(np.arange(m, dtype=np.int32), self.counts)
        df = np.bincount(positions, minlength=BITS)
        self.slot = np.full(BITS, -1, dtype=np.int64)
        self.slot[np.argsort(-df, kind="stable")[:HEAD]] = np.arange(HEAD)
        head = self.slot[positions] >= 0
        self.head = np.zeros(m, dtype=np.uint64)
        if head.any():  # distinct bits of a word: their sum is their OR
            bits = np.left_shift(np.uint64(1), self.slot[positions[head]].astype(np.uint64))
            starts = np.flatnonzero(np.r_[True, member[head][1:] != member[head][:-1]])
            self.head[member[head][starts]] = np.add.reduceat(bits, starts)
        tail = ~head
        order = np.argsort(positions[tail], kind="stable")
        self.post = member[tail][order]
        self.post_off = np.concatenate(
            ([0], np.cumsum(np.bincount(positions[tail], minlength=BITS))))

    def row(self, q: int) -> np.ndarray:
        return self.positions[self.offsets[q]:self.offsets[q + 1]]

    def search(self, q: int, t: int) -> list:
        m = self.counts.size
        if q >= m:
            return []
        mine = self.row(q)
        s = mine.size
        qh = np.uint64(0)
        for k in self.slot[mine][self.slot[mine] >= 0].tolist():
            qh |= np.uint64(1) << np.uint64(k)
        rare = mine[self.slot[mine] < 0].tolist()
        lists = [self.post[self.post_off[p]:self.post_off[p + 1]] for p in rare]
        ct = np.bincount(np.concatenate(lists), minlength=m) if lists else np.zeros(m, np.int64)
        need = s * t // 100 + 1 - int(qh).bit_count()
        cand = np.flatnonzero(ct >= need) if need > 0 else np.arange(m)
        c = np.bitwise_count(self.head[cand] & qh).astype(np.int64) + ct[cand]
        hit = gate(c, self.counts[cand], s, t)
        return pairs(cand[hit], c[hit])

    def answer_dense(self, q: int, t: int) -> list:
        """``search`` over every molecule's uint64[64] words: the plain
        AND + popcount form, for the tests at a rehearsal's size."""
        m = self.counts.size
        if q >= m:
            return []
        words = np.zeros((m, BITS // 64), dtype=np.uint64)
        member = np.repeat(np.arange(m), self.counts)
        p = self.positions.astype(np.int64)
        np.bitwise_or.at(words, (member, p >> 6), np.left_shift(np.uint64(1), (p & 63).astype(np.uint64)))
        c = np.bitwise_count(words & words[q]).sum(axis=1).astype(np.int64)
        hit = gate(c, self.counts, int(self.counts[q]), t)
        return pairs(np.flatnonzero(hit), c[hit])
