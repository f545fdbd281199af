"""Relocation windows that keep a ``DistArray``'s places evenly loaded
under YCSB's key popularity.

The records are keys ``0..N-1`` in blocks of ``block_records``; place
``p`` starts with an equal run of blocks.  The popularity of a key is
that of YCSB's ``requestdistribution=zipfian`` (``ScrambledZipfianGenerator``):
a zipfian with constant ``zipf_theta`` over ``zipf_items`` ranks (YCSB's
``ITEM_COUNT``, normalised by its precomputed ``zeta``), each rank
hashed to a key by ``fnvhash64(rank) % N``.  The ranks past the first
``2**head_bits`` are spread evenly over the keys, as their hashes are.

The hot set moves: in window ``w`` block ``b`` has the popularity that
block ``b - (w + seed) * drift_blocks`` had at the start.  Each window
the balancer spends its budget of ``moves_per_window`` block moves: it
takes from the most loaded place the block whose load is nearest half
the gap to the least loaded place, and sends it there, until the budget
is spent.  The plan depends only on the mix, the configuration, the
seed and the window's index: the program's choices never feed back.

``warmup()`` first yields one window for each send-buffer size the
device transport can round a pair to: ``k`` blocks on one pair, and
the rest of the budget one block to a pair.
"""
from __future__ import annotations

import functools

import numpy as np

_FNV_OFFSET, _FNV_PRIME = 0xCBF29CE484222325, 1099511628211


def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the value's 8 bytes, low
    byte first, as a non-negative Java ``long``."""
    v = np.asarray(v, np.int64).astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(_FNV_PRIME)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


@functools.lru_cache(maxsize=4)
def key_popularity(n_keys: int, theta: float, items: int, zetan: float,
                   head_bits: int) -> np.ndarray:
    """Share of YCSB's requests that fall on each of ``n_keys`` keys."""
    ranks = np.arange(1 << head_bits, dtype=np.int64)
    p = np.power(ranks + 1.0, -theta) / zetan
    share = np.bincount(fnvhash64(ranks) % n_keys, weights=p,
                        minlength=n_keys)
    return share + (1.0 - p.sum()) / n_keys


class Traffic:
    def __init__(self, mix: dict, config: dict, seed: int):
        self.places = int(config["places"])
        self.n_keys = self.places * int(config["rows_per_place"])
        self.block = int(mix["block_records"])
        if self.n_keys % (self.block * self.places):
            raise ValueError("places do not hold whole blocks")
        self.blocks = self.n_keys // self.block
        share = key_popularity(self.n_keys, float(mix["zipf_theta"]),
                               int(mix["zipf_items"]), float(mix["zipf_zetan"]),
                               int(mix["head_bits"]))
        self.popularity = share.reshape(self.blocks, self.block).sum(1)
        self.drift = int(mix["drift_blocks"])
        self.budget = int(mix["moves_per_window"])
        self.warmup_windows = int(mix["warmup_windows"])
        self.phase = int(seed) % self.blocks
        self.owner = np.repeat(np.arange(self.places),
                               self.blocks // self.places)
        self.window = 0

    def block_range(self, b: int) -> tuple[int, int]:
        return b * self.block, (b + 1) * self.block

    def _moves(self, blocks, dests) -> list:
        """``[(start, end, src, dest), ...]``, applied to the owners."""
        out = []
        for b, d in zip(blocks, dests):
            out.append((*self.block_range(int(b)), int(self.owner[b]), int(d)))
            self.owner[b] = d
        return out

    def warmup(self):
        """The windows set-up runs before the clock starts."""
        k = 1
        while k <= self.budget:
            yield self._probe(k)
            k = 2 * k - 1 if k > 1 else 2       # 1, 2, 3, 5, 9, 17, ...
        for _ in range(self.warmup_windows):
            yield self.next_window()

    def _probe(self, k: int) -> list:
        pairs = [(s, (s + o) % self.places) for o in range(1, self.places)
                 for s in range(self.places)]
        plan = [pairs[0]] * k + pairs[1:1 + self.budget - k]
        blocks, dests, taken = [], [], set()
        for s, d in plan:
            b = next(int(b) for b in np.flatnonzero(self.owner == s)
                     if int(b) not in taken)
            taken.add(b)
            blocks.append(b)
            dests.append(d)
        return self._moves(blocks, dests)

    def next_window(self) -> list:
        """The next window's block moves, in registration order."""
        shift = ((self.window + self.phase) * self.drift) % self.blocks
        load = np.roll(self.popularity, shift)
        place = np.bincount(self.owner, weights=load, minlength=self.places)
        moves, moved = [], np.zeros(self.blocks, bool)
        for _ in range(self.budget):
            hot, cold = int(place.argmax()), int(place.argmin())
            free = np.flatnonzero((self.owner == hot) & ~moved)
            half_gap = (place[hot] - place[cold]) / 2
            b = int(free[np.argmin(np.abs(load[free] - half_gap))])
            moved[b] = True
            place[hot] -= load[b]
            place[cold] += load[b]
            moves += self._moves([b], [cold])
        self.window += 1
        return moves
