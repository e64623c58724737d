"""The plain reference: HyperLogLog in numpy, written from the published
algorithms and sharing no code with the program under test.

* Hash: h1 of MurmurHash3_x64_128 over each item as a 4-byte little-endian
  key (Appleby's reference C code, ``MurmurHash3_x64_128`` tail path with
  ``len = 4``), in native uint64 arithmetic.
* Bucket and rank (arXiv:2005.13332 Algorithm 1): the first ``p`` hash bits
  pick the bucket; the rank is the leading-zero count of the other
  ``64 - p`` bits plus one, at most ``65 - p``.
* Estimate: Flajolet et al.'s raw harmonic-mean estimate with the
  LinearCounting small-range correction, and no large-range correction for a
  64-bit hash (the paper's "original" estimator), in float64.

Work is split into chunks run on a thread pool (numpy releases the GIL in
its array loops), so a run's reference takes seconds, not minutes.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_C1 = np.uint64(0x87C37B91114253D5)
_C2 = np.uint64(0x4CF5AD432745937F)
_F1 = np.uint64(0xFF51AFD7ED558CCD)
_F2 = np.uint64(0xC4CEB9FE1A85EC53)
_S33 = np.uint64(33)
_CHUNK = 1 << 22
_RANK_BITS = 6  # ranks are at most 65 - p <= 61 < 64
# the original estimator switches from LinearCounting to the raw estimate at
# E = 2.5 m; within this relative distance of the switch the program's
# float32 and this float64 may take different branches, and either is right
SWITCH_SLACK = 1e-5


def _threads() -> int:
    return max(1, min(16, (os.cpu_count() or 2) - 1))


def _pool():
    return ThreadPoolExecutor(_threads())


def _fmix64(k: np.ndarray) -> np.ndarray:
    k ^= k >> _S33
    k *= _F1
    k ^= k >> _S33
    k *= _F2
    k ^= k >> _S33
    return k


def murmur3_x64_h1(items: np.ndarray, seed: int = 0) -> np.ndarray:
    """uint64 h1 of MurmurHash3_x64_128 of each uint32 item (4-byte key)."""
    k1 = items.astype(np.uint64)
    k1 *= _C1
    k1 = (k1 << np.uint64(31)) | (k1 >> _S33)
    k1 *= _C2
    s = np.uint64(seed & 0xFFFFFFFFFFFFFFFF)
    h1 = k1 ^ s ^ np.uint64(4)
    h2 = s ^ np.uint64(4)
    h1 += h2
    h2 = h1 + h2
    h1 = _fmix64(h1)
    h2 = _fmix64(h2)
    h1 += h2
    return h1


def _bit_length32(v: np.ndarray) -> np.ndarray:
    # frexp is exact on integers below 2^53: v = f * 2^e with f in [0.5, 1)
    return np.frexp(v.astype(np.float64))[1]


def bucket_rank(items: np.ndarray, p: int, seed: int = 0):
    """(bucket int64 in [0, 2^p), rank uint8 in [1, 65 - p]) per item."""
    h = murmur3_x64_h1(items, seed)
    bucket = (h >> np.uint64(64 - p)).astype(np.int64)
    w = h << np.uint64(p)
    hi = (w >> np.uint64(32)).astype(np.uint32)
    lo = (w & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    clz = np.where(hi > 0, 32 - _bit_length32(hi), 64 - _bit_length32(lo))
    rank = (np.minimum(clz, 64 - p) + 1).astype(np.uint8)
    return bucket, rank


def _chunk_cells(keys, items, p, seed):
    """Sorted distinct cells (row * m + bucket) with their max rank."""
    bucket, rank = bucket_rank(items, p, seed)
    packed = ((keys.astype(np.int64) << p) | bucket) << _RANK_BITS
    packed |= rank
    packed.sort()
    cell = packed >> _RANK_BITS
    last = np.empty(cell.size, bool)
    last[:-1] = cell[1:] != cell[:-1]
    last[-1:] = True
    return cell[last], (packed[last] & ((1 << _RANK_BITS) - 1)).astype(np.uint8)


def bank_registers(streams, rows: int, p: int, seed: int = 0) -> np.ndarray:
    """(rows, 2^p) uint8 registers of keyed (keys, items) streams.

    ``streams`` is an iterable of (keys, items) array pairs; keys outside
    [0, rows) are dropped.  Each chunk reduces to its distinct cells on the
    pool; the merge splits the cell space into ranges, one thread each.
    """
    m = 1 << p
    table = np.zeros(rows * m, np.uint8)
    parts = []

    def chunks():
        for keys, items in streams:
            keys = np.asarray(keys)
            items = np.asarray(items)
            ok = (keys >= 0) & (keys < rows)
            if not ok.all():
                keys, items = keys[ok], items[ok]
            for s in range(0, keys.size, _CHUNK):
                yield keys[s : s + _CHUNK], items[s : s + _CHUNK]

    with _pool() as pool:
        parts = list(pool.map(lambda kx: _chunk_cells(kx[0], kx[1], p, seed), chunks()))
        n_ranges = 4 * _threads()
        edges = np.linspace(0, rows * m, n_ranges + 1).astype(np.int64)

        def merge(r):
            lo_cell, hi_cell = edges[r], edges[r + 1]
            for cell, rank in parts:
                lo, hi = np.searchsorted(cell, (lo_cell, hi_cell))
                if hi > lo:
                    c = cell[lo:hi]
                    table[c] = np.maximum(table[c], rank[lo:hi])

        list(pool.map(merge, range(n_ranges)))
    return table.reshape(rows, m)


def sketch_registers(items_chunks, p: int, seed: int = 0) -> np.ndarray:
    """(2^p,) uint8 registers of one sketch over an iterable of item arrays."""
    m = 1 << p

    def one(x):
        bucket, rank = bucket_rank(x, p, seed)
        regs = np.zeros(m, np.uint8)
        np.maximum.at(regs, bucket, rank)
        return regs

    def chunks():
        for arr in items_chunks:
            for s in range(0, arr.size, _CHUNK):
                yield arr[s : s + _CHUNK]

    out = np.zeros(m, np.uint8)
    with _pool() as pool:
        for regs in pool.map(one, chunks()):
            np.maximum(out, regs, out=out)
    return out


def alpha(m: int) -> float:
    """Flajolet et al.'s bias constant alpha_m."""
    if m == 16:
        return 0.673
    if m == 32:
        return 0.697
    if m == 64:
        return 0.709
    return 0.7213 / (1.0 + 1.079 / m)


def estimates(registers: np.ndarray, p: int):
    """float64 estimates of (..., m) registers: (chosen, alternative).

    ``chosen`` follows the estimator's branch in float64.  ``alternative``
    is the other branch where the raw estimate lies within SWITCH_SLACK of
    the 2.5 m switch (and equals ``chosen`` elsewhere): a float32
    implementation may rightly land on either side there.
    """
    m = 1 << p
    regs = np.asarray(registers).reshape(-1, m)
    rows = regs.shape[0]
    weights = np.ldexp(1.0, -np.arange(256))  # 2^-M for every uint8 value
    harm = np.empty(rows)
    zeros = np.empty(rows)
    block = max(1, (1 << 22) // m)

    def one(s):
        r = regs[s : s + block]
        harm[s : s + block] = weights[r].sum(axis=1)
        zeros[s : s + block] = (r == 0).sum(axis=1)

    with _pool() as pool:
        list(pool.map(one, range(0, rows, block)))
    raw = alpha(m) * m * m / harm
    with np.errstate(divide="ignore"):
        lc = m * np.log(m / np.maximum(zeros, 1.0))
    small = raw <= 2.5 * m
    chosen = np.where(small & (zeros > 0), lc, raw)
    other = np.where(small & (zeros > 0), raw, np.where(zeros > 0, lc, raw))
    near = np.abs(raw - 2.5 * m) <= SWITCH_SLACK * 2.5 * m
    alternative = np.where(near, other, chosen)
    shape = np.asarray(registers).shape[:-1]
    return chosen.reshape(shape), alternative.reshape(shape)


def relative_gap(got, chosen, alternative) -> np.ndarray:
    """Per-entry |got - ref| / max(ref, 1), taking the nearer branch."""
    got = np.asarray(got, np.float64)
    gap_a = np.abs(got - chosen) / np.maximum(np.abs(chosen), 1.0)
    gap_b = np.abs(got - alternative) / np.maximum(np.abs(alternative), 1.0)
    return np.minimum(gap_a, gap_b)
