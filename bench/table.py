"""The benchmark's own base table: a greedy fit on sample words.

The program under test is handed this table as an input, like weights
made from the seed; the reference gets the same table, so neither side's
result depends on the program's fitting code.

Each pick takes the window of ``2**w`` consecutive word values (for a
width ``w`` of the format) that saves the most bits over the words no
earlier base covers, and centres a base on it.
"""
from __future__ import annotations

import numpy as np

from fmt import Format


def _to_int32(v: int, word_bits: int) -> int:
    v &= (1 << word_bits) - 1
    if word_bits == 32 and v >= 1 << 31:
        v -= 1 << 32
    return v


def fit(sample: np.ndarray, f: Format) -> tuple[np.ndarray, np.ndarray]:
    """sample: word values (16-bit words as 0..65535, 32-bit as int32).
    Returns (bases, widths), each (num_bases,) int32."""
    v = np.asarray(sample, np.int64).reshape(-1)
    if f.word_bits == 32:
        v = v & 0xFFFFFFFF              # order 32-bit words as unsigned
    v = v[v != 0]
    vals, counts = np.unique(v, return_counts=True)
    live = counts.astype(np.int64)
    bases: list[int] = []
    widths: list[int] = []
    for _ in range(f.num_bases):
        best = (0, 0, 0, 0, 0)          # gain, start, width, lo, hi
        cum = np.concatenate([[0], np.cumsum(live)])
        for w in f.width_set:
            hi = np.searchsorted(vals, vals + (1 << w), side="left")
            gain = (cum[hi] - cum[:-1]) * (f.word_bits - w)
            if gain.size and gain.max() > best[0]:
                i = int(gain.argmax())
                best = (int(gain[i]), int(vals[i]), w, i, int(hi[i]))
        gain, start, w, lo, hi = best
        if gain == 0:
            break
        bases.append(_to_int32(start + (1 << (w - 1)), f.word_bits))
        widths.append(w)
        live[lo:hi] = 0
    while len(bases) < f.num_bases:     # nothing left to cover: repeat
        bases.append(bases[-1] if bases else 0)
        widths.append(widths[-1] if widths else f.width_set[-1])
    return np.asarray(bases, np.int32), np.asarray(widths, np.int32)
