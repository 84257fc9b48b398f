"""Busy and idle time of one chip from its operations, with NumPy: a
traced four-chip suite call holds ~3.2 M operations a chip, which the
pure-Python interval code of ``xplane`` takes seconds a chip to merge."""
from __future__ import annotations

import numpy as np


def merged(ops, lo: int, hi: int):
    """(starts, ends) of the union of the ``(name, start, end)`` intervals
    in ``ops`` cut to [lo, hi], sorted and disjoint."""
    if not ops:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    a = np.asarray([(s, e) for _, s, e in ops], np.int64)
    s, e = np.maximum(a[:, 0], lo), np.minimum(a[:, 1], hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    if not len(s):
        return s, e
    new = s[1:] > e[:-1]
    return (s[np.concatenate([[True], new])],
            e[np.concatenate([new, [True]])])


def busy_ns(trace, device: str) -> int:
    """Time in the window in which an operation ran on ``device``."""
    s, e = merged(trace.ops[device], *trace.window)
    return int((e - s).sum())


def idle_within(trace, device: str, windows) -> int:
    """Idle time of ``device`` inside the disjoint ``(start, end)``
    ``windows``, summed."""
    s, e = merged(trace.ops[device], *trace.window)
    total = 0
    for lo, hi in windows:
        busy = np.clip(np.minimum(e, hi) - np.maximum(s, lo), 0, None).sum()
        total += (hi - lo) - int(busy)
    return total
