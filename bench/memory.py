"""Estimate against measured need (the paper's Eq. 2, as
``repro.core.metrics`` defines it, copied so that the yardstick stays
with the benchmark), and the ballast bisection that measures the need.

The measured need of a training step is the least device memory in
which one more step completes. It is measured by occupying the rest: a
ballast of ``b`` bytes is placed beside the live parameters and
optimizer state, and one step is run. The largest ``b`` with which the
step completes gives ``need = bytes_limit - b``, to the bisection's
resolution.
"""
from __future__ import annotations


def rel_error_pct(estimate: int, truth: int, floor: int = 0) -> float:
    """``|estimate - truth| / truth`` in percent (Eq. 2), with the
    difference floored at ``floor`` bytes: a measurement resolved to
    ``floor`` cannot show a smaller error."""
    return 100.0 * max(abs(estimate - truth), floor) / truth


def bisect_ballast(probe, lo: int, hi: int) -> tuple[int, int]:
    """Largest ``k`` in ``[lo, hi)`` with ``probe(k)`` true, given that
    ``probe(lo)`` holds, that ``probe`` is monotone (true up to some
    ``k``, false above) and that ``probe(hi)`` fails or ``hi`` is past
    what can be held. Returns ``(k, probes_made)``."""
    probes = 0
    while hi - lo > 1:
        mid = (lo + hi) // 2
        probes += 1
        if probe(mid):
            lo = mid
        else:
            hi = mid
    return lo, probes
