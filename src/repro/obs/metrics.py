"""Rank correlation for the search.

:func:`spearman` scores how well the cost model's predicted order of a
round's candidates matches their measured order.
"""

from __future__ import annotations

from typing import List, Optional, Sequence



def _ranks(values: Sequence[float]) -> List[float]:
    """Average ranks (ties share the mean of their positions)."""
    n = len(values)
    order = sorted(range(n), key=lambda i: values[i])
    ranks = [0.0] * n
    i = 0
    while i < n:
        j = i
        while j + 1 < n and values[order[j + 1]] == values[order[i]]:
            j += 1
        r = (i + j) / 2.0
        for k in range(i, j + 1):
            ranks[order[k]] = r
        i = j + 1
    return ranks


def spearman(x: Sequence[float], y: Sequence[float]) -> Optional[float]:
    """Spearman rank correlation; None when undefined (n < 2 or a
    constant side)."""
    if len(x) != len(y) or len(x) < 2:
        return None
    rx, ry = _ranks(list(x)), _ranks(list(y))
    mx = sum(rx) / len(rx)
    my = sum(ry) / len(ry)
    sxx = sum((a - mx) ** 2 for a in rx)
    syy = sum((b - my) ** 2 for b in ry)
    if sxx <= 0 or syy <= 0:
        return None
    sxy = sum((a - mx) * (b - my) for a, b in zip(rx, ry))
    return sxy / (sxx * syy) ** 0.5
