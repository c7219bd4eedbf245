"""Grouped-expert matmul kernels of the prefill program in the traced
window: the least time their shapes need on this chip over the device
time they took, in percent.

A grouped kernel is found among the Pallas ops of the trace by its shape:
its last two operands are the rows ``x`` (m, k) and the experts' weights
``w`` (g, k, n), its result (m, n); the operands before them are the
group bookkeeping it reads as scalars.  Its work is every row against its
own group's weight, 2 m k n operations, and its bytes are ``work.py``'s
bf16 rule: x, every weight of w once, and the result.
"""

from work import BF16, ideal_s


def grouped_work(row):
    """(flops, bytes) of a trace row that is a grouped matmul, else None."""
    r, ops = row["result"], row["operands"]
    if len(r) != 2 or len(ops) < 3:
        return None
    x, w = ops[-2], ops[-1]
    if len(x) != 2 or len(w) != 3:
        return None
    (m, k), (g, k2, n) = x, w
    if k != k2 or list(r) != [m, n]:
        return None
    return 2 * m * k * n, (m * k + g * k * n + m * n) * BF16


def read(obs):
    if obs.get("job") != "prefill" or not obs.get("trace"):
        return None
    rows = [(k, grouped_work(k)) for k in obs["trace"]["pallas"]]
    rows = [(k, w) for k, w in rows if w is not None]
    took = sum(k["device_s"] for k, _ in rows)
    if not rows or took <= 0:
        return None
    need = sum(k["count"] * ideal_s(*w, obs["peak"]) for k, w in rows)
    return 100.0 * need / took
