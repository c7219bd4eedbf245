"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

One function, :func:`reduce_trace`, reads the device planes and the host
spans of one traced window and returns:

* ``window_s``: the length of the traced window, taken from the host span
  named ``bench.traced_window`` (else the extent of the device events);
* ``busy_s``: the union of the intervals in which an XLA op ran on the
  device, clipped to the window and averaged over the chips;
* ``pallas``: the Pallas kernel calls (``tpu_custom_call``), grouped by
  kind and shape, with their device time and the operations and bytes
  their shapes need (:func:`work.kernel_work`);
* ``ops_top`` and ``gaps_top``: the ten device ops that took most time,
  and the ten longest idle gaps, each named by the ``bench.*`` host span
  that overlaps it most (``host:untraced`` where none does).

The device's clock in the trace runs a millisecond or two behind the
host's.  Each program run on the device carries a ``run_id``, and so does
the host callback that sees it complete; the device events are shifted by
the least gap between the two (:func:`clock_shift_ns`), so that a device
op never seems to end after the host saw it done.  Control-flow ops
(``while``, ``call``, ``conditional``) span their bodies' ops: they count
towards busy time but are left out of the top ops.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from work import kernel_work

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DONE_EVENT = "CompleteCallbacks"
CONTAINERS = ("while", "call", "conditional")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
TOP = 10
MIN_GAP_NS = 1000  # abutting ops leave nanosecond slivers, not idle time

_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def find_xplane(path: str) -> str:
    """The newest ``.xplane.pb`` at or under ``path``."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def _dims(text: str) -> Tuple[int, ...]:
    return tuple(int(x) for x in text.split(",") if x)


def parse_op(text: str) -> Dict:
    """Name, opcode, result and operand shapes of one HLO instruction as
    the trace prints it (``%name = type opcode(operands), attrs``)."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    out: Dict = {"name": name, "opcode": "", "result": (), "operands": [],
                 "pallas": PALLAS_TARGET in rest}
    m = _OPCODE.search(rest)
    if not m:
        return out
    out["opcode"] = m.group(1)
    res = _SHAPE.match(rest)
    if res:
        out["result"] = _dims(res.group(2))
    # operands: up to the parenthesis that closes the opcode's
    depth, i = 0, m.end() - 1
    for j in range(i, len(rest)):
        c = rest[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                args = rest[i + 1:j]
                break
    else:
        args = rest[i + 1:]
    # layouts like {1,0:T(8,128)} hold no shapes; drop them first
    args = re.sub(r"\{[^}]*\}", "", args)
    out["operands"] = [_dims(d) for _, d in _SHAPE.findall(args)]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _span_for(gap: Tuple[float, float], spans) -> str:
    best, best_key = "host:untraced", None
    for name, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov <= 0 or name == WINDOW_SPAN:
            continue
        key = (ov, -(b - a))  # most overlap, then the innermost span
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def _run_id(e) -> Optional[str]:
    for k, v in e.stats:
        if k == "run_id":
            return str(v)
    return None


def clock_shift_ns(module_ends: Dict[str, float], done: Dict[str, float]) -> float:
    """Nanoseconds to add to device times: the least, over programs seen
    on both sides, of (host saw it complete) - (device ended it); 0 when
    no program is seen on both."""
    gaps = [done[r] - end for r, end in module_ends.items() if r in done]
    return min(gaps) if gaps else 0.0


def reduce_trace(path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    spans: List[Tuple[str, float, float]] = []
    raw: Dict[str, List[Tuple[float, float, str]]] = {}
    module_ends: Dict[str, float] = {}
    done: Dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = raw.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for e in line.events:
                        rid = _run_id(e)
                        if rid is not None:
                            module_ends[rid] = e.start_ns + e.duration_ns
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = e.start_ns
                    evs.append((s, s + e.duration_ns, e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns
                        spans.append((e.name, s, s + e.duration_ns))
                    elif e.name == DONE_EVENT:
                        rid = _run_id(e)
                        if rid is not None:
                            done[rid] = min(done.get(rid, e.start_ns), e.start_ns)
    if not raw or not any(raw.values()):
        raise ValueError("the trace holds no device op")
    shift = clock_shift_ns(module_ends, done)
    chips = {
        k: [(a + shift, b + shift, t) for a, b, t in evs] for k, evs in raw.items()
    }
    win = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(a for evs in chips.values() for a, _, _ in evs)
        w1 = max(b for evs in chips.values() for _, b, _ in evs)
    parsed: Dict[str, Dict] = {}
    op_time: Dict[str, float] = {}
    pallas: Dict[Tuple, Dict] = {}
    busy_total, gaps = 0.0, []
    for evs in chips.values():
        clipped = []
        for a, b, text in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            op = parsed.get(text)
            if op is None:
                op = parsed[text] = parse_op(text)
            dur = (b - a) * 1e-9
            if op["pallas"]:
                w = kernel_work(op["result"], op["operands"])
                kind = w[0] if w else "unknown"
                key = (kind, op["result"], tuple(op["operands"]))
                row = pallas.setdefault(key, {
                    "kind": kind, "result": list(op["result"]),
                    "operands": [list(o) for o in op["operands"]],
                    "flops": w[1] if w else 0, "bytes": w[2] if w else 0,
                    "count": 0, "device_s": 0.0,
                })
                row["count"] += 1
                row["device_s"] += dur
                dims = op["operands"][:2] if kind in ("dense", "batch_matmul") else [
                    op["result"]]
                label = f"pallas {kind} " + " ".join(
                    "x".join(map(str, d)) for d in dims)
            elif op["opcode"] in CONTAINERS:
                continue
            else:
                label = op["name"]
            op_time[label] = op_time.get(label, 0.0) + dur
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        prev = w0
        for a, b in busy + [(w1, w1)]:
            if a - prev >= MIN_GAP_NS:
                gaps.append((prev, a))
            prev = max(prev, b)
    n = len(chips)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out = {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "chips": n,
        "pallas": sorted(pallas.values(), key=lambda r: -r["device_s"]),
        "ops_top": [
            [k, v] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])
        ][:TOP],
        "gaps_top": [
            [_span_for(g, spans), (g[1] - g[0]) * 1e-9] for g in gaps[:TOP]
        ],
        "clock_shift_s": shift * 1e-9,
    }
    return out
