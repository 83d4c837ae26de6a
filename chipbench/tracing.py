"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

From one trace of a traced window (the host span ``bench.trace``):

* each device's busy time: the union of the intervals in which an
  operation ran on it, within the window.  Operations that enclose others
  (a ``while`` loop, a call) count by the operations inside them;
* its collective time (all-gather, reduce-scatter, all-reduce,
  collective-permute, all-to-all) and the part of it during which no other
  operation ran on that device (exposed);
* the device operations that took most time;
* the longest idle gaps of the first device, each named by the host span
  (``bench.transfer``, ``bench.dispatch``, ``bench.loss_read``) that
  covers most of it.
"""

from __future__ import annotations

import collections
import dataclasses
import re

WINDOW_SPAN = "bench.trace"
HOST_SPANS = ("bench.transfer", "bench.dispatch", "bench.loss_read")
_DEVICE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all")
# the device line that holds one event per executed operation, and the one
# that holds asynchronous operations from their start to their done
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"


@dataclasses.dataclass
class Events:
    """Intervals in nanoseconds: ``device[i]`` the operations of device i,
    ``device_async[i]`` its asynchronous operations in flight, ``host`` the
    benchmark's own host spans.  Operations are named by their HLO name."""

    device: dict[int, list[tuple[str, float, float]]]
    host: list[tuple[str, float, float]]
    device_async: dict[int, list[tuple[str, float, float]]] = \
        dataclasses.field(default_factory=dict)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ev = Events({}, [], {})
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                to = ev.device if line.name == OPS_LINE else ev.device_async
                to.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                ev.host.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS)
    return ev


def self_times(ops) -> tuple[list, list]:
    """``ops`` split into leaves (enclosing no other operation) and each
    operation's self time: its length less that of the operations directly
    inside it."""
    order = sorted(ops, key=lambda o: (o[1], -o[2]))
    self_t = [b - a for _, a, b in order]
    leaf = [True] * len(order)
    stack: list[int] = []
    for i, (_, a, b) in enumerate(order):
        while stack and order[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= order[stack[-1]][2]:
            self_t[stack[-1]] -= b - a
            leaf[stack[-1]] = False
        stack.append(i)
    leaves = [o for o, is_leaf in zip(order, leaf) if is_leaf]
    return leaves, [(o[0], t) for o, t in zip(order, self_t)]


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _minus(a_iv, b_iv) -> list[tuple[float, float]]:
    """The parts of the (sorted, disjoint) ``a_iv`` outside ``b_iv``."""
    out, j = [], 0
    for a, b in a_iv:
        cur = a
        while j < len(b_iv) and b_iv[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_iv) and b_iv[k][0] < b:
            if b_iv[k][0] > cur:
                out.append((cur, b_iv[k][0]))
            cur = max(cur, b_iv[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(ev: Events, top: int = 10) -> dict | None:
    """The window's device numbers, in seconds; None where the trace holds
    no window span or no device operation in it."""
    spans = [(a, b) for n, a, b in ev.host if n == WINDOW_SPAN]
    if not spans or not ev.device:
        return None
    lo, hi = spans[0]
    busy, coll, exposed = [], [], []
    op_time: collections.Counter = collections.Counter()
    gaps0 = None
    for dev in sorted(ev.device):
        ops, selfs = self_times(
            [(n, max(a, lo), min(b, hi)) for n, a, b in ev.device[dev]
             if b > lo and a < hi])
        for n, t in selfs:
            op_time[n] += t
        in_flight = [(n, max(a, lo), min(b, hi))
                     for n, a, b in ev.device_async.get(dev, ())
                     if b > lo and a < hi and _COLLECTIVE.search(n)]
        all_u = _union((a, b) for _, a, b in ops)
        c_u = _union((a, b) for n, a, b in ops + in_flight
                     if _COLLECTIVE.search(n))
        other_u = _union((a, b) for n, a, b in ops
                         if not _COLLECTIVE.search(n))
        busy.append(_length(all_u))
        coll.append(_length(c_u))
        exposed.append(_length(_minus(c_u, other_u)))
        if gaps0 is None:
            gaps0 = _minus([(lo, hi)], all_u)
    if not any(busy):
        return None
    n = len(busy)
    named = []
    for a, b in gaps0:
        cover = collections.Counter()
        for name, sa, sb in ev.host:
            if name in HOST_SPANS:
                for ca, cb in _clip([(sa, sb)], a, b):
                    cover[name] += cb - ca
        label = cover.most_common(1)[0][0] if cover else "no bench span"
        named.append([label, (b - a) * 1e-9])
    named.sort(key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "collective_s": sum(coll) / n * 1e-9,
        "exposed_collective_s": sum(exposed) / n * 1e-9,
        "device_ops": [[k, v / n * 1e-9] for k, v in op_time.most_common(top)],
        "idle_gaps": named[:top],
        "devices": n,
    }
