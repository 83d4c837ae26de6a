"""Put the program's scope names on a traced window's device time.

The program names its training step's work with ``jax.named_scope``
(``mics.gather`` to ``model.head``, below), and the names reach the compiled
HLO's ``metadata={op_name=...}``.  A profiler trace names each operation by
its HLO instruction name.  So a map from instruction name to ``op_name``,
read from the executable that ran the traced steps (:func:`op_names`), puts
a scope on each trace event, and :func:`reduce` gives, from one trace:

* each scope's device time: the time of the leaf operations whose innermost
  known scope it is, each instant of the busy time counted once (where two
  leaves overlap, the one that started first has it).  The scopes and
  ``unscoped`` add up to ``tracing.reduce``'s ``busy_s``;
* the exposed collective time under each communicating scope: collective
  time (operations and in-flight asynchronous collectives) under it during
  which no other operation ran on that device, and the union over them;
* the operations that took most time, each with its scope, and those under
  no scope.

All in seconds over the traced window, mean over the devices.  The known
names are the benchmark's own list, not imported from the program: a rename
in the program shows as ``unscoped`` time rising, not as a silent zero.
"""

from __future__ import annotations

import collections
import re

from chipbench import tracing

SCOPES = ("mics.gather", "mics.hop1", "mics.hop2", "mics.carry",
          "mics.grad_accum", "mics.optimizer", "model.attention", "model.mlp",
          "model.embed", "model.head")
# the scopes whose collectives are the step's communication
COMM_SCOPES = ("mics.gather", "mics.hop1", "mics.hop2", "mics.optimizer")
UNSCOPED = "unscoped"

_SCOPE = re.compile(r"(?<![\w.])(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?![\w.])")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled module's text
    (``compiled.as_text()``).  An instruction that calls a computation (a
    fusion, an asynchronous wrapper) and has no metadata of its own takes
    that of the called computation's root, or else of its first instruction
    that has one."""
    names: dict[str, str] = {}
    roots: dict[str, str] = {}      # computation -> its root's op_name
    firsts: dict[str, str] = {}     # computation -> first op_name in it
    callers: dict[str, str] = {}    # caller without metadata -> callee
    comp = None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OP_NAME.search(rest)
        if op:
            names[name] = op.group(1)
            firsts.setdefault(comp, op.group(1))
            if line.lstrip().startswith("ROOT"):
                roots[comp] = op.group(1)
        elif (called := _CALLS.search(rest)):
            callers[name] = called.group(1)
    for name, comp in callers.items():
        op = roots.get(comp) or firsts.get(comp)
        if op:
            names[name] = op
    return names


def scope_of(op_name: str | None) -> str:
    """The innermost known scope named in ``op_name``, or ``unscoped``."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def carries_scopes(names: dict[str, str]) -> bool:
    return any(scope_of(op) != UNSCOPED for op in names.values())


def reduce(ev: tracing.Events, names: dict[str, str],
           top: int = 10) -> dict | None:
    """The traced window's device time by scope, in seconds; None where the
    trace holds no window span or no device operation in it."""
    spans = [(a, b) for n, a, b in ev.host if n == tracing.WINDOW_SPAN]
    if not spans or not ev.device:
        return None
    lo, hi = spans[0]
    scope_t: collections.Counter = collections.Counter()
    op_t: collections.Counter = collections.Counter()
    exposed: collections.Counter = collections.Counter()
    exposed_comm = 0.0
    coll = tracing._COLLECTIVE
    for dev in sorted(ev.device):
        leaves, _ = tracing.self_times(
            [(n, max(a, lo), min(b, hi)) for n, a, b in ev.device[dev]
             if b > lo and a < hi])
        covered = lo
        for n, a, b in leaves:
            t = max(0.0, b - max(a, covered))
            covered = max(covered, b)
            scope_t[scope_of(names.get(n))] += t
            op_t[n] += t
        in_flight = [(n, max(a, lo), min(b, hi))
                     for n, a, b in ev.device_async.get(dev, ())
                     if b > lo and a < hi and coll.search(n)]
        other_u = tracing._union((a, b) for n, a, b in leaves
                                 if not coll.search(n))
        by_scope = collections.defaultdict(list)
        for n, a, b in leaves + in_flight:
            if coll.search(n):
                by_scope[scope_of(names.get(n))].append((a, b))
        for s, iv in by_scope.items():
            exposed[s] += tracing._length(
                tracing._minus(tracing._union(iv), other_u))
        comm = [iv for s in COMM_SCOPES for iv in by_scope.get(s, ())]
        exposed_comm += tracing._length(
            tracing._minus(tracing._union(comm), other_u))
    n_dev = len(ev.device)

    def per_dev(t):
        return t / n_dev * 1e-9

    return {
        "scope_s": {s: per_dev(scope_t[s]) for s in SCOPES},
        "unscoped_s": per_dev(scope_t[UNSCOPED]),
        "busy_s": per_dev(sum(scope_t.values())),
        "exposed_s": {s: per_dev(t) for s, t in exposed.items()},
        "exposed_comm_s": per_dev(exposed_comm),
        "ops": [[n, per_dev(t), scope_of(names.get(n))]
                for n, t in op_t.most_common(top)],
        "unscoped_ops": [[n, per_dev(t)] for n, t in op_t.most_common()
                         if scope_of(names.get(n)) == UNSCOPED][:top],
        "devices": n_dev,
    }
