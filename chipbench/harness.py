"""Run one training cell once: set-up, a timed window, the comparison.

Set-up builds the program's state (``init_state``), swaps in the weights
drawn from the seed, compiles the program's step once, drives that compiled
step through the checked steps with the window's own feed (their readings
are kept for the comparison), warms up, and reads the compiled step's
memory.  The window then runs steps for ``--seconds``: each moves a
host batch to the device, calls the jitted step and reads the loss back,
which blocks, as the program's training loop does.  With ``--trace 1`` a
few more steps run under the profiler afterwards.  Once the window has
closed and the program's state is freed, the plain reference repeats the
checked steps and decides ``correct``.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import importlib
import json
import math
import shutil
import sys
import tempfile
import time

from chipbench import cell as cellmod
from chipbench import correct, tracing
from chipbench.synthetic import MarkovTokens


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers here."""

    chips: int
    tokens_per_step: int
    flops_per_step: float
    peak_flops: float
    setup_s: float
    init_s: float
    first_step_s: float
    window_s: float
    window_steps: int
    dispatch_s: list
    peak_hbm_bytes: int
    trace: dict | None = None
    trace_steps: int = 0


class Phases:
    """Prints each phase's end, in seconds since process start, to stderr."""

    def __init__(self, t0: float):
        self.t0 = t0

    def __call__(self, what: str) -> None:
        print(f"chipbench: {time.time() - self.t0:8.2f} s  {what}",
              file=sys.stderr)


class Feed:
    """The window's call: batch to device, jitted step, blocking loss read."""

    def __init__(self, step_fn, state):
        self.step_fn, self.state = step_fn, state

    def step(self, host_batch) -> tuple[float, float]:
        """One step; returns its loss and its dispatch time in seconds."""
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        t = time.perf_counter()
        with TraceAnnotation("bench.transfer"):
            batch = jax.tree.map(jnp.asarray, host_batch)
        with TraceAnnotation("bench.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch)
        dispatch = time.perf_counter() - t
        with TraceAnnotation("bench.loss_read"):
            loss = float(metrics["loss"])
        return loss, dispatch


def seed_key(seed: int):
    """A JAX key from a seed of up to 64 bits."""
    import jax

    seed %= 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def peak_flops(device_kind: str) -> float:
    with open(cellmod.HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]["bf16_flops_per_s"]


def batches(cell: cellmod.Cell, seed: int) -> tuple[list, list]:
    """The checked steps' batches and the window's, all rows distinct."""
    tr = cell.traffic
    data = MarkovTokens(cell.cfg["vocab"], tr["seq"], seed=seed, **tr["data"])
    n = tr["checked_steps"]
    out = [data.batch(i, tr["global_batch"], tr["micro_steps"])
           for i in range(n + tr["distinct_batches"])]
    return out[:n], out[n:]


def start(program, seed: int, key, host_batch
          ) -> tuple[dict, object, float, float]:
    """The program's state from the seed with the benchmark's weights in it,
    and its step compiled once for the cell's batch.  Also returns the
    seconds ``init_state`` took and the seconds the compile took."""
    import jax
    import jax.numpy as jnp

    t = time.perf_counter()
    state = jax.block_until_ready(program.init_state(seed))
    init_s = time.perf_counter() - t
    state = jax.block_until_ready(program.load_weights(state, key))
    t = time.perf_counter()
    compiled = program.step.lower(
        state, jax.tree.map(jnp.asarray, host_batch)).compile()
    return state, compiled, init_s, time.perf_counter() - t


def checked_steps(program, feed: Feed, key, checked: list
                  ) -> tuple[dict, float]:
    """Drive the step through the checked batches.  Returns the program's
    readings (each step's loss, the first gradient and the change of each
    leaf) and the first step's time."""
    t = time.perf_counter()
    losses = [feed.step(checked[0])[0]]
    first_step_s = time.perf_counter() - t
    grad = program.grad_norms(feed.state)
    losses += [feed.step(b)[0] for b in checked[1:]]
    return {"losses": losses, "grad": grad,
            "delta": program.delta_norms(feed.state, key)}, first_step_s


def footprint(compiled) -> dict[str, int]:
    """The compiled step's own memory on one device, by kind, and its
    footprint: arguments + outputs - aliased + temporaries."""
    ma = compiled.memory_analysis()
    out = {k: getattr(ma, f"{k}_size_in_bytes")
           for k in ("argument", "output", "alias", "temp")}
    out["footprint"] = (out["argument"] + out["output"] - out["alias"]
                        + out["temp"])
    return out


class Compiles:
    """Counts the programs compiled or loaded from the cache while on."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name: str, **_):
        if self.on and name == "/jax/compilation_cache/compile_requests_use_cache":
            self.count += 1


def free(tree) -> None:
    import jax

    for a in jax.tree.leaves(tree):
        a.delete()


def run(cell: cellmod.Cell, seed: int, seconds: float, trace: bool, *,
        t0: float, peak: float, fault=None) -> dict:
    """One run of ``cell``; returns its result line as a dict.

    ``fault`` (tests only) wraps the compiled step to plant a fault.
    """
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = Phases(t0)
    compiles = Compiles()
    tr = cell.traffic
    opt = cell.opt()
    program = cell.module("program").Program(cell.cfg, tr, opt)
    checked, window = batches(cell, seed)
    key = seed_key(seed)
    log("program built, batches drawn")

    state, compiled, init_s, compile_s = start(program, seed, key, checked[0])
    mem = footprint(compiled)
    log(f"state initialised ({init_s:.2f} s), weights loaded, step "
        f"compiled; its footprint, bytes: {mem}")
    feed = Feed(compiled if fault is None else fault(compiled), state)
    mine, first_step_s = checked_steps(program, feed, key, checked)
    first_step_s += compile_s
    log(f"checked steps (compile and first step {first_step_s:.2f} s)")
    for i in range(tr["warmup_steps"]):
        feed.step(window[i % len(window)])
    log("warm-up")
    setup_s = time.time() - t0

    dispatch, failed = [], 0
    compiles.on = True
    t = time.perf_counter()
    while True:
        loss, d = feed.step(window[len(dispatch) % len(window)])
        dispatch.append(d)
        failed += not math.isfinite(loss)
        if time.perf_counter() - t >= seconds:
            break
    window_s = time.perf_counter() - t
    compiles.on = False
    log(f"window: {len(dispatch)} steps in {window_s:.3f} s, "
        f"{compiles.count} programs compiled or loaded")

    reduced = None
    if trace:
        reduced = traced(feed, window, tr["trace_steps"])
        log("traced steps reduced")
    devices = jax.devices()[:cell.chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    free(feed.state)
    feed = None

    want = cell.module("reference").readings(cell.cfg, opt, key, checked,
                                             devices=devices)
    log(f"reference; leaves left out of update_gap: {correct.still(want)}")
    checked_numbers = correct.checks(correct.gaps(mine, want), cell.limits)
    ok = correct.passed(checked_numbers) and failed == 0

    r = Run(chips=cell.chips, tokens_per_step=cell.tokens_per_step,
            flops_per_step=cell.tokens_per_step * cell.module("flops")
            .flops_per_token(cell.cfg, tr["seq"]),
            peak_flops=peak, setup_s=setup_s, init_s=init_s,
            first_step_s=first_step_s,
            window_s=window_s, window_steps=len(dispatch),
            dispatch_s=dispatch,
            peak_hbm_bytes=max(max(peaks), mem["footprint"]),
            trace=reduced, trace_steps=tr["trace_steps"])
    metrics = {}
    for m in cell.metrics:
        v = importlib.import_module(f"chipbench.metrics.{m['name']}").read(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = devices[0]
    line = {"correct": ok, "attempted": len(dispatch), "failed": failed,
            "metrics": metrics,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": max(peaks)}}
    if reduced is not None:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["checks"] = checked_numbers
    return line


def traced(feed: Feed, window: list, steps: int) -> dict | None:
    """``steps`` more steps under the profiler, reduced to device numbers."""
    import jax
    from jax.profiler import TraceAnnotation

    d = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(d)
        with TraceAnnotation(tracing.WINDOW_SPAN):
            for i in range(steps):
                feed.step(window[i % len(window)])
        jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        return tracing.reduce(tracing.load(path))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main(argv=None, *, t0: float) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cellmod.load(args.workload, trace=bool(args.trace))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    line = run(cell, args.seed, args.seconds, bool(args.trace), t0=t0,
               peak=peak_flops(devices[0].device_kind))
    for k, c in line["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0
