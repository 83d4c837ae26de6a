"""Run one cell as ``run.py --trace 1`` does, and keep what its scopes say.
Not part of a benchmark run.

    python3 chipbench/scoped_run.py --workload <cell> --seed <n> \
        --seconds <s> --out <dir>

It drives ``harness.run`` as it stands, with two of its seams replaced:

* the step compiles with ``jax_compilation_cache_include_metadata_in_key``
  on.  JAX's default cache key strips debug info, so an entry compiled from
  a build without the program's scopes would otherwise be loaded, and its
  HLO would name none;
* the traced steps keep their trace, and the compiled step's instruction
  names are mapped to their ``op_name``s (``scoped.op_names``).

It prints the run's result line with ``scoped`` added: the traced window's
device time by scope (``scoped.reduce``), in ms per traced step.  Into
``--out`` it writes ``<cell>.scoped.json`` (that line, and the scoped
numbers in seconds), ``<cell>.xplane.pb`` (the trace, trimmed as
``tests/trim_trace.py`` trims it where TensorFlow's protobuf can be
imported, else whole) and ``<cell>.op_names.json`` (the op name of each
operation in that trace).  Where the compiled step names none of the known
scopes, ``scoped`` is absent and standard error says why.
"""

import time

T0 = time.time()   # set-up is timed from here, as in run.py

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, scoped, tracing  # noqa: E402

METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


def _log(msg: str) -> None:
    print(f"scoped_run: {msg}", file=sys.stderr)


def _keep(src: str, dst: pathlib.Path) -> None:
    try:
        from chipbench.tests.trim_trace import trim
    except ImportError as e:
        _log(f"trace kept whole, not trimmed ({e})")
        shutil.copyfile(src, dst)
        return
    trim(src, str(dst))


def _event_names(ev: tracing.Events) -> set[str]:
    return {n for lines in (ev.device, ev.device_async)
            for events in lines.values() for n, _, _ in events}


def record(cell, seed: int, seconds: float, out: pathlib.Path, *,
           t0: float, peak: float) -> dict:
    """One traced run of ``cell``; returns its result line, with
    ``scoped`` where the trace and the compiled step give it."""
    import jax

    kept: dict = {}
    start, traced = harness.start, harness.traced

    def start_keyed(*args):
        jax.config.update(METADATA_IN_KEY, True)
        try:
            return start(*args)
        finally:
            jax.config.update(METADATA_IN_KEY, False)

    def traced_kept(feed, window, steps):
        from jax.profiler import TraceAnnotation

        names = scoped.op_names(feed.step_fn.as_text())
        d = tempfile.mkdtemp(prefix="chipbench_trace_")
        try:
            jax.profiler.start_trace(d)
            with TraceAnnotation(tracing.WINDOW_SPAN):
                for i in range(steps):
                    feed.step(window[i % len(window)])
            jax.profiler.stop_trace()
            path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
            ev = tracing.load(path)
            dst = out / f"{cell.name}.xplane.pb"
            _keep(path, dst)
            seen = _event_names(tracing.load(str(dst)))
            with open(out / f"{cell.name}.op_names.json", "w") as f:
                json.dump({n: op for n, op in sorted(names.items())
                           if n in seen}, f, indent=0)
            if not scoped.carries_scopes(names):
                _log("the compiled step names none of the known scopes; "
                     "no scoped numbers")
            elif (kept_s := scoped.reduce(ev, names)) is None:
                _log("no device operation in the traced window")
            else:
                kept["scoped"] = kept_s
            return tracing.reduce(ev)
        finally:
            shutil.rmtree(d, ignore_errors=True)

    harness.start, harness.traced = start_keyed, traced_kept
    try:
        line = harness.run(cell, seed, seconds, True, t0=t0, peak=peak)
    finally:
        harness.start, harness.traced = start, traced
    s = kept.get("scoped")
    if s is not None:
        steps = cell.traffic["trace_steps"]
        line["scoped"] = {
            "ms_per_step": {**{k: v / steps * 1e3
                               for k, v in s["scope_s"].items()},
                            "unscoped": s["unscoped_s"] / steps * 1e3},
            "exposed_ms_per_step": {k: v / steps * 1e3
                                    for k, v in s["exposed_s"].items()},
            "exposed_comm_ms_per_step": s["exposed_comm_s"] / steps * 1e3,
            "busy_ms_per_step": s["busy_s"] / steps * 1e3,
        }
    with open(out / f"{cell.name}.scoped.json", "w") as f:
        json.dump({"line": line, "scoped_s": s}, f, indent=1)
    if s is not None:
        for k, v in line["scoped"]["exposed_ms_per_step"].items():
            _log(f"exposed collective ms/step under {k}: {v:.4f}")
        for n, t, sc in s["ops"]:
            _log(f"top op {n}: {t / steps * 1e3:.3f} ms/step, {sc}")
        for n, t in s["unscoped_ops"]:
            _log(f"unscoped op {n}: {t / steps * 1e3:.3f} ms/step")
    return line


def main(argv=None) -> int:
    from chipbench import cell as cellmod

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = cellmod.load(args.workload, trace=True)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        _log(f"{cell.name} needs {cell.chips} TPU chips, JAX found "
             f"{len(devices)} {devices[0].platform}")
        return 2
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    line = record(cell, args.seed, args.seconds, out, t0=T0,
                  peak=harness.peak_flops(devices[0].device_kind))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
