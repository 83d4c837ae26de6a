"""Readings that set a cell's limits: the program's on many seeds, the
control's and the planted faults' on a few.  Not part of a benchmark run.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,...,12 \
        --control-seeds 1,2,3 --out chiprun_out/calib_<cell>.json

One process builds the program's step once and drives it through the
checked steps from each seed (the timed path's own call and feed), then,
with the program's state freed, runs the plain reference on each seed, the
control (the reference in 8-bit floats, ``reference.FP8``) and the
reference with half of each micro-batch left out (the mean taken over the
rest) on the control seeds.  It prints, and writes to ``--out``, every gap
the comparison reads; the lower reading of a number is the largest over
the program's seeds, the upper the smallest over the control's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

T0 = time.time()


def half_batch(batches: list) -> list:
    """Each micro-batch with its second half of rows masked out."""
    out = []
    for b in batches:
        mask = b["mask"].copy()
        mask[:, mask.shape[1] // 2:] = 0.0
        out.append({**b, "mask": mask})
    return out


def program_readings(program, cell, seeds, log) -> tuple[dict, dict]:
    """The program's readings from each seed, and each seed's batches."""
    from chipbench import harness

    mine, checked = {}, {}
    for s in seeds:
        checked[s], _ = harness.batches(cell, s)
        key = harness.seed_key(s)
        state, compiled, *_ = harness.start(program, s, key, checked[s][0])
        feed = harness.Feed(compiled, state)
        mine[s], _ = harness.checked_steps(program, feed, key, checked[s])
        harness.free(feed.state)
        log(f"program, seed {s}: losses {mine[s]['losses']}")
    return mine, checked


def main(argv=None) -> int:
    import pathlib

    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root), str(root / "src")]
    from chipbench import cell as cellmod
    from chipbench import correct, harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl_seeds = [int(s) for s in args.control_seeds.split(",")]
    cell = cellmod.load(args.workload)

    import jax

    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = harness.Phases(T0)
    opt = cell.opt()
    program = cell.module("program").Program(cell.cfg, cell.traffic, opt)
    mine, checked = program_readings(program, cell, seeds, log)
    ref = cell.module("reference")
    out = {"workload": cell.name, "program": {}, "control": {},
           "half_batch": {}}
    devices = jax.devices()[:cell.chips]
    for s in seeds:
        key = harness.seed_key(s)
        want = ref.readings(cell.cfg, opt, key, checked[s], devices=devices)
        out["program"][s] = correct.gaps(mine[s], want)
        log(f"reference, seed {s}: program {out['program'][s]}")
        if s in ctl_seeds:
            ctl = ref.readings(cell.cfg, opt, key, checked[s], ref.FP8,
                               devices)
            out["control"][s] = correct.gaps(ctl, want)
            half = ref.readings(cell.cfg, opt, key, half_batch(checked[s]),
                                devices=devices)
            out["half_batch"][s] = correct.gaps(half, want)
            log(f"control {out['control'][s]} half {out['half_batch'][s]}")
    out["lower"] = {k: max(g[k] for g in out["program"].values())
                    for k in correct.NUMBERS}
    for kind in ("control", "half_batch"):
        out[f"{kind}_min"] = {k: min(g[k] for g in out[kind].values())
                              for k in correct.NUMBERS}
    text = json.dumps(out, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
