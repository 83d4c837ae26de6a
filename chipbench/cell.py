"""A benchmark cell, found by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic file and the limits of its comparison."""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    cfg: dict         # the configuration as it is run
    traffic: dict     # batch, sequence, micro-steps, mesh, data, optimizer
    limits: dict      # {number: limit} of the comparison that decides correct
    metrics: tuple    # the BENCHMARK.json metric entries this cell reports

    @property
    def family(self) -> str:
        return self.cfg["family"]

    def module(self, kind: str):
        """The family's module of a kind: program, reference or flops."""
        return importlib.import_module(f"chipbench.{kind}.{self.family}")

    def opt(self) -> dict:
        """The optimizer as stated, with the peak rate scaled by width."""
        opt = dict(self.traffic["optimizer"])
        opt["lr"] = opt.pop("lr_x_width") / self.cfg["d_model"]
        return opt

    @property
    def tokens_per_step(self) -> int:
        return self.traffic["global_batch"] * self.traffic["seq"]


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load(name: str, bench_path: pathlib.Path = ROOT / "BENCHMARK.json",
         *, trace: bool = False) -> Cell:
    """The cell ``name`` of ``bench_path``, with the metrics it reports in a
    run with (``trace``) or without the profiler."""
    bench = _json(bench_path)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in {bench_path}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = _json(ROOT / conf["file"])
    traffic = _json(HERE / "traffic" / f"{w['traffic']}.json")
    if traffic["chips"] != w["chips"]:
        raise ValueError(f"{name}: traffic {w['traffic']} is for "
                         f"{traffic['chips']} chips, the cell asks for "
                         f"{w['chips']}")
    limits = _json(HERE / "limits" / f"{name}.json")["limits"]
    kind = "per_layer" if trace else "end_to_end"
    metrics = tuple(m for m in bench[kind]
                    if name in m.get("workloads", (name,)))
    return Cell(name, w["chips"], cfg, traffic, limits, metrics)
