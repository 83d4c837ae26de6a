"""chip_smoke.py: its phases run end to end on the CPU at a tiny size, its
checks catch a run that did not train, and without a TPU — or without the
rest of the repo — it fails and prints no ``ok`` line."""

import importlib.util
import math
import pathlib
import shutil
import subprocess
import sys

import pytest

from harness_util import run_harness
from repro.runtime.train_loop import LoopStats
from tiny_bert import TINY, tiny_bert

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_script(cwd: pathlib.Path, script: pathlib.Path):
    return subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=300, cwd=str(cwd),
        env={"PATH": "/usr/bin:/bin", "HOME": str(pathlib.Path.home()),
             "JAX_PLATFORMS": "cpu"})


def test_one_chip_phase_trains():
    assert _load().one_chip_phase(tiny_bert(), **TINY) == []


def test_four_chip_phase_matches_reference():
    out = run_harness(pathlib.Path(__file__).parent / "chip_smoke_harness.py")
    assert out["fails"] == []


def _stats(losses, restarts=0, save_failures=0):
    return LoopStats(list(losses), [0.0] * len(losses), [], restarts,
                     save_failures=save_failures)


UNIFORM = math.log(4096)


@pytest.mark.parametrize("stats,needle", [
    (_stats([UNIFORM, math.nan]), "non-finite"),
    (_stats([UNIFORM, UNIFORM + 0.1]), "did not fall"),
    (_stats([2 * UNIFORM, UNIFORM]), "not within 10%"),
    (_stats([UNIFORM]), "1 losses for 2 steps"),
    (_stats([UNIFORM, UNIFORM - 1], restarts=1), "restarts"),
    (_stats([UNIFORM, UNIFORM - 1], save_failures=1), "save failures"),
])
def test_training_failures_flag(stats, needle):
    fails = _load().training_failures(stats, 2, 4096)
    assert any(needle in f for f in fails), fails


def test_training_failures_pass_a_good_run():
    assert _load().training_failures(
        _stats([UNIFORM, UNIFORM - 1]), 2, 4096) == []


def test_refuses_without_tpu():
    proc = _run_script(ROOT, SCRIPT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    proc = _run_script(tmp_path, tmp_path / SCRIPT.name)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
