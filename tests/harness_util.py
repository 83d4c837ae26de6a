"""Shared runner for the subprocess correctness harnesses.

The multi-device checks (dist_harness.py, comm_harness.py) run in child
processes so the main pytest process keeps its own device configuration;
this is the one place the child environment and JSON-output parsing live.
:func:`stored_carry` gives harnesses and tests the stored-carry reference.
"""

from __future__ import annotations

import contextlib
import json
import pathlib
import subprocess
import sys


def run_harness(script: pathlib.Path, timeout: int = 1500) -> dict:
    """Execute a harness script and return its parsed JSON result dict."""
    proc = subprocess.run(
        [sys.executable, str(script)],
        capture_output=True, text=True, timeout=timeout,
        cwd=str(script.parent.parent),
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": str(pathlib.Path.home()), "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    return json.loads(out[out.index("{"):])


@contextlib.contextmanager
def stored_carry():
    """Route every pool a training step would re-gather (``'remat'``) to
    the stored carry instead, as an enc-dec decoder pool is: the reference
    the re-gathering step is measured against.  Patches
    ``models.lm.pool_route``, which the step, the memory planner and the
    traffic model all consult; wrap the step's lowering and calls too."""
    from repro.models import lm

    route = lm.pool_route

    def stored(*args, **kw):
        r = route(*args, **kw)
        return "stored" if r == "remat" else r

    lm.pool_route = stored
    try:
        yield
    finally:
        lm.pool_route = route
