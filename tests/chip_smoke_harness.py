"""chip_smoke.py's --four-chips phase at a tiny size, in a subprocess with 4
virtual CPU devices: the repl=2 x shard=2 mesh run and its one-device
reference, through the same code the chip runs at full width.

Prints one JSON object: ``{"fails": [...]}`` (empty when every check of
the phase holds); tests/test_chip_smoke.py asserts on it.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 "
    + os.environ.get("XLA_FLAGS", "")
)

import contextlib
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(chip_smoke)

from tiny_bert import TINY, tiny_bert  # noqa: E402

with contextlib.redirect_stdout(sys.stderr):   # keep stdout one JSON object
    fails = chip_smoke.four_chip_phase(tiny_bert(), **TINY)
print(json.dumps({"fails": fails}))
