"""The benchmark's own tests run on the CPU, with four virtual devices for
the mesh cases.  They live outside the repository's ``tests/`` and run
directly: ``python -m pytest chipbench/tests``."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
