# NOTE: deliberately NO --xla_force_host_platform_device_count here --
# smoke tests and benches must see 1 device (the dry-run sets its own flags
# as the first lines of repro.launch.dryrun).  Multi-device tests spawn
# subprocesses (see tests/helpers/).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# tests/ itself, for the _hypothesis_compat shim (real hypothesis when
# installed, deterministic fallback runner otherwise)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

# The float64 reference engine is the tests' oracle; `import repro` leaves
# JAX's 64-bit mode off (device code is float32), so the tests turn it on.
jax.config.update("jax_enable_x64", True)
