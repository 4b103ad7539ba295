"""benchmark/tests: rehearsals and unit tests of the benchmark itself,
run by hand (``python -m pytest benchmark/tests -q`` from the root).

The CPU backend with four virtual devices, set before jax is imported.
The command has no option that lets it run without a chip; the test-only
path is here: a ``Bench`` built on the CPU's devices around a cell whose
widths the test cuts, handed to the kind's ``run`` directly
(``rehearsal.py``).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
