"""CPU rehearsal of the benchmark: four host devices, the harness and the
program on the import path. Run from the repository's root:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        f"{_flags} --xla_force_host_platform_device_count=4".strip())

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
