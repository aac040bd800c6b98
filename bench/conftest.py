"""Test set-up for ``python -m pytest bench/tests``, loaded before any test
module imports JAX: the host's CPU is split into four devices, so a
four-chip cell's tiny case runs its mesh on the CPU (``cpu_chips`` in
``bench/tests/conftest.py`` hands a run the first devices it asks for)."""
import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=4").strip()
