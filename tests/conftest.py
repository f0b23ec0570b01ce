"""Test env: force an 8-device virtual CPU mesh before JAX initializes.

This is the SURVEY §4 obligation: the reference exercises its whole distributed
protocol as multiple processes on localhost; we exercise ours on 8 virtual CPU
devices so sync/async semantics, sharding, recovery, and checkpointing are
testable without TPU hardware.
"""

import os
import sys

# Force CPU even when a real TPU is attached: tests validate *semantics* on an
# 8-device virtual mesh; the benchmark (perfbench/run.py) uses the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Keep compilation fast and deterministic on CPU.
os.environ.setdefault("JAX_ENABLE_X64", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Runtime lock-order assertions (ISSUE 10, docs/static_analysis.md): with
# DTF_LOCKCHECK=1 every lock created from here on is order-checked, and
# the session fails if any AB/BA inversion was observed — the chaos CI
# leg runs under this (ci.sh).  A no-op otherwise.
if os.environ.get("DTF_LOCKCHECK") == "1":
    from distributed_tensorflow_tpu.utils import lockcheck as _lockcheck

    _lockcheck.install()

    def pytest_sessionfinish(session, exitstatus):
        try:
            _lockcheck.assert_clean()
        except AssertionError as e:
            print(str(e), file=sys.stderr)
            session.exitstatus = 3
