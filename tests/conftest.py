"""Test harness: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding is validated the way SURVEY.md §4 prescribes for a
single-host environment: ``--xla_force_host_platform_device_count=8`` gives
jax 8 CPU devices, so every pjit/shard_map path compiles and executes with a
real (virtual) mesh.  The mesh is forced here, before any test touches a
device: XLA parses the device-count flag once per process.
"""

import os
import sys
import tempfile

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# route TrainingLog output (tests that run apps/cli in-process or as
# subprocesses) into a per-session tmpdir instead of littering the repo
# root with training_log_*.txt; tests that pass directory=/path= still
# win over the env default
os.environ.setdefault(
    "SPARKNET_LOG_DIR", tempfile.mkdtemp(prefix="sparknet_test_logs_")
)

# repo-hygiene baseline, captured BEFORE any test runs: tier-1 must not
# add training_log_*.txt at the repo root (the PR-4 tmpdir-routing
# regression guard in test_bench_smoke.py compares against this set)
import glob as _glob  # noqa: E402

REPO_ROOT_TRAINING_LOGS = frozenset(
    os.path.basename(p)
    for p in _glob.glob(os.path.join(_REPO_ROOT, "training_log_*.txt"))
)

from sparknet_tpu.utils.devices import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)
