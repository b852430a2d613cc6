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
# regression guard in test_io_and_utils.py compares against this set)
import glob as _glob  # noqa: E402

REPO_ROOT_TRAINING_LOGS = frozenset(
    os.path.basename(p)
    for p in _glob.glob(os.path.join(_REPO_ROOT, "training_log_*.txt"))
)

from sparknet_tpu.utils.devices import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _no_span_sink_outlives_its_test_file():
    """``obs.span``'s sinks are process globals, and a worker runs many test
    files in an order the scheduler picks: a file that leaves one installed
    (the process-wide training metrics wire the phase observer and several
    files never drop them) turns ``span()`` on for whichever file comes next,
    whose off-path tests (``span() is _NULL_SPAN``) then fail by luck of the
    draw.  Every file ends with all five sinks off."""
    yield
    from sparknet_tpu import obs
    from sparknet_tpu.obs import trace

    obs._reset_training_metrics_for_tests()  # phase observer, ship, flight, profiler
    trace.uninstall_tracer()
    trace.set_span_observer(None)
