"""Every cell of ``BENCHMARK.json`` rehearsed on the CPU with the benchmark's
own command: what tells a PR, before the chip does, that it broke a cell.
Reads ``benchmark/`` and edits nothing there; its own tests (the references,
the readers of a trace) are ``benchmark/tests/``."""

import ast
import concurrent.futures
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(_REPO, "BENCHMARK.json")) as _f:
    _TABLE = json.load(_f)

# rehearsals at a time: each is one process of a few cores and at most a few
# GiB, and one after another they take over ten minutes
_AT_ONCE = max(1, min(4, (os.cpu_count() or 1) // 2))


def _rehearse(cell):
    command = list(_TABLE["command"])
    if command[0].startswith("python"):
        command[0] = sys.executable
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the rehearsal sizes its own virtual devices
    return subprocess.run(
        [*command, "--workload", cell, "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.fixture(scope="module")
def rehearsals(request):
    """The selected cells' rehearsals, started in the tests' order on a pool
    of ``_AT_ONCE`` as the first of them asks: each test waits for its own."""
    cells = [item.callspec.params["cell"] for item in request.session.items
             if item.module is request.module]
    with concurrent.futures.ThreadPoolExecutor(_AT_ONCE) as pool:
        yield {cell: pool.submit(_rehearse, cell) for cell in cells}


@pytest.mark.parametrize("cell", [w["name"] for w in _TABLE["workloads"]])
def test_cell_rehearses_on_the_cpu(cell, rehearsals):
    proc = rehearsals[cell].result()
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    declared = {m["name"] for m in _TABLE["end_to_end"]}
    assert set(result["metrics"]) == declared
    # a CPU number is never printed under the name of a device metric
    assert all(m["value"] is None for m in result["metrics"].values())
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    (verdict,) = [ln for ln in lines if ln.startswith("[bench] correct: ")]
    verdict = ast.literal_eval(verdict[len("[bench] correct: "):])
    assert verdict["nothing_failed"] is True, verdict
    assert verdict["no_compile_in_window"] is True, verdict
