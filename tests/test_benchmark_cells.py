"""Every cell of ``BENCHMARK.json`` rehearsed on the CPU with the benchmark's
own command: what tells a PR, before the chip does, that it broke a cell.
Reads ``benchmark/`` and edits nothing there; its own tests (the references,
the readers of a trace) are ``benchmark/tests/``."""

import ast
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(_REPO, "BENCHMARK.json")) as _f:
    _TABLE = json.load(_f)


@pytest.mark.parametrize("cell", [w["name"] for w in _TABLE["workloads"]])
def test_cell_rehearses_on_the_cpu(cell):
    command = list(_TABLE["command"])
    if command[0].startswith("python"):
        command[0] = sys.executable
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the rehearsal sizes its own virtual devices
    proc = subprocess.run(
        [*command, "--workload", cell, "--seed", "3", "--seconds", "1",
         "--trace", "0", "--rehearse"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300,
    )
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
