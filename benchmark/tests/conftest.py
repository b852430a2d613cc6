"""CPU tests of the benchmark: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`.

Nothing here touches a TPU library or describes a topology; the cells run as
rehearsals (``run.py --rehearse``: tiny sizes, virtual CPU devices, every
metric null), one subprocess per cell, shared by the tests of a session."""

import functools
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELLS = ["caffenet-train", "caffenet-hostfed", "caffenet-dp4", "resnet50-train"]


def run_cell(*args, cwd=ROOT):
    """Run the benchmark's command; returns (exit code, stdout, stderr)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    with open(os.path.join(cwd, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    command = [sys.executable if command[0].startswith("python") else command[0],
               *command[1:]]
    proc = subprocess.run(
        [*command, *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=900,
    )
    return proc.returncode, proc.stdout, proc.stderr


class Rehearsal:
    def __init__(self, cell, trace=0):
        self.rc, self.stdout, self.stderr = run_cell(
            "--workload", cell, "--seed", "3", "--seconds", "1",
            "--trace", str(trace), "--rehearse",
        )
        lines = self.stdout.strip().splitlines()
        self.result = json.loads(lines[-1]) if lines else None
        self.notes = [ln for ln in lines if ln.startswith("[bench] ")]

    def note(self, prefix):
        """The free-form line that starts with ``prefix``, as Python data where
        it holds a dict."""
        (line,) = [ln for ln in self.notes if ln.startswith("[bench] " + prefix)]
        return line[len("[bench] " + prefix):]


@functools.lru_cache(maxsize=None)
def rehearse(cell, trace=0):
    """One rehearsal a cell and session, whichever test asks first."""
    return Rehearsal(cell, trace)


@pytest.fixture(params=CELLS)
def rehearsal(request):
    return request.param, rehearse(request.param)
