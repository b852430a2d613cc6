"""L8 end-to-end: the launcher takes 2 simulated hosts from nothing to a
finished multi-host CifarApp run (reference role: ``ec2/spark_ec2.py`` +
``SETUP.md`` — provision/wire/submit).

This drives ``tools/launch.py`` itself as a subprocess (the exact user
command from SETUP.md §0), which spawns 2 processes x 2 virtual CPU
devices, joins them via ``jax.distributed``, and runs the real CifarApp
averaging loop on a global dp=4 mesh with per-host data sharding.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_launcher_two_host_cifar(tmp_path):
    from sparknet_tpu.data.cifar import CifarLoader

    data_dir = str(tmp_path / "cifar")
    CifarLoader.write_synthetic(data_dir, num_train=1200, num_test=300)

    env = {
        **os.environ,
        "PYTHONPATH": _REPO,
        "JAX_PLATFORMS": "cpu",
        # route each host's TrainingLog into this test's tmpdir (the
        # conftest session default would otherwise swallow them)
        "SPARKNET_LOG_DIR": str(tmp_path),
    }
    cmd = [
        sys.executable,
        "-m",
        "sparknet_tpu.tools.launch",
        "--nprocs=2",
        "--devices_per_host=2",
        "cifar",
        f"--data={data_dir}",
        "--rounds=3",
        "--tau=2",
        "--batch=50",
        "--test_every=2",
    ]
    out = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=900,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stdout + out.stderr

    # both hosts trained all rounds; host 0 echoed the final accuracy
    assert "final accuracy" in out.stdout, out.stdout
    for r in range(3):
        assert f"round {r} trained" in out.stdout, out.stdout
    # a test pass ran with a real (finite, sane) accuracy on 10 classes
    accs = [
        float(line.rsplit(None, 1)[-1])
        for line in out.stdout.splitlines()
        if "final accuracy" in line
    ]
    assert accs and all(0.0 <= a <= 1.0 for a in accs), accs
    # per-host training logs were written into the cwd
    logs = [f for f in os.listdir(tmp_path) if f.startswith("training_log_")]
    assert len(logs) >= 1, logs


# ---------------------------------------------------------------------------
# Provisioning actions (spark_ec2.py launch/destroy/login analog): the plan
# is a pure function, so the exact gcloud sequence is asserted without a
# cloud project, and --dry-run must emit exactly that sequence.


def _run_launch(args):
    out = subprocess.run(
        [sys.executable, "-m", "sparknet_tpu.tools.launch", *args],
        env={**os.environ, "PYTHONPATH": _REPO, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    return out


def test_spawn_local_fleet_collector_wires_ship_to(monkeypatch, capsys):
    """--fleet_collector: the launcher starts the collector, appends
    --ship_to=<its url> to every simulated host's app argv, gives each
    host a stable SPARKNET_HOST_ID, and prints the end-of-run fleet
    summary (no child processes actually spawned here)."""
    import io
    import types

    from sparknet_tpu.tools import launch

    spawned = []

    class FakeProc:
        returncode = 0

        def __init__(self, cmd, env=None, **kw):
            spawned.append((cmd, env))
            self.stdout = io.StringIO("")

        def wait(self, timeout=None):
            return 0

        def poll(self):
            return 0

    monkeypatch.setattr(launch.subprocess, "Popen", FakeProc)
    args = types.SimpleNamespace(
        nprocs=2, devices_per_host=1, app="cifar", timeout=5,
        fleet_collector="127.0.0.1:0",
    )
    rc = launch.spawn_local(args, ["--rounds=1"])
    assert rc == 0
    assert len(spawned) == 2
    for pid, (cmd, env) in enumerate(spawned):
        ship = [a for a in cmd if a.startswith("--ship_to=")]
        assert ship and ship[0].startswith("--ship_to=http://127.0.0.1:")
        assert env["SPARKNET_HOST_ID"] == f"host{pid}"
    out = capsys.readouterr().out
    assert "fleet collector on" in out
    assert "fleet summary" in out


def test_provision_dry_run_emits_exact_sequence():
    out = _run_launch([
        "provision", "--dry-run", "--name=sparknet-v5e",
        "--zone=us-west4-8a", "--accelerator=v5litepod-8",
        "--repo=/root/repo", "--spot",
    ])
    assert out.returncode == 0, out.stderr
    lines = out.stdout.strip().splitlines()
    assert lines == [
        "gcloud compute tpus tpu-vm create sparknet-v5e "
        "--zone us-west4-8a --accelerator-type v5litepod-8 "
        "--version tpu-ubuntu2204-base --spot",
        "gcloud compute tpus tpu-vm describe sparknet-v5e "
        "--zone us-west4-8a '--format=value(state)'",
        "gcloud compute tpus tpu-vm ssh sparknet-v5e --zone us-west4-8a "
        "--worker=all --command 'rm -rf ~/sparknet_tpu'",
        "gcloud compute tpus tpu-vm scp --recurse /root/repo "
        "'sparknet-v5e:~/sparknet_tpu' --zone us-west4-8a --worker=all",
    ]


def test_teardown_and_run_dry_run():
    out = _run_launch([
        "teardown", "--dry-run", "--name=c1", "--zone=z1",
    ])
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == (
        "gcloud compute tpus tpu-vm delete c1 --zone z1 --quiet"
    )

    out = _run_launch([
        "run", "--dry-run", "--name=c1", "--zone=z1", "--",
        "imagenet", "--rounds=100",
    ])
    assert out.returncode == 0, out.stderr
    line = out.stdout.strip()
    assert line.startswith("gcloud compute tpus tpu-vm ssh c1 --zone z1 "
                           "--worker=all --command ")
    assert "python -m sparknet_tpu.tools.launch imagenet --rounds=100" in line


def test_provision_plan_pure_function():
    from sparknet_tpu.tools import provision

    opts = provision.make_parser().parse_args(
        ["--name=n", "--zone=z", "--project=p"]
    )
    plan = provision.command_plan("describe", opts)
    assert plan == [[
        "gcloud", "--project", "p", "compute", "tpus", "tpu-vm",
        "describe", "n", "--zone", "z",
    ]]
    ssh = provision.command_plan("ssh", opts)
    assert ssh[0][-1] == "--worker=0"
