"""Two-process jax.distributed harness, shared by the CI test
(``tests/test_multihost.py``) and the driver dryrun
(``__graft_entry__.dryrun_multichip`` mode 4) so the bring-up scaffolding
— port probe, forced-CPU env, spawn/reap/cleanup — and the toy averaging
worker itself have exactly one copy.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

# The canonical 2-process averaging worker: joins via jax.distributed,
# builds one global dp=4 mesh, runs a real ParameterAveragingTrainer
# round, asserts finite per-worker losses and post-averaging parameter
# agreement across this process's local shards, prints "<marker> p<pid>".
_TOY_AVERAGING_WORKER = r"""
import sys
import numpy as np

pid, port = int(sys.argv[1]), sys.argv[2]

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from sparknet_tpu import config
from sparknet_tpu.parallel import ParameterAveragingTrainer
from sparknet_tpu.parallel.mesh import initialize_distributed, make_mesh
from sparknet_tpu.solver import Solver

initialize_distributed(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()
assert jax.local_device_count() == 2

# fleet-plane wiring: with SPARKNET_SHIP_TO set (the dryrun's fleet
# leg) each process ships metric deltas + round spans to one collector
import os as _os

_run_obs = None
if _os.environ.get("SPARKNET_SHIP_TO"):
    from sparknet_tpu import obs as _obs

    _run_obs = _obs.start(
        ship_to=_os.environ["SPARKNET_SHIP_TO"],
        host_id=_os.environ.get("SPARKNET_HOST_ID", f"proc{pid}"),
        echo=None,
    )

NET = '''
name: "toy"
layer { name: "data" type: "HostData" top: "x" top: "label"
  java_data_param { shape { dim: 4 dim: 6 } shape { dim: 4 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "logits"
  inner_product_param { num_output: 3 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
  bottom: "label" top: "loss" }
'''
sp = config.parse_solver_prototxt(
    'base_lr: 0.05 lr_policy: "fixed" momentum: 0.9'
)
solver = Solver(sp, net_param=config.parse_net_prototxt(NET))
mesh = make_mesh({"dp": 4})
trainer = ParameterAveragingTrainer(solver, mesh)
state = trainer.init_state(seed=0)

rng = np.random.RandomState(0)  # same data on both processes
full = {
    "x": rng.randn(4, 2, 4, 6).astype(np.float32),
    "label": rng.randint(0, 3, (4, 2, 4)).astype(np.float32),
}
sharding = NamedSharding(mesh, P("dp"))
batches = {
    k: jax.make_array_from_callback(
        v.shape, sharding, lambda idx, v=v: v[idx]
    )
    for k, v in full.items()
}
state, losses = trainer.round(state, batches)
local = np.concatenate(
    [np.asarray(s.data) for s in losses.addressable_shards], axis=0
)
assert np.isfinite(local).all(), local
# post-averaging: this process's local shards of every param must agree
for key, blobs in state.params.items():
    for blob in blobs:
        shards = [np.asarray(s.data) for s in blob.addressable_shards]
        np.testing.assert_allclose(shards[0], shards[1], rtol=1e-6)
if _run_obs is not None:
    _run_obs.close()  # final flush ships the run's tail
print(f"@MARKER@ p{pid} smoothed={solver.smoothed_loss:.4f}")
"""


def toy_averaging_worker(marker: str) -> str:
    return _TOY_AVERAGING_WORKER.replace("@MARKER@", marker)


# Fleet-shipping worker: a real single-device training loop (tiny
# InnerProduct net, per-round ``execute`` spans carrying the absolute
# round) that ships its metric deltas + run-log events to the collector
# named by SPARKNET_SHIP_TO — the per-process half of the fleet e2e
# proof (tests/test_fleet.py, tests/test_membership.py).  Env knobs (all
# optional) shape the fleet scenario WITHOUT touching the harness:
# SPARKNET_FLEET_ROUNDS / _ROUND_S (clock-paced rounds), _LINGER_S (keep
# the shipper heartbeating after the loop so a peer's lag verdict can be
# observed against a live fleet).  Needs no cross-process collectives, so
# it runs on any CPU jax build.
_FLEET_SHIP_WORKER = r"""
import os
import sys
import time

import numpy as np

pid = int(sys.argv[1])

from sparknet_tpu import config, obs
from sparknet_tpu.solver import Solver

NET = '''
name: "fleet_toy"
layer { name: "data" type: "HostData" top: "x" top: "label"
  java_data_param { shape { dim: 4 dim: 6 } shape { dim: 4 } } }
layer { name: "ip1" type: "InnerProduct" bottom: "x" top: "logits"
  inner_product_param { num_output: 3 weight_filler { type: "xavier" } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "logits"
  bottom: "label" top: "loss" }
'''

rounds = int(os.environ.get("SPARKNET_FLEET_ROUNDS", "5"))
round_s = float(os.environ.get("SPARKNET_FLEET_ROUND_S", "0.02"))
linger_s = float(os.environ.get("SPARKNET_FLEET_LINGER_S", "0"))

run = obs.start(
    ship_to=os.environ["SPARKNET_SHIP_TO"],
    host_id=os.environ.get("SPARKNET_HOST_ID", f"host{pid}"),
    echo=None,
)
sp = config.parse_solver_prototxt(
    'base_lr: 0.05 lr_policy: "fixed" momentum: 0.9'
)
solver = Solver(sp, net_param=config.parse_net_prototxt(NET))
state = solver.init_state(seed=pid)
rng = np.random.RandomState(pid)


def window():
    return {
        "x": rng.randn(1, 4, 6).astype(np.float32),
        "label": rng.randint(0, 3, (1, 4)).astype(np.float32),
    }


for r in range(rounds):
    with obs.span("execute", round=r):
        state, losses = solver.step(state, window())
    run.shipper.note_round(r)
    time.sleep(round_s)
print(f"@MARKER@ p{pid} rounds={rounds} loss={solver.smoothed_loss:.4f}")
sys.stdout.flush()
if linger_s:
    # loop done; keep the shipper heartbeating (a finished-but-alive
    # host) until the harness kills us or the linger expires
    time.sleep(linger_s)
run.close()
"""


def fleet_ship_worker(marker: str) -> str:
    return _FLEET_SHIP_WORKER.replace("@MARKER@", marker)


def run_two_process_round(
    worker_src: str,
    marker: str,
    repo_root: str,
    devices_per_process: int = 2,
    timeout: int = 600,
    env_extra: Optional[Dict[str, str]] = None,
) -> List[str]:
    """Spawn two workers running ``worker_src`` (argv: pid, port) on
    forced-CPU virtual devices; assert both exit 0 and print
    ``<marker> p<pid>``; return the outputs.  ``env_extra`` merges into
    each worker's environment (e.g. ``SPARKNET_SHIP_TO`` pointing both
    at one fleet collector).

    Each worker is reaped on its own thread (so a fast-failing peer's
    output surfaces immediately and pipes never fill); on timeout the
    survivors are killed and the error carries every output collected.
    """
    with tempfile.TemporaryDirectory(prefix="mp_round_") as d:
        script = os.path.join(d, "worker.py")
        with open(script, "w") as f:
            f.write(worker_src)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = {
            **os.environ,
            "PYTHONPATH": repo_root + os.pathsep + os.environ.get(
                "PYTHONPATH", ""
            ),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (
                f"--xla_force_host_platform_device_count="
                f"{devices_per_process}"
            ),
            **(env_extra or {}),
        }
        procs = [
            subprocess.Popen(
                [sys.executable, script, str(pid), str(port)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            for pid in range(2)
        ]
        results: Dict[int, Tuple[int, str]] = {}

        def reap(pid: int, p: subprocess.Popen) -> None:
            out, _ = p.communicate()
            results[pid] = (p.returncode, out)

        threads = [
            threading.Thread(
                target=reap, args=(pid, p), name=f"procs-reap-p{pid}",
                daemon=True,
            )
            for pid, p in enumerate(procs)
        ]
        for t in threads:
            t.start()
        deadline = time.time() + timeout
        try:
            while time.time() < deadline:
                if all(not t.is_alive() for t in threads):
                    break
                if any(rc != 0 for rc, _ in results.values()):
                    # a worker already failed: don't wait out the peer
                    # stuck on the coordinator — kill it and report
                    break
                time.sleep(0.2)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for t in threads:
                t.join(timeout=30)
        if len(results) < 2:
            raise TimeoutError(
                f"worker(s) did not finish within {timeout}s; collected: "
                + "".join(
                    f"\n-- worker {pid} rc={rc}:\n{out}"
                    for pid, (rc, out) in sorted(results.items())
                )
            )
        if any(rc != 0 for rc, _ in results.values()):
            # show every worker's output — the killed survivor's rc=-9 is
            # noise next to the real traceback
            raise AssertionError(
                "worker failure:" + "".join(
                    f"\n-- worker {pid} rc={rc}:\n{out}"
                    for pid, (rc, out) in sorted(results.items())
                )
            )
        for pid in range(2):
            assert f"{marker} p{pid}" in results[pid][1], results[pid][1]
        return [results[pid][1] for pid in range(2)]
