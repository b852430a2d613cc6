"""Multi-host launcher — the cluster bring-up layer (L8).

Reference: ``ec2/spark_ec2.py`` (provision EC2, wire master/workers, submit
apps) + ``SETUP.md``.  On TPU there is nothing to *provision* from inside
the job — the pod slice exists and every host runs the same program — so
the L8 role reduces to: start one process per host, join them through
``jax.distributed`` (``parallel/mesh.py initialize_distributed``), shard
the data per host, and run the app.  This tool does all three:

Local simulation (N processes on this machine, CPU devices standing in
for per-host chips — the development / CI path)::

    python -m sparknet_tpu.tools.launch --nprocs=2 --devices_per_host=2 \
        cifar --rounds=3 --tau=2

One process per real host (run the same line on EVERY host of the slice;
on Cloud TPU use ``gcloud ... ssh --worker=all --command=...``)::

    python -m sparknet_tpu.tools.launch \
        --coordinator=10.0.0.2:8476 --num_processes=4 --process_id=$WORKER_ID \
        imagenet --data=/mnt/imagenet --rounds=100

On a multi-host Cloud TPU slice the three flags can all be omitted —
``jax.distributed.initialize()`` discovers the slice topology from the
metadata server — so ``launch imagenet ...`` alone is a full bring-up.

A single host needs none of this, however many chips it holds: one
process drives all of them (a chip belongs to one process at a time), so
a four-chip host runs the app directly —
``python -m sparknet_tpu.apps.imagenet_app --workers=4`` — and a bare
``launch imagenet`` there would wait on a metadata server that a sealed
single-host machine does not have.

Apps see the joined runtime: ``jax.process_count() > 1`` switches them to
global-mesh mode, loading only their own workers' partitions (see
``parallel.local_worker_slice``).  SETUP.md walks the full path from
"N TPU VMs" to a running multi-host ImageNetApp.
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys

APPS = {
    "cifar": "sparknet_tpu.apps.cifar_app",
    "imagenet": "sparknet_tpu.apps.imagenet_app",
    "cifar_db": "sparknet_tpu.apps.cifar_db_app",
    "imagenet_create_db": "sparknet_tpu.apps.imagenet_create_db_app",
    "imagenet_run_db": "sparknet_tpu.apps.imagenet_run_db_app",
    "featurizer": "sparknet_tpu.apps.featurizer_app",
}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_app(app: str, app_argv, coordinator, num_processes, process_id) -> int:
    """Join the distributed runtime, then hand off to the app's main()."""
    import importlib

    from sparknet_tpu.parallel.mesh import initialize_distributed

    if coordinator or num_processes is not None:
        initialize_distributed(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    else:
        # Cloud TPU VM: topology comes from the metadata server
        initialize_distributed()
    mod = importlib.import_module(APPS[app])
    return int(mod.main(list(app_argv)) or 0)


def spawn_local(args, app_argv) -> int:
    """The CI/dev path: N OS processes on this machine, each given
    ``devices_per_host`` virtual CPU devices — process boundaries stand in
    for host boundaries exactly as in tests/test_multihost.py.

    With ``--fleet_collector`` the launcher starts the fleet collector
    (obs/fleet.py) and points every simulated host's shipper at it
    (``--ship_to`` appended to each app argv), so the whole run has ONE
    merged /fleet + /metrics view and the end-of-run summary names any
    late/dead host."""
    collector = None
    if args.fleet_collector:
        from sparknet_tpu.obs.fleet import FleetCollector, parse_hostport

        chost, cport = parse_hostport(args.fleet_collector)
        collector = FleetCollector(host=chost, port=cport).start()
        print(f"launch: fleet collector on {collector.url}/fleet")
        app_argv = list(app_argv) + [f"--ship_to={collector.url}"]
    try:
        return _spawn_local_procs(args, app_argv, collector)
    finally:
        # the listener thread + bound port must not outlive a failed
        # spawn/wait (Ctrl-C, bad app argv, a worker that never exits)
        if collector is not None:
            collector.close()


def proc_slice_members(nprocs: int, slices: int):
    """Contiguous process->slice grouping (the simulated-pod topology
    rule, shared with ``parallel/hierarchy.py``)."""
    from sparknet_tpu.parallel.hierarchy import slice_members

    return slice_members(nprocs, max(1, slices))


def _spawn_local_procs(args, app_argv, collector) -> int:
    import signal as _signal
    import threading
    import time

    port = free_port()
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env_base = {
        **os.environ,
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            f"--xla_force_host_platform_device_count={args.devices_per_host} "
            + os.environ.get("SPARKNET_EXTRA_XLA_FLAGS", "")
        ).strip(),
    }
    # simulated-slice topology: contiguous process blocks; every child
    # learns its slice through SPARKNET_SLICE_ID (the membership
    # controller's SIGTERM hook marks THAT slice leaving)
    slices = proc_slice_members(args.nprocs, getattr(args, "slices", 1))
    slice_of = {
        pid: i for i, members in enumerate(slices) for pid in members
    }
    # flag validation BEFORE any child spawns: a bad --preempt_slice
    # must not leave nprocs orphaned training processes behind an
    # early return
    if getattr(args, "preempt_slice", None) is not None and not (
        0 <= args.preempt_slice < len(slices)
    ):
        print(
            f"launch: --preempt_slice={args.preempt_slice} out of "
            f"range (have {len(slices)} slice(s))",
            file=sys.stderr,
        )
        return 2

    procs = []
    outputs = []
    readers = []
    preempt_killed = set()

    def spawn(pid: int, relaunched: bool = False):
        cmd = [
            sys.executable,
            "-m",
            "sparknet_tpu.tools.launch",
            f"--coordinator=127.0.0.1:{port}",
            f"--num_processes={args.nprocs}",
            f"--process_id={pid}",
            args.app,
            *app_argv,
        ]
        env = {**env_base, "SPARKNET_SLICE_ID": str(slice_of[pid])}
        if collector is not None:
            # each simulated host gets a stable fleet identity —
            # STABLE across a relaunch, so the collector sees the same
            # host come back with a new boot_id (restart detection)
            env["SPARKNET_HOST_ID"] = f"host{pid}"
        if relaunched:
            env["SPARKNET_RELAUNCHED"] = "1"
        p = subprocess.Popen(
            cmd,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        procs.append(p)
        buf = []
        outputs.append((pid, p, buf))
        # drain every child's pipe CONCURRENTLY — a sequential
        # communicate() deadlocks once any later child fills its 64KB
        # pipe while an earlier one waits on it in a collective
        t = threading.Thread(
            target=lambda p=p, buf=buf: buf.extend(p.stdout),
            name=f"launch-drain-p{pid}",
            daemon=True,
        )
        t.start()
        readers.append(t)
        return p

    for pid in range(args.nprocs):
        spawn(pid)

    # slice-granular lifecycle: --preempt_slice kills a WHOLE simulated
    # slice (SIGTERM — the orchestrator's preemption notice) at
    # --preempt_at seconds and relaunches the same processes
    # --relaunch_after seconds later, same argv + SPARKNET_RELAUNCHED=1
    # — the launcher-level half of "train through a preempted slice"
    preempt_thread = None
    t_end = time.time() + args.timeout
    if getattr(args, "preempt_slice", None) is not None:
        members = slices[args.preempt_slice]

        def do_preempt():
            time.sleep(args.preempt_at)
            victims = [
                (pid, p) for pid, p, _ in list(outputs)
                if pid in members and p.poll() is None
            ]
            if not victims:
                # the run finished (or died) before the scheduled
                # preemption: there is nothing to preempt, and
                # relaunching would re-run the whole app from scratch
                # into a completed run's accounting
                print(
                    "launch: slice %d preemption skipped (no live "
                    "process in the slice)" % args.preempt_slice
                )
                return
            for pid, p in victims:
                preempt_killed.add(p.pid)
                p.send_signal(_signal.SIGTERM)
            print(
                "launch: slice %d preempted (SIGTERM to host(s) %s)"
                % (args.preempt_slice, sorted(pid for pid, _ in victims))
            )
            time.sleep(args.relaunch_after)
            if time.time() >= t_end:
                # the global deadline passed while we slept: the main
                # loop has killed everything and moved on — spawning
                # now would orphan fresh children behind its back
                print(
                    "launch: slice %d relaunch skipped (run deadline "
                    "passed)" % args.preempt_slice
                )
                return
            # orchestrator escalation: a victim that treated the
            # SIGTERM as a notice and kept running (--elastic children
            # do) is hard-killed and REAPED before its replacement
            # takes the same --process_id/coordinator identity — two
            # live children with one identity would wedge the join
            for pid, p in victims:
                if p.poll() is None:
                    p.kill()
            for pid, p in victims:
                try:
                    p.wait(timeout=30)
                # sparknet: except-ok(best-effort reap of a just-killed victim; the main wait loop owns final reaping and rc accounting)
                except Exception:  # noqa: BLE001
                    pass
            for pid in members:
                spawn(pid, relaunched=True)
            print(
                "launch: slice %d relaunched (host(s) %s)"
                % (args.preempt_slice, sorted(members))
            )

        preempt_thread = threading.Thread(
            target=do_preempt, name="launch-preempt", daemon=True
        )
        preempt_thread.start()

    rc = 0
    waited = 0
    while True:
        # procs may GROW (a relaunched slice): keep waiting until every
        # spawned process — original and relaunched — has exited
        current = list(procs)
        for p in current[waited:]:
            try:
                p.wait(timeout=max(1, t_end - time.time()))
            except subprocess.TimeoutExpired:
                for q in list(procs):
                    if q.poll() is None:
                        q.kill()
                rc = 1
        waited = len(current)
        if preempt_thread is not None and preempt_thread.is_alive():
            preempt_thread.join(timeout=max(1, t_end - time.time()))
        if time.time() >= t_end:
            # global deadline: nothing further may spawn — reap and go
            for q in list(procs):
                if q.poll() is None:
                    q.kill()
                    rc = rc or 1
            break
        if len(procs) == waited and (
            preempt_thread is None or not preempt_thread.is_alive()
        ):
            break
    for t in readers:
        t.join(timeout=30)
    for pid, p, buf in outputs:
        prefix = f"[host {pid}] "
        sys.stdout.write(
            "".join(prefix + line.rstrip("\n") + "\n" for line in buf)
        )
        if p.returncode != 0 and p.pid not in preempt_killed:
            # a deliberately-preempted incarnation's kill rc is the
            # fault we injected, not a failure
            rc = rc or p.returncode or 1
    if collector is not None:
        view = collector.fleet_view()
        f = view["fleet"]
        print(
            "launch: fleet summary — %d host(s): %d live, %d late, "
            "%d dead; round skew %s"
            % (
                f["hosts_total"], f["hosts_live"], f["hosts_late"],
                f["hosts_dead"], f["round_skew"],
            )
        )
        for h, st in sorted(view["hosts"].items()):
            if st["state"] != "live":
                print(
                    "launch:   %s is %s (round %s, last push %.1fs ago)"
                    % (h, st["state"], st["round"], st["last_push_age_s"])
                )
            else:
                print(
                    "launch:   %s is live (round %s, last push %.1fs ago)"
                    % (h, st["round"], st["last_push_age_s"])
                )
    return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    # cluster lifecycle actions (spark_ec2.py real_main action dispatch
    # analog) live in tools/provision.py: `launch provision --dry-run ...`
    from sparknet_tpu.tools import provision

    if argv and argv[0] in provision.ACTIONS:
        return provision.main(argv[0], argv[1:])

    parser = argparse.ArgumentParser(
        prog="launch", description=__doc__.split("\n", 1)[0],
        epilog="cluster lifecycle actions (dispatched before app "
        "launch): launch provision|describe|run|ssh|teardown "
        "[--dry-run] ... — see `launch provision --help` and SETUP.md §1",
    )
    parser.add_argument(
        "--nprocs", type=int, default=0,
        help="spawn N local processes (simulation mode); 0 = this process "
        "IS one host of a real cluster",
    )
    parser.add_argument(
        "--devices_per_host", type=int, default=2,
        help="virtual CPU devices per simulated host (simulation mode)",
    )
    parser.add_argument(
        "--fleet_collector", nargs="?", default=None,
        const="127.0.0.1:0", metavar="HOST:PORT",
        help="simulation mode: start the fleet collector (obs/fleet.py) "
        "in the launcher and ship every simulated host's telemetry to "
        "it (appends --ship_to to each app argv); prints the merged "
        "live/late/dead summary at the end.  Real clusters pass the "
        "apps' own --fleet_collector/--ship_to flags instead",
    )
    parser.add_argument(
        "--slices", type=int, default=1,
        help="simulation mode: group the --nprocs processes into N "
        "contiguous simulated TPU slices (each child learns its slice "
        "via SPARKNET_SLICE_ID; pairs with the apps' --slices/"
        "--cross_slice_every two-tier averaging flags)",
    )
    parser.add_argument(
        "--preempt_slice", type=int, default=None, metavar="IDX",
        help="simulation mode: SIGTERM every process of slice IDX at "
        "--preempt_at seconds (the orchestrator's preemption notice) "
        "and relaunch them --relaunch_after seconds later with "
        "SPARKNET_RELAUNCHED=1 — kill and relaunch a whole simulated "
        "slice mid-run",
    )
    parser.add_argument(
        "--preempt_at", type=float, default=5.0,
        help="seconds into the run at which --preempt_slice fires",
    )
    parser.add_argument(
        "--relaunch_after", type=float, default=5.0,
        help="seconds after the preemption at which the slice's "
        "processes are relaunched",
    )
    parser.add_argument(
        "--coordinator", default=None, help="host:port of process 0"
    )
    parser.add_argument("--num_processes", type=int, default=None)
    parser.add_argument("--process_id", type=int, default=None)
    parser.add_argument("--timeout", type=int, default=1200)
    # lifecycle actions appear in choices purely for help/typo messages;
    # real action argv is dispatched above before argparse runs
    parser.add_argument(
        "app", choices=sorted(APPS) + list(provision.ACTIONS)
    )
    parser.add_argument("app_argv", nargs=argparse.REMAINDER,
                        help="arguments passed through to the app")
    args = parser.parse_args(argv)
    app_argv = [a for a in args.app_argv if a != "--"]

    if args.app in provision.ACTIONS:
        # lifecycle action given after launcher flags: the flags don't
        # apply to provisioning — require the action-first form instead
        # of falling into the app path (which would KeyError on APPS)
        print(
            f"launch: lifecycle action {args.app!r} must come first: "
            f"`launch {args.app} ...` (launcher flags like --nprocs do "
            "not apply to provisioning)",
            file=sys.stderr,
        )
        return 2

    if args.nprocs:
        return spawn_local(args, app_argv)
    return run_app(
        args.app, app_argv, args.coordinator, args.num_processes,
        args.process_id,
    )


if __name__ == "__main__":
    raise SystemExit(main())
