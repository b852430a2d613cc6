#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses to start unless jax's first device is a TPU and the cell's chips are
there; ``--rehearse`` is the only way onto a CPU (tiny sizes from the files'
own ``rehearse`` groups, every metric printed as null).  Earlier lines are
free-form (``[bench] ...``); the last line of stdout is the result object.
See README.md beside this file.
"""

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(message):
    print(f"[bench] {message}", flush=True)


def memory_peak_bytes(devices):
    """The peak on the fullest device: the live buffers' peak plus the peak of
    what the runtime reserves beside them, which on the TPU is where a
    program's temporaries live (it equals the compiler's ``temp_size`` for the
    round: PERF.md, PR 22).  0 where the backend reports nothing."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(
        s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        for s in stats
    )


def read_layer_metric(name, ev):
    """The metric's own file names its reader and the reader's arguments."""
    from benchmark import files

    spec = files.load_json("benchmark", "layer_metrics", name + ".json")
    reader = importlib.import_module("benchmark.reducers." + spec["reducer"])
    return reader.reduce(ev, **spec["args"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import evidence, files, xplane
    from benchmark.compile_log import CompileLog

    work, config, traffic = files.cell(args.workload, args.rehearse)
    seconds = files.table()["run_seconds"] if args.seconds is None else args.seconds
    try:
        from sparknet_tpu.utils import devices as device_policy
    except ImportError:
        print("benchmark/run.py: the program (sparknet_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 1
    if args.rehearse:
        device_policy.force_virtual_cpu_devices(work["chips"])
        seconds = min(seconds, 1.0)
    import jax

    devices = jax.devices()
    if not args.rehearse and (
        devices[0].platform != "tpu" or len(devices) < work["chips"]
    ):
        print(f"benchmark/run.py: {work['name']} needs {work['chips']} TPU "
              f"chip(s); jax found {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    # a rehearsal computes every number and prints none: the first row of the
    # table stands in for the CPU's missing peaks
    peaks = files.peaks(
        next(iter(files.load_json("benchmark", "peaks.json")["devices"]))
        if args.rehearse else kind
    )
    # a rehearsal keeps no compiled program: CPU programs are of no use later
    cache_dir = None if args.rehearse else device_policy.enable_compile_cache()
    # keep every program, however quickly it compiled: a run builds dozens of
    # small ones, and only the first run of a cell in a checkout may compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileLog()
    phases, clock = {}, time.perf_counter()

    def phase(name):
        nonlocal clock
        now = time.perf_counter()
        phases[name], clock = now - clock, now

    phases["import"] = clock - PROCESS_START
    module = importlib.import_module(
        "benchmark.kinds." + traffic["kind"].replace("-", "_")
    )
    cell = module.Cell(work, config, traffic, args.seed, log)
    phase("build_and_data")
    try:
        verdict = cell.check()
        phase("check")
        verdict.update(cell.warm())
        phase("warm")
        setup = {**compiles.since(), "seconds": time.perf_counter() - PROCESS_START}
        mark = compiles.mark()
        marks = evidence.Marks(on=bool(args.trace))
        if args.trace:
            trace_dir = os.path.join(ROOT, ".bench_out", work["name"], "trace")
            window, spans = evidence.traced(
                lambda: cell.measure(min(seconds, evidence.TRACE_SECONDS), marks),
                trace_dir,
            )
        else:
            window = cell.measure(seconds, marks)
        in_window = compiles.since(mark)
        peak_bytes = memory_peak_bytes(cell.devices)
        e2e = cell.end_to_end(window, peaks, peak_bytes)
    finally:
        cell.close()
    verdict["no_compile_in_window"] = in_window["compile_requests"] == 0
    verdict["nothing_failed"] = window["failed"] == 0 and window["attempted"] > 0
    e2e["setup_s"] = setup["seconds"]
    log(f"set-up {setup['seconds']:.2f} s by phase "
        f"{ {k: round(v, 2) for k, v in phases.items()} }; compile requests "
        f"{setup['compile_requests']} ({setup['compile_s']:.1f} s), persistent-"
        f"cache hits {setup['cache_hits']}, cache at {cache_dir}")
    log(f"window: {window}; compile requests inside "
        f"{in_window['compile_requests']}")
    log(f"memory of {cell.devices[0]}: {cell.devices[0].memory_stats()}")
    log(f"correct: {verdict}")
    log(f"end to end: {e2e}")

    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    result = {
        "correct": all(verdict.values()),
        "attempted": window["attempted"], "failed": window["failed"],
    }
    group, values = "end_to_end", e2e
    if args.trace:
        group, values = "per_layer", {}
        path = xplane.newest_xplane(trace_dir)
        ev = path and evidence.collect(
            path, marks.names(), window, peaks, marks.starts, spans)
        if not ev:
            log("the profiler left no device trace to read")
        else:
            device.update(evidence.device_line(ev))
            result["breakdown"] = evidence.breakdown(ev)
            values = {
                m["name"]: read_layer_metric(m["name"], ev)
                for m in files.metrics_of(work["name"], group)
            }
    # a reader that found nothing returned None: its metric is left out
    result["metrics"] = {
        m["name"]: {
            "value": None if args.rehearse else values[m["name"]],
            "unit": m["unit"],
        }
        for m in files.metrics_of(work["name"], group)
        if values.get(m["name"]) is not None
    }
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
