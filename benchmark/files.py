"""Where the benchmark's data files are, and how a cell is looked up.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``; everything that
belongs to its configuration, traffic mix or a per-layer metric is a file of
its own, found by the name the table gives.
"""

import functools
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def table():
    return load_json("BENCHMARK.json")


def _rehearsed(spec, rehearse):
    """The file's sizes, or under ``--rehearse`` its own tiny ones."""
    spec = dict(spec)
    tiny = spec.pop("rehearse", {})
    if rehearse:
        spec.update(tiny)
    return spec


def cell(name, rehearse=False):
    """(workload entry, configuration, traffic) of the cell ``name``."""
    bench = table()
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(by_name)}"
        )
    work = by_name[name]
    entry = next(c for c in bench["configs"] if c["name"] == work["config"])
    config = _rehearsed(load_json(entry["file"]), rehearse)
    traffic = _rehearsed(
        load_json("benchmark", "traffic", work["traffic"] + ".json"), rehearse
    )
    return work, config, traffic


def metrics_of(name, group):
    """The ``end_to_end`` or ``per_layer`` entries the cell ``name`` reports."""
    return [
        m for m in table()[group]
        if "workloads" not in m or name in m["workloads"]
    ]


def peaks(device_kind):
    """The chip's published peaks; a device that is not in the table is an
    error, not a default."""
    devices = load_json("benchmark", "peaks.json")["devices"]
    if device_kind not in devices:
        raise SystemExit(
            f"no peaks recorded for device_kind {device_kind!r}; add its row "
            "and source to benchmark/peaks.json"
        )
    return devices[device_kind]
