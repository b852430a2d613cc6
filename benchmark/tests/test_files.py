"""BENCHMARK.json against the files it names, the peaks table, and the
operation count against the program's own."""

import importlib
import math
import os
import re

import pytest

from benchmark import files, flops
from benchmark.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
# a layer's name may also start with '_' (the driver's check; it refused
# "training loop" before any run)
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_in_the_table_has_its_file():
    bench = files.table()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        spec = files.load_json(c["file"])
        assert spec["name"] == c["name"] and spec["source"] == c["source"]
        importlib.import_module("benchmark.reference." + spec["reference"])
    pairs = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["config"] in configs and w["chips"] in (1, 4)
        _, _, traffic = files.cell(w["name"])
        assert traffic["workers"] == w["chips"]
        importlib.import_module(
            "benchmark.kinds." + traffic["kind"].replace("-", "_"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and NAME.match(m["name"])
        assert LAYER.match(m["layer"]), m["layer"]
        spec = files.load_json("benchmark", "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module("benchmark.reducers." + spec["reducer"])
        assert callable(reader.reduce)
        for cell in m.get("workloads", []):
            assert cell in {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert files.metrics_of(w["name"], "per_layer")
    assert 1 <= bench["run_seconds"] <= 51 and bench["run_seconds"] == int(bench["run_seconds"])
    assert all(not a.startswith("/") and ".." not in a for a in bench["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_rehearsal_sizes_come_from_the_files_own_groups():
    _, config, traffic = files.cell("caffenet-train")
    _, tiny_config, tiny_traffic = files.cell("caffenet-train", rehearse=True)
    assert config["crop"] == 227 and config["batch_per_worker"] == 256
    assert tiny_config["crop"] < config["crop"] and tiny_traffic["tau"] < traffic["tau"]
    assert "rehearse" not in tiny_config and "rehearse" not in config


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    v5e = files.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(SystemExit):
        files.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name,gflops", [("caffenet", 4.35), ("resnet50", 23.15)])
def test_operation_count_equals_the_programs_own_walk(name, gflops):
    """The benchmark's walk of the configuration's layer table against
    ``utils/flops.train_flops`` on the program's net (shapes only, no compile)."""
    from sparknet_tpu import config as cfg, models
    from sparknet_tpu.net import JaxNet
    from sparknet_tpu.utils.flops import train_flops

    config = files.load_json("benchmark", "configs", name + ".json")
    batch, crop = 4, config["crop"]
    shapes = [(batch, 3, crop, crop), (batch,)]
    net = JaxNet(cfg.replace_data_layers(
        models.load_model(config["program_model"], classes=config["classes"]),
        shapes, shapes), phase="TRAIN")
    ours = flops.train_flops_per_image(config)
    assert math.isclose(ours, train_flops(net) / batch, rel_tol=1e-12)
    assert abs(ours / 1e9 - gflops) < 0.01
