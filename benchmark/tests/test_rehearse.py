"""Each cell end to end at a tiny size, and what the command refuses."""

import ast
import os
import re
import shutil

from benchmark import files
from benchmark.tests.conftest import ROOT, rehearse, run_cell


def verdict_of(run):
    return ast.literal_eval(run.note("correct: "))


def test_cell_runs_and_its_last_line_is_the_result(rehearsal):
    cell, run = rehearsal
    assert run.rc == 0, run.stderr[-2000:]
    assert set(run.result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert run.stdout.strip().splitlines()[-1].startswith('{"correct"')
    declared = {m["name"]: m["unit"] for m in files.metrics_of(cell, "end_to_end")}
    assert {k: v["unit"] for k, v in run.result["metrics"].items()} == declared
    assert "setup_s" in declared and len(declared) >= 2
    # a CPU number is never printed under the name of a device metric
    assert all(v["value"] is None for v in run.result["metrics"].values())
    work = {w["name"]: w for w in files.table()["workloads"]}[cell]
    assert run.result["device"] == {
        "platform": "cpu", "kind": "cpu", "count": work["chips"],
        "memory_peak_bytes": 0,
    }
    assert run.result["attempted"] >= 1 and run.result["failed"] == 0


def test_checks_that_hold_at_any_size(rehearsal):
    cell, run = rehearsal
    verdict = verdict_of(run)
    for name in ("transform_is_a_crop", "no_compile_in_window", "nothing_failed"):
        assert verdict[name] is True, (name, verdict)
    # the loss band and the dtype's tolerances are set for the published
    # sizes, so a rehearsal may miss them; they must still be judged
    assert {"reference_exact", "reference_stated_dtype",
            "first_loss_in_band"} <= set(verdict)


def test_plain_reference_agrees_with_the_program_at_a_tiny_size(rehearsal):
    cell, run = rehearsal
    text = run.note("step against the plain reference, relative L2 error: ")
    exact = ast.literal_eval(re.search(r"float32/highest (\{.*?\})", text).group(1))
    if files.cell(cell)[1]["reference"] == "caffenet":
        # only summation order differs
        assert max(exact.values()) < 1e-5, exact
    else:
        # 53 BatchNorms over 8 images of 2x2 at the last stage: the program's
        # E[x^2]-E[x]^2 against the reference's E[(x-E[x])^2] is ill-
        # conditioned there (either is percents from float64 on the first
        # convolution's gradient); a dropped term would be of order 1
        assert exact["loss"] < 1e-3 and exact["logits"] < 3e-3, exact
        assert exact["grad_last_fc"] < 3e-3 and exact["grad_first_conv"] < 0.2, exact


def test_averaging_across_four_workers():
    run = rehearse("caffenet-dp4")
    verdict = verdict_of(run)
    for name in ("workers_bit_equal", "leaves_on_every_device",
                 "average_is_mean_of_workers"):
        assert verdict[name] is True, (name, verdict)
    assert "leaves on 4 devices" in run.note("averaging across 4 workers: ")


def test_traced_rehearsal_reports_no_device_metric():
    run = rehearse("caffenet-hostfed", trace=1)
    assert run.rc == 0, run.stderr[-2000:]
    # no TPU plane in a CPU trace: every reader finds nothing, the line stays
    assert run.result["metrics"] == {} and "breakdown" not in run.result
    assert set(run.result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"}


def test_refuses_without_a_tpu():
    rc, out, err = run_cell("--workload", "caffenet-train", "--seed", "1",
                            "--seconds", "1", "--trace", "0")
    assert rc == 2 and out == "" and "needs 1 TPU" in err


def test_refuses_an_unknown_cell():
    rc, out, _ = run_cell("--workload", "no-such-cell", "--rehearse")
    assert rc != 0 and out == ""


def test_alone_in_a_directory_it_exits_nonzero_and_prints_nothing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    rc, out, err = run_cell("--workload", "caffenet-train", "--seed", "1",
                            "--seconds", "1", "--trace", "0", cwd=str(tmp_path))
    assert rc == 1 and out == "" and "not in this checkout" in err
