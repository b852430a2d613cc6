"""The trace reduction: its interval arithmetic on made-up intervals, and the
whole of it on a trace recorded on the chip."""

import gzip
import os

import numpy as np
import pytest

from benchmark import evidence, files, flops, run
from benchmark import xplane as X

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MARKS = ["feed.next_round", "trainer.round", "wait.losses", "drain"]


def test_union_gaps_and_difference():
    a = [(0, 10), (5, 20), (30, 40)]
    assert X.merged(a).tolist() == [[0, 20], [30, 40]]
    assert X.covered(a) == 30 and X.covered(a, (8, 35)) == 17
    assert X.gaps(a, (0, 50)).tolist() == [[20, 30], [40, 50]]
    assert X.minus(a, [(3, 7), (15, 32)]).tolist() == [[0, 3], [7, 15], [32, 40]]
    assert X.intersect(a, [(18, 33)]).tolist() == [[18, 20], [30, 33]]
    assert X.covered([]) == 0 and len(X.minus([], a)) == 0


def test_self_time_counts_every_nanosecond_once():
    ops = X.Events(*zip(("while", 0, 100), ("a", 10, 20), ("b", 20, 50),
                        ("a", 60, 70), ("c", 120, 130)))
    totals = X.self_seconds_by_name(ops, (0, 200))
    assert {k: round(v * 1e9) for k, v in totals.items()} == {
        "while": 50, "a": 20, "b": 30, "c": 10}
    assert X.leaf_mask(ops).tolist() == [False, True, True, True, True]
    assert X.top(totals, 2) == [["while", 50e-9], ["b", 30e-9]]
    half = X.self_seconds_by_name(ops, (15, 65))
    assert {k: round(v * 1e9) for k, v in half.items()} == {
        "while": 10, "a": 10, "b": 30}


def test_idle_goes_to_the_first_host_activity_open_in_the_gap():
    ops = X.Events(*zip(("x", 0, 100), ("y", 200e3, 300e3)))
    idle = X.idle_by_host_activity(
        ops.intervals(), (0, 400e3),
        {"h2d": np.array([[100.0, 60e3]]),
         "feed.next_round": np.array([[50.0, 150e3]])},
        short_ns=1e3,
    )
    assert idle == {"h2d": (60e3 - 100) / 1e9, "feed.next_round": 90e3 / 1e9,
                    "other": 150e3 / 1e9}


def test_an_operation_is_named_by_its_hlo_line():
    hlo = ("%psum_invariant.113 = f32[4096,9216]{1,0:T(8,128)} "
           "all-reduce(%broadcast_select_fusion), channel_id=1")
    assert X.short_name(hlo) == "psum_invariant.113 f32[4096,9216]"
    assert X.opcode(hlo) == "all-reduce" and X.COLLECTIVE.match(X.opcode(hlo))
    tupled = "%fusion.483 = (f32[96,3,11,11]{0,1}, f32[96]{0}) fusion(f32[8]{0} %x), kind=kOutput"
    assert X.short_name(tupled) == "fusion.483 f32[96,3,11,11]"
    assert X.opcode(tupled) == "fusion" and not X.COLLECTIVE.match("fusion")


# -- on a trace recorded on the chip -------------------------------------------
@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    """One round of ``caffenet-dp4`` on two of its four chips, cut from this
    PR's own traced run on the v5e by ``data/make_trimmed_trace.py``."""
    path = tmp_path_factory.mktemp("trace") / "caffenet-dp4.xplane.pb"
    with gzip.open(os.path.join(DATA, "caffenet-dp4.trimmed.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path)


@pytest.fixture(scope="module")
def recorded(recorded_path):
    config = files.load_json("benchmark", "configs", "caffenet.json")
    window = {"workers": 2, "tau": 10, "flops_per_round_and_worker":
              10 * 256 * flops.train_flops_per_image(config)}
    return evidence.collect(
        recorded_path, MARKS, window, files.peaks("TPU v5 lite"))


def read(ev, metric):
    return run.read_layer_metric(metric, ev)


def test_recorded_trace_window_and_busy_time(recorded):
    # from the third feed.next_round mark to the end of the last mark
    assert recorded["window_ns"] == (1992966508.0, 2159914076.0)
    assert recorded["devices"] == [0, 1]
    assert recorded["rounds_on_device"][0] == pytest.approx(1.00059, abs=1e-5)
    line = evidence.device_line(recorded)
    assert line["window_s"] == pytest.approx(0.166947568, abs=1e-9)
    assert line["busy_s"] == pytest.approx(0.1669301315, abs=1e-9)
    assert read(recorded, "device_idle_share") == pytest.approx(1.0444e-4, rel=1e-3)
    assert read(recorded, "step_device_ms") == pytest.approx(16.68328, rel=1e-6)
    assert read(recorded, "busy_mfu") == pytest.approx(0.3388606, rel=1e-6)


def test_recorded_trace_collective_is_all_exposed(recorded):
    # fc6's own all-reduce (2.666 ms) and the combined one of the other leaves
    # (1.595 ms) close the round program; nothing else runs beside them
    assert read(recorded, "collective_ms") == pytest.approx(4.2579089, rel=1e-6)
    assert read(recorded, "collective_exposed_ms") == pytest.approx(4.2579089, rel=1e-6)
    ops = evidence.ops_of(recorded, 0)
    held = {X.opcode(n) for n, leaf in zip(ops.names, X.leaf_mask(ops)) if not leaf}
    assert "while" in held  # the scan over tau holds its body's operations


def test_recorded_trace_breakdown_and_what_is_absent(recorded):
    top = evidence.breakdown(recorded)
    assert [name for name, _ in top["device_ops"][:3]] == [
        "fusion.481 f32[96,3,11,11]",
        "bitcast_dynamic-update-slice_fusion.2 f32[256,3,227,227]",
        "fusion.495 f32[4096,9216]",
    ]
    assert top["device_ops"][0][1] == pytest.approx(0.012886444, rel=1e-6)
    assert len(top["device_ops"]) == 10
    assert top["idle_gaps"] == [["between ops (<50 us)", pytest.approx(1.4617e-05)]]
    # the program's spans were not kept with the trace: their readers find
    # nothing and say so
    assert read(recorded, "round_dispatch_ms") is None
    assert read(recorded, "feed_h2d_ms") is None


def test_a_trace_without_the_harness_marks_gives_nothing(recorded_path):
    window = {"workers": 1, "tau": 1, "flops_per_round_and_worker": 1.0}
    assert evidence.collect(recorded_path, ["no.such.mark"], window, {}) is None
    assert evidence.collect(recorded_path, [], window, {}) is None
