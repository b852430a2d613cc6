"""``chip_smoke.py``'s phase plumbing on the CPU mesh at toy sizes — the
real script still refuses to pass here."""

import json

import chip_smoke

# every phase, cut to what a CPU compiles in seconds: a zoo model with no
# fc8, tiny kernel shapes in interpret mode, one comm leg, a 2-worker LM
TINY = {
    **chip_smoke.FULL,
    "model": "cifar10_quick",
    "train_batch": 4,
    "test_batch": 2,
    "full_size": 36,
    "crop": 32,
    "classes": 10,
    "rounds": 2,
    "attention_shapes": (("tiny", 1, 8, 1, 8, "float32"),),
    "mla_shapes": (("tiny", 1, 16, 2, 128, 8, "float32"),),
    "lm_loss_shapes": (("tiny", 32, 128, 300, True),),
    "comm_legs": (("int8", True),),
    "lm_rounds": 2,
    "lm_args": (
        "--workers=2", "--batch=2", "--dim=16", "--depth=1", "--seq_len=16",
    ),
    "interpret": True,
}


def test_main_refuses_off_the_chip(capsys):
    assert chip_smoke.main() != 0
    assert capsys.readouterr().out == ""  # no result line


def test_result_line_is_the_last_and_holds_only_ok_and_device(
    monkeypatch, capsys, tmp_path
):
    """What the chip check parses: the last stdout line is one JSON object
    with exactly ``ok`` and ``device`` {platform, kind, count}; the phases
    go on the line before it and into ``summary.json``."""
    from sparknet_tpu.utils import devices

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    phases = {"train-1chip": {"status": "failed", "error": "boom"}}
    monkeypatch.setattr(devices, "require_chip", lambda: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run", lambda sizes: (False, phases))
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    assert chip_smoke.main() == 1
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": False,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    summary = json.loads(lines[-2].split("summary: ", 1)[1])
    assert summary["phases"] == phases and summary["claim"] is None
    assert json.loads((tmp_path / "summary.json").read_text()) == summary


def test_phase_plumbing_on_the_cpu_mesh(tmp_path):
    ok, phases = chip_smoke.run(TINY, out_dir=str(tmp_path))
    assert ok, json.dumps(phases, indent=1)
    assert list(phases) == ["train-1chip", "train-4chip", "kernels", "lm-train"]
    for name, phase in phases.items():
        assert phase["status"] == "ran", (name, phase)
        assert phase["wall_s"] > 0 and phase["compiles"] > 0
    assert phases["train-1chip"]["rounds"] == 2
    assert phases["train-4chip"]["devices_per_leaf"] == 4
    assert phases["train-4chip"]["worker_param_spread"] == 0.0
    assert set(phases["kernels"]["lm_loss_tiny_32x128x300_bfloat16"]) == {
        "loss", "dx", "dhead"}
    assert phases["lm-train"]["flash_kernel"] is False  # dense off the TPU
    assert list(tmp_path.glob("training_log_*.txt"))  # logs land in out_dir


def test_fewer_than_four_devices_is_a_skip_not_a_pass(monkeypatch, tmp_path):
    import jax

    monkeypatch.setattr(jax, "device_count", lambda: 3)
    for phase in ("phase_train", "phase_kernels", "phase_lm"):
        monkeypatch.setattr(chip_smoke, phase, lambda *a, **kw: {})
    ok, phases = chip_smoke.run(TINY, out_dir=str(tmp_path))
    assert phases["train-4chip"] == {"status": "skipped: 3 devices"}
    assert ok and phases["train-1chip"]["status"] == "ran"
