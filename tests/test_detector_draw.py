"""pycaffe long-tail parity: net visualization (draw.py analog) and the
windowed-detection driver (detector.py analog)."""

import os

import numpy as np

from sparknet_tpu import config, models
from sparknet_tpu.tools import draw
from sparknet_tpu.tools.detector import Detector

DEPLOY = """
name: "tiny_det"
layer { name: "data" type: "Input" top: "data"
  input_param { shape { dim: 4 dim: 3 dim: 8 dim: 8 } } }
layer { name: "fc" type: "InnerProduct" bottom: "data" top: "fc"
  inner_product_param { num_output: 3 } }
layer { name: "prob" type: "Softmax" bottom: "fc" top: "prob" }
"""


# -- draw -------------------------------------------------------------------


def test_net_to_dot_structure():
    netp = models.load_model("lenet")
    dot = draw.net_to_dot(netp, phase="TEST")
    assert dot.startswith('digraph "LeNet"')
    assert "rankdir=LR;" in dot
    # conv node carries kernel/stride/pad and the conv color
    assert (
        '"conv1_Convolution" [label="conv1\\n(Convolution)\\n'
        "kernel size: 5\\nstride: 1\\npad: 0\"" in dot
    )
    assert '#FF5050' in dot and '#FF9900' in dot
    # blob octagons and layer->blob edges
    assert '"conv1_blob" [label="conv1", shape=octagon' in dot
    assert '"conv1_Convolution" -> "conv1_blob" [label="20"];' in dot
    # every edge endpoint is a declared node
    nodes = {
        line.strip().split(" ")[0]
        for line in dot.splitlines() if "[label=" in line
    }
    for line in dot.splitlines():
        if " -> " in line:
            src, dst = line.strip().rstrip(";").split(" -> ")
            assert src in nodes and dst.split(" [")[0] in nodes


def test_in_place_layers_get_neuron_style():
    netp = config.parse(
        """
        layer { name: "in" type: "Input" top: "x"
          input_param { shape { dim: 1 dim: 4 } } }
        layer { name: "act" type: "ReLU" bottom: "x" top: "x" }
        """,
        config.NetParameter,
    )
    dot = draw.net_to_dot(netp)
    assert '"act_ReLU"' in dot and "#90EE90" in dot


def test_draw_net_cli(tmp_path):
    from sparknet_tpu.tools import cli

    src = tmp_path / "net.prototxt"
    src.write_text(DEPLOY)
    out = tmp_path / "net.dot"
    assert cli.main(["draw_net", str(src), str(out), "--rankdir=TB"]) == 0
    text = out.read_text()
    assert text.startswith('digraph "tiny_det"')
    assert "rankdir=TB;" in text


def test_committed_googlenet_dot_is_current():
    """The committed artifact regenerates byte-identically."""
    import os

    path = os.path.join(
        os.path.dirname(models.__file__), "zoo", "googlenet.dot"
    )
    netp = models.load_model("googlenet")
    assert open(path).read() == draw.net_to_dot(netp, phase="TEST")


# -- detector ---------------------------------------------------------------


def _red_blue_image():
    """16x16 image: left half red, right half blue."""
    im = np.zeros((16, 16, 3), np.uint8)
    im[:, :8, 0] = 200
    im[:, 8:, 2] = 200
    return im


def _channel_picker_params(det):
    # fc weights score each class by one channel's mean intensity
    w = np.zeros((3, 3 * 8 * 8), np.float32)
    for cls in range(3):
        w[cls, cls * 64:(cls + 1) * 64] = 0.01
    det.params["fc"] = [w, np.zeros(3, np.float32)]


def test_detect_windows_scores_by_content():
    netp = config.parse(DEPLOY, config.NetParameter)
    det = Detector(netp, batch=4)
    _channel_picker_params(det)
    im = _red_blue_image()
    # windows: (ymin, xmin, ymax, xmax) exclusive max, reference layout
    red_win = (0, 0, 16, 8)
    blue_win = (0, 8, 16, 16)
    dets = det.detect_windows([(im, [red_win, blue_win])])
    assert len(dets) == 2
    assert dets[0]["filename"] is None
    assert tuple(dets[0]["window"]) == red_win
    assert int(np.argmax(dets[0]["prediction"])) == 0  # red channel
    assert int(np.argmax(dets[1]["prediction"])) == 2  # blue channel
    # softmax outputs
    for d in dets:
        np.testing.assert_allclose(d["prediction"].sum(), 1.0, rtol=1e-4)


def test_detect_windows_batching_and_files(tmp_path):
    from PIL import Image

    netp = config.parse(DEPLOY, config.NetParameter)
    det = Detector(netp, batch=4)
    _channel_picker_params(det)
    p = tmp_path / "im.png"
    Image.fromarray(_red_blue_image()).save(p)
    # 6 windows across a batch boundary (batch=4)
    wins = [(0, 0, 16, 8), (0, 8, 16, 16)] * 3
    dets = det.detect_windows([(str(p), wins)])
    assert len(dets) == 6
    assert dets[0]["filename"] == str(p)
    preds = [int(np.argmax(d["prediction"])) for d in dets]
    assert preds == [0, 2, 0, 2, 0, 2]


def test_detector_context_pad_runs():
    netp = config.parse(DEPLOY, config.NetParameter)
    det = Detector(netp, context_pad=2, crop_mode="square", batch=2)
    _channel_picker_params(det)
    dets = det.detect_windows([(_red_blue_image(), [(2, 2, 10, 7)])])
    assert len(dets) == 1
    assert np.isfinite(dets[0]["prediction"]).all()


def test_detector_context_pad_mean_keeps_padding_zero():
    """With context_pad + a mean, the zero-padded border must stay at
    zero signal after mean subtraction (R-CNN standard config;
    WindowSampler training batches behave the same — ADVICE r4)."""
    netp = config.parse(DEPLOY, config.NetParameter)
    mean = np.full(3, 100.0, np.float32)
    det = Detector(netp, mean=mean, context_pad=2, batch=1)
    # window at the image corner: the context overhangs the image, so
    # crop_window zero-pads the top-left of the crop
    im = _red_blue_image()
    out, content = det.crop(im, (0, 0, 6, 6))
    pad_h, pad_w, (wh, ww) = content
    assert pad_h > 0 and pad_w > 0  # the config actually padded
    chw = det._preprocess(out, content)
    # padded border: exactly zero (NOT -mean)
    assert np.all(chw[:, :pad_h, :] == 0.0)
    assert np.all(chw[:, :, :pad_w] == 0.0)
    # content region: mean actually subtracted (image corner is red 200
    # or black 0, never equal to the 100 mean everywhere)
    assert np.any(chw[:, pad_h:pad_h + wh, pad_w:pad_w + ww] != 0.0)


def test_detector_derives_deploy_view():
    """A train/test config (HostData + loss) reduces via deploy_variant."""
    netp = models.load_model("lenet")
    det = Detector(netp, batch=2)
    im = np.random.RandomState(0).randint(0, 255, (40, 40, 1), np.uint8)
    dets = det.detect_windows([(im, [(0, 0, 28, 28), (5, 5, 33, 33)])])
    assert len(dets) == 2
    for d in dets:
        assert d["prediction"].shape == (10,)
        np.testing.assert_allclose(d["prediction"].sum(), 1.0, rtol=1e-4)


def test_detect_cli(tmp_path):
    """`cli detect` scores every window of an R-CNN window file through
    the Detector (the detector.py-over-window_data workflow)."""
    import subprocess
    import sys

    from PIL import Image

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    im = _red_blue_image()
    img_path = tmp_path / "im0.png"
    Image.fromarray(im).save(img_path)
    # window-file rows: class overlap x1 y1 x2 y2 (inclusive)
    wf = tmp_path / "windows.txt"
    wf.write_text(
        f"# 0\n{img_path}\n3\n16\n16\n2\n"
        "1 0.9 0 0 7 15\n"   # left half: red
        "2 0.9 8 0 15 15\n"  # right half: blue
    )
    deploy = tmp_path / "deploy.prototxt"
    deploy.write_text(DEPLOY)

    out = subprocess.run(
        [sys.executable, "-m", "sparknet_tpu.tools.cli", "detect",
         "--model", str(deploy), "--window_file", str(wf), "--batch", "2"],
        env={**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    lines = out.stdout.strip().splitlines()
    assert len(lines) == 2
    # "<image> <x1> <y1> <x2> <y2> <class> <score>" with the original
    # inclusive coordinates echoed back
    p0 = lines[0].split()
    assert p0[0] == str(img_path)
    assert p0[1:5] == ["0", "0", "7", "15"]
    assert "scored 2 windows over 1 images" in out.stderr
