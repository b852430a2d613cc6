"""Dataset prep tool (put_imagenet_on_s3.py role): the produced layout
must round-trip through the read side (ImageNetLoader) unchanged."""

import io
import os
import subprocess
import sys
import tarfile

import numpy as np
import pytest
from PIL import Image

from sparknet_tpu.data.imagenet import ImageNetLoader
from sparknet_tpu.tools import prepare_imagenet as prep

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_class_tree(root, classes=3, per_class=4, size=(48, 40)):
    # globally-unique basenames, like real ILSVRC (load_labels keys on
    # basename — ImageNetLoader.scala:41-54 semantics)
    rng = np.random.RandomState(0)
    for c in range(classes):
        d = root / f"class_{c}"
        d.mkdir(parents=True)
        for i in range(per_class):
            arr = rng.randint(0, 256, (size[1], size[0], 3), np.uint8)
            Image.fromarray(arr).save(d / f"c{c}_img_{i}.JPEG")


def test_prepare_dir_roundtrips_through_loader(tmp_path):
    src = tmp_path / "raw"
    out = tmp_path / "prepared"
    _make_class_tree(src)
    rc = prep.main([
        str(out), "--train_dir", str(src),
        "--num_train_chunks", "4", "--resize", "32", "32",
    ])
    assert rc == 0

    loader = ImageNetLoader(str(out))
    shards = loader.list_shards("train")
    assert len(shards) == 4
    labels = loader.load_labels(str(out / "train.txt"))
    assert len(labels) == 12 and set(labels.values()) == {0, 1, 2}

    got_labels = []
    for shard in shards:
        for data, label in loader.iter_shard(shard, labels):
            img = Image.open(io.BytesIO(data))
            assert img.size == (32, 32)  # resized
            got_labels.append(label)
    # every image lands in exactly one shard with its label kept
    assert sorted(got_labels) == sorted(labels.values())

    # manifest lists every artifact (the HTTP-root listing)
    index = (out / "index.txt").read_text().split()
    assert "train.txt" in index
    # local list_shards returns absolute paths; the manifest is relative
    assert all(os.path.basename(s) in index for s in shards)


def test_chunking_is_seed_deterministic_and_round_robin():
    pairs = [(f"img{i}", i % 3) for i in range(10)]
    a = prep.split_label_lines(pairs, 3, seed=7)
    b = prep.split_label_lines(pairs, 3, seed=7)
    assert a == b
    c = prep.split_label_lines(pairs, 3, seed=8)
    assert a != c
    # round-robin deal: chunk sizes differ by at most 1, nothing lost
    sizes = sorted(len(x) for x in a)
    assert sizes == [3, 3, 4]
    assert sorted(p for ch in a for p in ch) == sorted(pairs)


def test_nested_tar_input(tmp_path):
    # ILSVRC shape: outer tar of per-class sub-tars
    rng = np.random.RandomState(1)

    def jpeg():
        arr = rng.randint(0, 256, (24, 24, 3), np.uint8)
        b = io.BytesIO()
        Image.fromarray(arr).save(b, format="JPEG")
        return b.getvalue()

    outer_path = tmp_path / "train_nested.tar"
    with tarfile.open(outer_path, "w") as outer:
        for cls in ("n01", "n02"):
            sub = io.BytesIO()
            with tarfile.open(fileobj=sub, mode="w") as st:
                for i in range(3):
                    data = jpeg()
                    info = tarfile.TarInfo(f"{cls}_img{i}.JPEG")
                    info.size = len(data)
                    st.addfile(info, io.BytesIO(data))
            sub.seek(0)
            info = tarfile.TarInfo(f"{cls}.tar")
            info.size = len(sub.getvalue())
            outer.addfile(info, sub)

    labels = tmp_path / "train.txt"
    labels.write_text(
        "".join(
            f"{cls}/{cls}_img{i}.JPEG {l}\n"
            for l, cls in enumerate(("n01", "n02"))
            for i in range(3)
        )
    )
    out = tmp_path / "out"
    rc = prep.main([
        str(out), "--train_tar", str(outer_path),
        "--train_labels", str(labels), "--num_train_chunks", "2",
    ])
    assert rc == 0
    loader = ImageNetLoader(str(out))
    lab = loader.load_labels(str(out / "train.txt"))
    count = sum(
        1
        for shard in loader.list_shards("train")
        for _ in loader.iter_shard(shard, lab)
    )
    assert count == 6


def test_nested_tar_reader_concurrent_readers(tmp_path):
    """Regression (ADVICE r5 low): the reader shared one handle with an
    unsynchronized seek+read pair — interleaved threads read bytes from
    the WRONG member.  os.pread carries the offset in the call, so many
    threads hammering one reader must each get exactly their member."""
    import threading

    payloads = {}
    outer_path = tmp_path / "nested.tar"
    rng = np.random.RandomState(7)
    with tarfile.open(outer_path, "w") as outer:
        for cls in ("n01", "n02", "n03"):
            sub = io.BytesIO()
            with tarfile.open(fileobj=sub, mode="w") as st:
                for i in range(4):
                    # distinct sizes + contents so a misread can't alias
                    data = rng.randint(0, 256, 512 + 37 * i).astype(
                        np.uint8
                    ).tobytes()
                    payloads[f"{cls}/{cls}_f{i}.bin"] = data
                    info = tarfile.TarInfo(f"{cls}_f{i}.bin")
                    info.size = len(data)
                    st.addfile(info, io.BytesIO(data))
            sub.seek(0)
            info = tarfile.TarInfo(f"{cls}.tar")
            info.size = len(sub.getvalue())
            outer.addfile(info, sub)

    read = prep.nested_tar_reader(str(outer_path))
    names = sorted(payloads) * 8
    errors = []

    def worker(my_names):
        try:
            for n in my_names:
                if read(n) != payloads[n]:
                    errors.append(f"corrupt read for {n}")
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [
        threading.Thread(target=worker, args=(names[i::8],))
        for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errors, errors[:5]


def test_nested_tar_reader_closes_fd_on_collect(tmp_path):
    import gc

    outer_path = tmp_path / "one.tar"
    with tarfile.open(outer_path, "w") as outer:
        sub = io.BytesIO()
        with tarfile.open(fileobj=sub, mode="w") as st:
            info = tarfile.TarInfo("a.bin")
            info.size = 3
            st.addfile(info, io.BytesIO(b"abc"))
        sub.seek(0)
        info = tarfile.TarInfo("n01.tar")
        info.size = len(sub.getvalue())
        outer.addfile(info, sub)

    read = prep.nested_tar_reader(str(outer_path))
    assert read("n01/a.bin") == b"abc"
    fd = read.__closure__[
        [i for i, c in enumerate(read.__code__.co_freevars)
         if c == "fd"][0]
    ].cell_contents
    os.fstat(fd)  # open while the reader lives
    del read
    gc.collect()
    with pytest.raises(OSError):
        os.fstat(fd)  # finalizer closed it


def test_upload_dry_run(tmp_path):
    src = tmp_path / "raw"
    out = tmp_path / "prepared"
    _make_class_tree(src, classes=1, per_class=1)
    res = subprocess.run(
        [
            sys.executable, "-m", "sparknet_tpu.tools.prepare_imagenet",
            str(out), "--train_dir", str(src), "--num_train_chunks", "1",
            "--upload", "gs://bucket/imagenet", "--dry-run",
        ],
        env={**os.environ, "PYTHONPATH": _REPO, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip().splitlines()[-1] == (
        f"gsutil -m rsync -r {out} gs://bucket/imagenet"
    )
    with pytest.raises(ValueError, match="unsupported"):
        prep.upload_command(str(out), "ftp://x")


def test_worker_pool_writes_identical_shards(tmp_path):
    src = tmp_path / "raw"
    _make_class_tree(src, classes=2, per_class=4)
    outs = {}
    for w in (1, 2):
        out = tmp_path / f"out_w{w}"
        rc = prep.main([
            str(out), "--train_dir", str(src), "--num_train_chunks", "3",
            "--resize", "24", "24", "--workers", str(w),
        ])
        assert rc == 0
        outs[w] = {
            p: (out / p).read_bytes()
            for p in sorted(os.listdir(out))
        }
    assert outs[1].keys() == outs[2].keys()
    for name in outs[1]:
        assert outs[1][name] == outs[2][name], name


def test_duplicate_basenames_refused(tmp_path):
    src = tmp_path / "raw"
    rng = np.random.RandomState(0)
    for c in range(2):
        d = src / f"cls{c}"
        d.mkdir(parents=True)
        # SAME basename in both classes: reader keys labels by basename
        Image.fromarray(
            rng.randint(0, 256, (16, 16, 3), np.uint8)
        ).save(d / "0001.JPEG")
    with pytest.raises(SystemExit, match="duplicate image basename"):
        prep.main([
            str(tmp_path / "out"), "--train_dir", str(src),
            "--num_train_chunks", "1",
        ])
