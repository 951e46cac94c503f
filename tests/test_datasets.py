"""CIFAR-10/100 + MNIST ingestion against generated wire-format fixtures
(reference examples/cnn/data/{cifar10,cifar100,mnist}.py), and the
north-star command `train_cnn.py resnet cifar10` end-to-end on a tiny
fixture dataset."""

import gzip
import os
import pickle
import struct
import subprocess
import sys

import numpy as np
import pytest

from singa_tpu import datasets

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixture writers: tiny datasets in the REAL wire formats
# ---------------------------------------------------------------------------

def write_cifar10_py(root, n_per_batch=20, num_batches=5, seed=0):
    d = root / "cifar-10-batches-py"
    d.mkdir()
    rng = np.random.RandomState(seed)
    all_y = []
    for i in range(1, num_batches + 1):
        y = rng.randint(0, 10, n_per_batch)
        blob = {"data": rng.randint(0, 256, (n_per_batch, 3072),
                                    dtype=np.uint8).astype(np.uint8),
                "labels": y.tolist()}
        with open(d / f"data_batch_{i}", "wb") as f:
            pickle.dump(blob, f)
        all_y.append(y)
    vy = rng.randint(0, 10, n_per_batch)
    with open(d / "test_batch", "wb") as f:
        pickle.dump({"data": rng.randint(0, 256, (n_per_batch, 3072),
                                         dtype=np.uint8),
                     "labels": vy.tolist()}, f)
    return np.concatenate(all_y), vy


def write_cifar10_bin(root, n_per_batch=20, seed=0):
    d = root / "cifar-10-batches-bin"
    d.mkdir()
    rng = np.random.RandomState(seed)
    all_y = []
    for i in range(1, 6):
        y = rng.randint(0, 10, n_per_batch, dtype=np.uint8)
        px = rng.randint(0, 256, (n_per_batch, 3072), dtype=np.uint8)
        rec = np.concatenate([y[:, None], px], axis=1)
        rec.tofile(d / f"data_batch_{i}.bin")
        all_y.append(y)
    y = rng.randint(0, 10, n_per_batch, dtype=np.uint8)
    px = rng.randint(0, 256, (n_per_batch, 3072), dtype=np.uint8)
    np.concatenate([y[:, None], px], axis=1).tofile(d / "test_batch.bin")
    return np.concatenate(all_y).astype(np.int32), y.astype(np.int32)


def write_cifar100(root, n=30, seed=0):
    d = root / "cifar-100-python"
    d.mkdir()
    rng = np.random.RandomState(seed)
    out = {}
    for split in ("train", "test"):
        fine = rng.randint(0, 100, n)
        blob = {"data": rng.randint(0, 256, (n, 3072), dtype=np.uint8),
                "fine_labels": fine.tolist(),
                "coarse_labels": rng.randint(0, 20, n).tolist()}
        with open(d / split, "wb") as f:
            pickle.dump(blob, f)
        out[split] = fine
    return out["train"], out["test"]


def write_mnist(root, n_train=40, n_test=15, seed=0, gz=True):
    rng = np.random.RandomState(seed)
    out = {}
    for stem, n in [("train", n_train), ("t10k", n_test)]:
        imgs = rng.randint(0, 256, (n, 28, 28), dtype=np.uint8)
        labels = rng.randint(0, 10, n, dtype=np.uint8)
        ib = struct.pack(">4i", 2051, n, 28, 28) + imgs.tobytes()
        lb = struct.pack(">2i", 2049, n) + labels.tobytes()
        if gz:
            with gzip.open(root / f"{stem}-images-idx3-ubyte.gz", "wb") as f:
                f.write(ib)
            with gzip.open(root / f"{stem}-labels-idx1-ubyte.gz", "wb") as f:
                f.write(lb)
        else:
            (root / f"{stem}-images-idx3-ubyte").write_bytes(ib)
            (root / f"{stem}-labels-idx1-ubyte").write_bytes(lb)
        out[stem] = (imgs, labels)
    return out


# ---------------------------------------------------------------------------
# loader tests
# ---------------------------------------------------------------------------

class TestCifar10:
    def test_python_format(self, tmp_path):
        ty, vy = write_cifar10_py(tmp_path)
        tx, ty2, vx, vy2 = datasets.load_cifar10(str(tmp_path))
        assert tx.shape == (100, 3, 32, 32) and tx.dtype == np.uint8
        assert vx.shape == (20, 3, 32, 32)
        np.testing.assert_array_equal(ty2, ty)
        np.testing.assert_array_equal(vy2, vy)

    def test_binary_format(self, tmp_path):
        ty, vy = write_cifar10_bin(tmp_path)
        tx, ty2, vx, vy2 = datasets.load_cifar10(str(tmp_path))
        assert tx.shape == (100, 3, 32, 32)
        np.testing.assert_array_equal(ty2, ty)
        np.testing.assert_array_equal(vy2, vy)

    def test_formats_agree_on_same_data(self, tmp_path):
        """Same pixels through both wire formats parse identically."""
        (tmp_path / "py").mkdir()
        (tmp_path / "bin").mkdir()
        write_cifar10_py(tmp_path / "py", seed=7)
        # regenerate identical content in binary layout
        rng = np.random.RandomState(7)
        d = tmp_path / "bin" / "cifar-10-batches-bin"
        d.mkdir()
        for i in range(1, 6):
            y = rng.randint(0, 10, 20)
            px = rng.randint(0, 256, (20, 3072), dtype=np.uint8)
            np.concatenate([y.astype(np.uint8)[:, None], px],
                           axis=1).tofile(d / f"data_batch_{i}.bin")
        y = rng.randint(0, 10, 20)
        px = rng.randint(0, 256, (20, 3072), dtype=np.uint8)
        np.concatenate([y.astype(np.uint8)[:, None], px],
                       axis=1).tofile(d / "test_batch.bin")
        a = datasets.load_cifar10(str(tmp_path / "py"))
        b = datasets.load_cifar10(str(tmp_path / "bin"))
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    def test_missing_raises_with_hint(self, tmp_path):
        with pytest.raises(datasets.DatasetNotFoundError,
                           match="no downloads"):
            datasets.load_cifar10(str(tmp_path))

    def test_normalize(self):
        x = np.full((2, 3, 32, 32), 255, np.uint8)
        out = datasets.normalize_cifar(x)
        expect = (1.0 - datasets.CIFAR10_MEAN) / datasets.CIFAR10_STD
        # ALL three channels normalized (the reference's loop stops at
        # channel 1)
        for c in range(3):
            np.testing.assert_allclose(out[:, c], expect[c], rtol=1e-5)


class TestCifar100:
    def test_fine_labels(self, tmp_path):
        ty, vy = write_cifar100(tmp_path)
        tx, ty2, vx, vy2 = datasets.load_cifar100(str(tmp_path))
        assert tx.shape == (30, 3, 32, 32)
        np.testing.assert_array_equal(ty2, ty)
        np.testing.assert_array_equal(vy2, vy)


class TestMnist:
    @pytest.mark.parametrize("gz", [True, False])
    def test_idx_roundtrip(self, tmp_path, gz):
        ref = write_mnist(tmp_path, gz=gz)
        tx, ty, vx, vy = datasets.load_mnist(str(tmp_path))
        assert tx.shape == (40, 1, 28, 28) and tx.dtype == np.uint8
        assert vx.shape == (15, 1, 28, 28)
        np.testing.assert_array_equal(tx[:, 0], ref["train"][0])
        np.testing.assert_array_equal(ty, ref["train"][1])
        np.testing.assert_array_equal(vy, ref["t10k"][1])

    def test_bad_magic(self, tmp_path):
        (tmp_path / "train-images-idx3-ubyte").write_bytes(
            struct.pack(">4i", 1234, 1, 28, 28) + b"\0" * 784)
        (tmp_path / "train-labels-idx1-ubyte").write_bytes(
            struct.pack(">2i", 2049, 1) + b"\0")
        (tmp_path / "t10k-images-idx3-ubyte").write_bytes(b"")
        (tmp_path / "t10k-labels-idx1-ubyte").write_bytes(b"")
        with pytest.raises(ValueError, match="magic"):
            datasets.load_mnist(str(tmp_path))


class TestTransforms:
    def test_augment_shapes_and_content(self):
        rng = np.random.RandomState(3)
        x = rng.randn(8, 3, 32, 32).astype(np.float32)
        out = datasets.augment_crop_flip(x, rng=np.random.RandomState(0))
        assert out.shape == x.shape
        assert out.dtype == np.float32
        # crops come from the padded plane: every output row must exist
        # somewhere in the symmetric-padded input
        xpad = np.pad(x, [(0, 0), (0, 0), (4, 4), (4, 4)], "symmetric")
        assert np.isin(np.round(out[0, 0, 0], 5),
                       np.round(xpad[0, 0], 5)).all()

    def test_augment_identity_stats(self):
        """Augmentation permutes pixels (crop window of padded input),
        never invents values far outside the input range."""
        x = np.random.RandomState(1).rand(16, 3, 32, 32).astype(np.float32)
        out = datasets.augment_crop_flip(x)
        assert out.min() >= x.min() - 1e-6 and out.max() <= x.max() + 1e-6

    def test_resize_batch(self):
        x = np.random.RandomState(2).rand(4, 3, 32, 32).astype(np.float32)
        out = datasets.resize_batch(x, 16)
        assert out.shape == (4, 3, 16, 16)
        # no-op path returns same values
        same = datasets.resize_batch(x, 32)
        np.testing.assert_array_equal(same, x)

    def test_partition(self):
        x = np.arange(12)
        y = np.arange(12) * 10
        a, b = datasets.partition(1, 3, x, y)
        np.testing.assert_array_equal(a, [4, 5, 6, 7])
        np.testing.assert_array_equal(b, [40, 50, 60, 70])

    def test_dispatch_unknown(self):
        with pytest.raises(ValueError, match="unknown dataset"):
            datasets.load("imagenet")


# ---------------------------------------------------------------------------
# the north-star command, end-to-end on fixtures
# ---------------------------------------------------------------------------

def _run_train_cnn(args, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "examples/train_cnn.py"] + args,
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"stdout:{proc.stdout[-2000:]}\nstderr:{proc.stderr[-2000:]}"
    return proc.stdout


@pytest.mark.slow
class TestNorthStar:
    def test_resnet_cifar10(self, tmp_path):
        """`train_cnn.py resnet cifar10` — the SURVEY north-star —
        runs a real epoch slice: pickle ingestion, normalization,
        batched augmentation, 32->224 resize, training metrics, and a
        val-accuracy line."""
        write_cifar10_py(tmp_path, n_per_batch=4)
        out = _run_train_cnn(["resnet", "cifar10", "--data-dir",
                              str(tmp_path), "--cpu", "--bs", "4",
                              "--epochs", "1", "--max-batches", "1"])
        assert "Training loss" in out
        assert "Evaluation accuracy" in out

    def test_cnn_mnist(self, tmp_path):
        write_mnist(tmp_path, n_train=32, n_test=8)
        out = _run_train_cnn(["cnn", "mnist", "--data-dir", str(tmp_path),
                              "--cpu", "--bs", "8", "--epochs", "1"])
        assert "Training loss" in out
        assert "Evaluation accuracy" in out

    def test_mlp_cifar100(self, tmp_path):
        write_cifar100(tmp_path, n=24)
        out = _run_train_cnn(["mlp", "cifar100", "--data-dir",
                              str(tmp_path), "--cpu", "--bs", "8",
                              "--epochs", "1"])
        assert "Training loss" in out


class TestSearchRoots:
    """Relative search roots are anchored at the repo root, not the
    process cwd: a launcher starting a script from elsewhere must find
    the same datasets the interactive run found."""

    def test_repo_anchored_before_cwd(self):
        repo_data = os.path.join(datasets._REPO_ROOT, "data")
        assert os.path.isabs(repo_data)
        assert repo_data in datasets._SEARCH_ROOTS
        assert (datasets._SEARCH_ROOTS.index(repo_data)
                < datasets._SEARCH_ROOTS.index("data"))

    def test_resolution_survives_cwd_change(self, tmp_path, monkeypatch):
        # README.md lives at the repo root (one of the roots); resolving
        # it must work from any cwd. /tmp-style shared roots are masked
        # so a stray foreign file cannot flake the test.
        monkeypatch.setattr(
            datasets, "_SEARCH_ROOTS",
            [r for r in datasets._SEARCH_ROOTS
             if r not in ("/tmp", "/root/data")])
        monkeypatch.chdir(tmp_path)
        p = datasets._resolve(None, ["README.md"], "readme", "n/a")
        assert p == os.path.join(datasets._REPO_ROOT, "README.md")
