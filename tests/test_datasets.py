"""Real-dataset decode paths against tiny on-disk fixtures.

The registry mirrors the reference's ``DatasetCollection`` formats
(``dataset/dataset_collection.py:28-69``): the CIFAR-10 pickle batches, the
ImageFolder train/val tree, and the CUB-200-2011 metadata join. These tests
generate each format in ``tmp_path`` and assert ``load_dataset`` decodes
pixels, labels, and splits exactly — previously only the synthetic fallback
had coverage, so a refactor could break the real decoders invisibly.
"""

import os
import pickle

import numpy as np
import pytest
from PIL import Image

from distributed_model_parallel_tpu.config import DataConfig
from distributed_model_parallel_tpu.data.registry import (
    CIFAR10_MEAN,
    IMAGENET_MEAN,
    load_dataset,
)


def _write_cifar_batch(path, images_hwc, labels):
    """images_hwc: (N, 32, 32, 3) uint8 -> the on-disk (N, 3072) CHW rows."""
    data = images_hwc.transpose(0, 3, 1, 2).reshape(len(images_hwc), -1)
    with open(path, "wb") as f:
        pickle.dump({b"data": data.astype(np.uint8),
                     b"labels": [int(l) for l in labels]}, f)


def test_cifar10_pickle_decode(tmp_path):
    rng = np.random.default_rng(0)
    d = tmp_path / "cifar-10-batches-py"
    d.mkdir()
    train_imgs = rng.integers(0, 256, (10, 32, 32, 3)).astype(np.uint8)
    train_lbls = np.arange(10) % 10
    for i in range(5):  # 2 images per train batch file
        _write_cifar_batch(d / f"data_batch_{i + 1}",
                           train_imgs[2 * i:2 * i + 2],
                           train_lbls[2 * i:2 * i + 2])
    test_imgs = rng.integers(0, 256, (4, 32, 32, 3)).astype(np.uint8)
    test_lbls = np.asarray([3, 1, 4, 1])
    _write_cifar_batch(d / "test_batch", test_imgs, test_lbls)

    tr, te = load_dataset(DataConfig(name="cifar10", root=str(tmp_path),
                                     synthetic_ok=False))
    # Round-trip: the CHW->HWC transpose must restore the exact pixels, and
    # batch files must concatenate in order.
    np.testing.assert_array_equal(tr.images, train_imgs)
    np.testing.assert_array_equal(tr.labels, train_lbls)
    np.testing.assert_array_equal(te.images, test_imgs)
    np.testing.assert_array_equal(te.labels, test_lbls)
    assert tr.num_classes == 10
    np.testing.assert_allclose(tr.mean, CIFAR10_MEAN)


@pytest.mark.parametrize("name", ["imagenet", "place365"])
def test_imagefolder_decode(tmp_path, name):
    root = tmp_path / name
    rng = np.random.default_rng(1)
    # two classes; val must reuse train's class->index mapping
    pixels = {}
    for split, per_class in (("train", 2), ("val", 1)):
        for cls in ("ant", "bee"):
            cdir = root / split / cls
            cdir.mkdir(parents=True)
            for j in range(per_class):
                arr = rng.integers(0, 256, (8, 8, 3)).astype(np.uint8)
                Image.fromarray(arr).save(cdir / f"img{j}.png")
                pixels[(split, cls, j)] = arr
    tr, te = load_dataset(DataConfig(name=name, root=str(tmp_path),
                                     image_size=8, synthetic_ok=False))
    assert tr.images.shape == (4, 8, 8, 3) and te.images.shape == (2, 8, 8, 3)
    # classes sorted alphabetically: ant=0, bee=1; files sorted by name.
    np.testing.assert_array_equal(tr.labels, [0, 0, 1, 1])
    np.testing.assert_array_equal(te.labels, [0, 1])
    np.testing.assert_array_equal(tr.images[0], pixels[("train", "ant", 0)])
    np.testing.assert_array_equal(te.images[1], pixels[("val", "bee", 0)])
    assert tr.num_classes == 2
    np.testing.assert_allclose(tr.mean, IMAGENET_MEAN)


def test_imagefolder_resizes_to_image_size(tmp_path):
    root = tmp_path / "imagenet"
    for split in ("train", "val"):
        cdir = root / split / "only"
        cdir.mkdir(parents=True)
        Image.fromarray(np.full((32, 32, 3), 200, np.uint8)).save(
            cdir / "a.png")
    tr, _ = load_dataset(DataConfig(name="imagenet", root=str(tmp_path),
                                    image_size=16, synthetic_ok=False))
    assert tr.images.shape == (1, 16, 16, 3)
    assert int(tr.images[0, 0, 0, 0]) == 200    # constant image survives resize


def test_cub200_metadata_join(tmp_path):
    """The images.txt / image_class_labels.txt / train_test_split.txt join
    keyed on image id (reference dataset_collection.py:48-61): labels are
    1-based on disk, splits use 1=train."""
    root = tmp_path / "CUB_200_2011"
    rng = np.random.default_rng(2)
    rows = [  # (id, relpath, label_1based, is_train)
        (1, "001.Ant/a.png", 1, 1),
        (2, "001.Ant/b.png", 1, 0),
        (3, "002.Bee/c.png", 2, 1),
        (4, "002.Bee/d.png", 2, 1),
    ]
    pixels = {}
    for img_id, rel, _, _ in rows:
        p = root / "images" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        arr = rng.integers(0, 256, (8, 8, 3)).astype(np.uint8)
        Image.fromarray(arr).save(p)
        pixels[img_id] = arr
    (root / "images.txt").write_text(
        "".join(f"{i} {rel}\n" for i, rel, _, _ in rows))
    (root / "image_class_labels.txt").write_text(
        "".join(f"{i} {lbl}\n" for i, _, lbl, _ in rows))
    (root / "train_test_split.txt").write_text(
        "".join(f"{i} {t}\n" for i, _, _, t in rows))

    tr, te = load_dataset(DataConfig(name="cub200", root=str(tmp_path),
                                     image_size=8, synthetic_ok=False))
    assert tr.images.shape == (3, 8, 8, 3) and te.images.shape == (1, 8, 8, 3)
    np.testing.assert_array_equal(tr.labels, [0, 1, 1])   # 1-based -> 0-based
    np.testing.assert_array_equal(te.labels, [0])
    np.testing.assert_array_equal(tr.images[0], pixels[1])
    np.testing.assert_array_equal(te.images[0], pixels[2])
    assert tr.num_classes == 2


def test_missing_dataset_raises_when_synthetic_disallowed(tmp_path):
    with pytest.raises(FileNotFoundError, match="synthetic_ok"):
        load_dataset(DataConfig(name="cifar10", root=str(tmp_path / "none"),
                                synthetic_ok=False))


def _make_imagefolder(root, n_per_class=3, size=8, classes=("ant", "bee")):
    rng = np.random.default_rng(7)
    for split, per in (("train", n_per_class), ("val", 1)):
        for cls in classes:
            cdir = root / split / cls
            cdir.mkdir(parents=True)
            for j in range(per):
                arr = rng.integers(0, 256, (size, size, 3)).astype(np.uint8)
                Image.fromarray(arr).save(cdir / f"img{j}.png")


def test_lazy_decode_streams_without_materializing(tmp_path, monkeypatch):
    """An on-disk ImageFolder larger than the in-memory cap streams through
    BatchLoader: host memory holds the path list, batches decode on access,
    and whole-array conversion is refused loudly."""
    from distributed_model_parallel_tpu.data import registry
    from distributed_model_parallel_tpu.data.loader import BatchLoader
    from distributed_model_parallel_tpu.data.registry import LazyImageArray

    root = tmp_path / "imagenet"
    _make_imagefolder(root, n_per_class=4)
    # Cap of 0 bytes: ANY dataset exceeds it -> the auto path must stream.
    monkeypatch.setattr(registry, "LAZY_AUTO_BYTES", 0)
    tr, te = load_dataset(DataConfig(name="imagenet", root=str(tmp_path),
                                     image_size=8, synthetic_ok=False))
    assert isinstance(tr.images, LazyImageArray) and tr.is_lazy
    assert tr.images.shape == (8, 8, 8, 3)
    with pytest.raises(TypeError, match="refusing to materialize"):
        np.asarray(tr.images)

    batches = list(BatchLoader(tr, batch_size=4, shuffle=False))
    assert len(batches) == 2
    assert batches[0][0].shape == (4, 8, 8, 3)
    assert batches[0][0].dtype == np.uint8

    # Lazy and eager must produce identical pixels for identical indices.
    tr_eager, _ = load_dataset(DataConfig(name="imagenet", root=str(tmp_path),
                                          image_size=8, synthetic_ok=False,
                                          lazy_decode=False))
    assert isinstance(tr_eager.images, np.ndarray)
    got = np.concatenate([b[0] for b in batches])
    np.testing.assert_array_equal(got, tr_eager.images)
    np.testing.assert_array_equal(tr.labels, tr_eager.labels)


def test_lazy_decode_explicit_flag(tmp_path):
    """lazy_decode=True streams even a tiny dataset; single-index access
    decodes one image."""
    from distributed_model_parallel_tpu.data.registry import LazyImageArray

    root = tmp_path / "imagenet"
    _make_imagefolder(root)
    tr, _ = load_dataset(DataConfig(name="imagenet", root=str(tmp_path),
                                    image_size=8, synthetic_ok=False,
                                    lazy_decode=True))
    assert isinstance(tr.images, LazyImageArray)
    one = tr.images[0]
    assert one.shape == (8, 8, 3) and one.dtype == np.uint8
    np.testing.assert_array_equal(tr.images[np.asarray([0])][0], one)


def test_lazy_cub200_streams(tmp_path):
    """The CUB metadata join builds path lists; lazy_decode=True streams."""
    from distributed_model_parallel_tpu.data.registry import LazyImageArray

    root = tmp_path / "CUB_200_2011"
    rng = np.random.default_rng(3)
    rows = [(1, "001.Ant/a.png", 1, 1), (2, "001.Ant/b.png", 1, 0),
            (3, "002.Bee/c.png", 2, 1), (4, "002.Bee/d.png", 2, 1)]
    (root / "images").mkdir(parents=True)
    for _, rel, _, _ in rows:
        p = root / "images" / rel
        p.parent.mkdir(exist_ok=True)
        Image.fromarray(
            rng.integers(0, 256, (8, 8, 3)).astype(np.uint8)).save(p)
    (root / "images.txt").write_text(
        "".join(f"{i} {rel}\n" for i, rel, _, _ in rows))
    (root / "image_class_labels.txt").write_text(
        "".join(f"{i} {l}\n" for i, _, l, _ in rows))
    (root / "train_test_split.txt").write_text(
        "".join(f"{i} {t}\n" for i, _, _, t in rows))
    tr, te = load_dataset(DataConfig(name="cub200", root=str(tmp_path),
                                     image_size=8, synthetic_ok=False,
                                     lazy_decode=True))
    assert isinstance(tr.images, LazyImageArray)
    assert len(tr) == 3 and len(te) == 1
    assert tr.images[np.asarray([0, 1, 2])].shape == (3, 8, 8, 3)


def test_device_resident_rejects_lazy_dataset(tmp_path):
    """device_resident_data needs materialized pixels; a lazily-streamed
    dataset must be rejected with a message naming lazy_decode=False."""
    from distributed_model_parallel_tpu.config import (
        MeshConfig,
        ModelConfig,
        OptimizerConfig,
        TrainConfig,
    )
    from distributed_model_parallel_tpu.train.trainer import Trainer

    root = tmp_path / "imagenet"
    _make_imagefolder(root, n_per_class=8, size=32)
    cfg = TrainConfig(
        model=ModelConfig(name="tinycnn"),
        data=DataConfig(name="imagenet", root=str(tmp_path), image_size=32,
                        batch_size=8, eval_batch_size=2, synthetic_ok=False,
                        lazy_decode=True, augment=False),
        optimizer=OptimizerConfig(learning_rate=0.1, warmup_steps=0),
        mesh=MeshConfig(data=8),
        device_resident_data=True,
        log_dir=str(tmp_path / "log"), checkpoint_dir=str(tmp_path / "ck"))
    with pytest.raises(ValueError, match="lazy_decode=False"):
        Trainer(cfg)
