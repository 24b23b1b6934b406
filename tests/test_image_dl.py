"""Image ops/stages + ImageFeaturizer + ModelDownloader + zoo models."""

import numpy as np
import pytest

from synapseml_tpu.core import Table
from synapseml_tpu.dl import ImageFeaturizer, ModelDownloader, ZooRepository
from synapseml_tpu.image import (
    ImageSetAugmenter,
    ImageTransformer,
    ResizeImageTransformer,
    UnrollImage,
)
from synapseml_tpu.image import ops as iops


@pytest.fixture
def imgs():
    rng = np.random.default_rng(0)
    return rng.integers(0, 255, size=(4, 12, 10, 3)).astype(np.uint8)


@pytest.fixture
def t(imgs):
    return Table({"image": imgs, "id": np.arange(4)})


def test_resize_crop_flip(imgs):
    out = np.asarray(iops.resize(imgs, 6, 5))
    assert out.shape == (4, 6, 5, 3)
    out = np.asarray(iops.crop(imgs, 2, 1, 4, 6))
    assert out.shape == (4, 6, 4, 3)
    np.testing.assert_array_equal(out, imgs[:, 1:7, 2:6, :])
    out = np.asarray(iops.center_crop(imgs, 4, 4))
    assert out.shape == (4, 4, 4, 3)
    np.testing.assert_array_equal(np.asarray(iops.flip(imgs, 1)), imgs[:, :, ::-1, :])
    np.testing.assert_array_equal(np.asarray(iops.flip(imgs, 0)), imgs[:, ::-1, :, :])


def test_gaussian_blur_preserves_mean(imgs):
    x = imgs.astype(np.float32)
    out = np.asarray(iops.gaussian_blur(x, 5, 1.0))
    assert out.shape == x.shape
    # blur is mean-preserving-ish with edge padding
    np.testing.assert_allclose(out.mean(), x.mean(), rtol=0.05)
    # and reduces variance
    assert out.var() < x.var()


def test_gaussian_kernel_matches_scipy():
    from scipy.ndimage import gaussian_filter

    rng = np.random.default_rng(1)
    x = rng.normal(size=(1, 32, 32, 1)).astype(np.float32)
    out = np.asarray(iops.gaussian_blur(x, 9, 2.0))[0, :, :, 0]
    ref = gaussian_filter(x[0, :, :, 0], sigma=2.0, mode="nearest", truncate=2.0)
    # interior should match closely (edge handling differs slightly)
    np.testing.assert_allclose(out[8:-8, 8:-8], ref[8:-8, 8:-8], rtol=0.02, atol=0.01)


def test_color_convert(imgs):
    rgb = np.asarray(iops.color_convert(imgs, "bgr2rgb"))
    np.testing.assert_array_equal(rgb, imgs[..., ::-1])
    gray = np.asarray(iops.color_convert(imgs, "bgr2gray"))
    assert gray.shape == (4, 12, 10, 1)
    expected = imgs[..., 0] * 0.114 + imgs[..., 1] * 0.587 + imgs[..., 2] * 0.299
    np.testing.assert_allclose(gray[..., 0], expected, rtol=1e-4)


def test_image_transformer_stage_list(t):
    out = ImageTransformer(
        stages=[
            {"action": "resize", "height": 8, "width": 8},
            {"action": "gaussiankernel", "aperturesize": 3, "sigma": 1.0},
            {"action": "centercrop", "height": 6, "width": 6},
            {"action": "flip", "flipcode": 1},
        ]
    ).transform(t)
    assert out["image"].shape == (4, 6, 6, 3)


def test_image_transformer_ragged_input():
    rng = np.random.default_rng(2)
    col = np.empty(3, dtype=object)
    for i, (h, w) in enumerate([(10, 8), (12, 12), (7, 9)]):
        col[i] = rng.integers(0, 255, size=(h, w, 3)).astype(np.uint8)
    t = Table({"image": col})
    out = ImageTransformer(stages=[{"action": "resize", "height": 6, "width": 6}]).transform(t)
    assert out["image"].shape == (3, 6, 6, 3)


def test_resize_shorter_side():
    rng = np.random.default_rng(3)
    img = rng.integers(0, 255, size=(100, 50, 3)).astype(np.uint8)
    out = iops.resize_shorter(img, 25)
    assert out.shape == (50, 25, 3)


def test_unroll_image(t):
    small = ResizeImageTransformer(height=4, width=4).transform(t)
    out = UnrollImage(output_col="feat").transform(small)
    assert out["feat"].shape == (4, 48)


def test_image_set_augmenter(t):
    out = ImageSetAugmenter(flip_left_right=True, flip_up_down=True).transform(t)
    assert out.num_rows == 12
    assert out["id"].tolist() == [0, 1, 2, 3] * 3


def test_model_downloader_cache_and_hash(tmp_path):
    dl = ModelDownloader(str(tmp_path / "models"))
    names = [s.name for s in dl.remote_models()]
    assert "ResNet50" in names and "BERTTiny" in names
    schema = dl.download_by_name("BERTTiny")
    assert schema.sha256 and schema.size > 0
    # cached second call, and bytes identical (deterministic zoo)
    again = dl.download_by_name("BERTTiny")
    assert again.sha256 == schema.sha256
    data = dl.local.read_bytes(schema)
    assert len(data) == schema.size
    # corrupt the file -> hash check trips
    import os

    p = os.path.join(dl.local.base_dir, schema.path)
    with open(p, "r+b") as f:
        f.write(b"corrupt!")
    with pytest.raises(IOError, match="hash mismatch"):
        dl.local.read_bytes(schema)


def test_resnet18_zoo_runs():
    from synapseml_tpu.models import build_model_bytes
    from synapseml_tpu.onnx import OnnxFunction

    fn = OnnxFunction(build_model_bytes("ResNet18", num_classes=10))
    x = np.random.default_rng(4).normal(size=(2, 3, 224, 224)).astype(np.float32)
    out = fn({"data": x})
    assert np.asarray(out["logits"]).shape == (2, 10)
    assert np.asarray(out["features"]).shape == (2, 512)
    assert np.isfinite(np.asarray(out["logits"])).all()


def test_bert_tiny_zoo_runs():
    from synapseml_tpu.models import build_model_bytes
    from synapseml_tpu.onnx import OnnxFunction

    fn = OnnxFunction(build_model_bytes("BERTTiny", num_classes=3))
    ids = np.random.default_rng(5).integers(0, 1000, size=(2, 16)).astype(np.int64)
    out = fn({"input_ids": ids})
    assert np.asarray(out["logits"]).shape == (2, 3)
    assert np.asarray(out["pooled"]).shape == (2, 128)
    assert np.asarray(out["sequence"]).shape == (2, 16, 128)


def test_image_featurizer_end_to_end(tmp_path):
    """The minimum end-to-end slice (SURVEY.md §7 phase 3): images -> headless CNN
    features through the full pipeline machinery."""
    from synapseml_tpu.models import build_model_bytes

    rng = np.random.default_rng(6)
    imgs = rng.integers(0, 255, size=(3, 40, 40, 3)).astype(np.uint8)
    t = Table({"image": imgs, "label": np.array([0, 1, 0])})
    feat = ImageFeaturizer(
        model_bytes=build_model_bytes("ResNet18", num_classes=7),
        image_height=64, image_width=64, batch_size=2,
    )
    out = feat.transform(t)
    assert out["features"].shape == (3, 512)
    assert np.isfinite(out["features"]).all()
    # cut_output_layers=0 -> logits head
    logits = ImageFeaturizer(
        model_bytes=build_model_bytes("ResNet18", num_classes=7),
        image_height=64, image_width=64, cut_output_layers=0,
    ).transform(t)
    assert logits["features"].shape == (3, 7)


def test_remote_repository_http_with_hash_verification(tmp_path):
    """HTTP repo with sha256 verification + downloader caching (reference
    ModelDownloader.scala:26-263 remote-blob contract; VERDICT r03 missing
    #6). Served from a local static HTTP server — same wire protocol."""
    import hashlib
    import json
    import threading
    from functools import partial
    from http.server import SimpleHTTPRequestHandler, ThreadingHTTPServer

    from synapseml_tpu.dl import ModelDownloader, RemoteRepository
    from synapseml_tpu.models.zoo import build_model_bytes

    # stage a repo directory: index.json + payload
    repo_dir = tmp_path / "repo"
    repo_dir.mkdir()
    payload = build_model_bytes("BERTTiny")
    (repo_dir / "berttiny.onnx").write_bytes(payload)
    good = {"name": "BERTTiny", "path": "berttiny.onnx",
            "sha256": hashlib.sha256(payload).hexdigest(),
            "size": len(payload), "input_name": "input_ids"}
    bad = dict(good, name="Corrupt", sha256="0" * 64)
    (repo_dir / "index.json").write_text(json.dumps([good, bad]))

    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        partial(SimpleHTTPRequestHandler, directory=str(repo_dir)))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        remote = RemoteRepository(base, backoffs_ms=())
        names = [s.name for s in remote.list_schemas()]
        assert names == ["BERTTiny", "Corrupt"]
        # verified fetch through the downloader, cached into the local repo
        dl = ModelDownloader(str(tmp_path / "cache"), remote=remote)
        schema = dl.download_by_name("BERTTiny")
        assert dl.local.read_bytes(schema) == payload
        # second call serves from cache (kill the server to prove it)
        httpd.shutdown()
        schema2 = dl.download_by_name("BERTTiny")
        assert dl.local.read_bytes(schema2) == payload
    finally:
        httpd.server_close()


def test_remote_repository_rejects_corrupt_payload(tmp_path):
    import hashlib
    import json
    import threading
    from functools import partial
    from http.server import SimpleHTTPRequestHandler, ThreadingHTTPServer

    import pytest

    from synapseml_tpu.dl import RemoteRepository

    repo_dir = tmp_path / "repo"
    repo_dir.mkdir()
    (repo_dir / "m.bin").write_bytes(b"tampered")
    (repo_dir / "index.json").write_text(json.dumps(
        [{"name": "M", "path": "m.bin",
          "sha256": hashlib.sha256(b"original").hexdigest()}]))
    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        partial(SimpleHTTPRequestHandler, directory=str(repo_dir)))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        remote = RemoteRepository(base, backoffs_ms=())
        with pytest.raises(IOError, match="hash mismatch"):
            remote.read_bytes(remote.get_schema("M"))
    finally:
        httpd.shutdown()
        httpd.server_close()
