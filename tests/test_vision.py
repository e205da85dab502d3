"""Vision package tests: model zoo shapes, transforms vs numpy oracle,
datasets from synthetic files, and a LeNet convergence gate.

Mirrors the reference's strategy (SURVEY §4): book-style convergence
thresholds (reference: python/paddle/fluid/tests/book/test_recognize_digits.py:126)
and numpy-oracle checks for image ops.
"""
import gzip
import os
import struct

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.vision import transforms as T
from paddle_tpu.vision import models as M
from paddle_tpu.vision import datasets as D
from paddle_tpu.vision.transforms import functional as TF


# --- models -----------------------------------------------------------------

def test_resnet_nhwc_matches_nchw():
    # data_format="NHWC" is the TPU-preferred layout (chip_smoke.py uses it);
    # same state_dict must produce identical outputs on transposed input
    paddle.seed(0)
    m1 = M.resnet18(num_classes=10)
    m2 = M.resnet18(num_classes=10, data_format="NHWC")
    m2.set_state_dict(m1.state_dict())
    m1.eval(); m2.eval()
    x = np.random.RandomState(0).uniform(-1, 1, (2, 3, 64, 64)).astype(np.float32)
    o1 = np.asarray(m1(paddle.to_tensor(x)))
    o2 = np.asarray(m2(paddle.to_tensor(x.transpose(0, 2, 3, 1))))
    assert np.abs(o1 - o2).max() < 2e-4


@pytest.mark.parametrize("mk,shape", [
    (lambda df: M.LeNet(data_format=df), (2, 1, 28, 28)),
    (lambda df: M.MobileNetV1(num_classes=5, data_format=df), (1, 3, 64, 64)),
    (lambda df: M.MobileNetV2(num_classes=5, data_format=df), (1, 3, 64, 64)),
    (lambda df: M.vgg11(batch_norm=True, num_classes=5, data_format=df),
     (1, 3, 224, 224)),
])
def test_model_zoo_nhwc_matches_nchw(mk, shape):
    # every zoo model runs the TPU-preferred layout off the SAME state_dict
    paddle.seed(0)
    m1 = mk("NCHW")
    m2 = mk("NHWC")
    m2.set_state_dict(m1.state_dict())
    m1.eval(); m2.eval()
    x = np.random.RandomState(0).uniform(-1, 1, shape).astype(np.float32)
    o1 = np.asarray(m1(paddle.to_tensor(x)))
    o2 = np.asarray(m2(paddle.to_tensor(x.transpose(0, 2, 3, 1))))
    assert np.abs(o1 - o2).max() < 5e-4


def test_lenet_forward():
    net = M.LeNet()
    out = net(np.zeros((2, 1, 28, 28), np.float32))
    assert out.shape == (2, 10)


@pytest.mark.parametrize("ctor,depth", [(M.resnet18, 18), (M.resnet50, 50)])
def test_resnet_forward(ctor, depth):
    net = ctor(num_classes=7)
    out = net(np.zeros((2, 3, 64, 64), np.float32))
    assert out.shape == (2, 7)


def test_resnet50_param_count():
    net = M.resnet50()
    n = sum(int(np.prod(p.shape)) for p in net.parameters())
    assert n == 25_557_032  # canonical ResNet-50 ImageNet param count


def test_resnet_no_pool_no_fc():
    net = M.ResNet(M.BasicBlock, 18, num_classes=-1, with_pool=False)
    out = net(np.zeros((1, 3, 32, 32), np.float32))
    assert out.shape == (1, 512, 1, 1)


def test_vgg16_forward():
    net = M.vgg16(num_classes=5)
    out = net(np.zeros((1, 3, 224, 224), np.float32))
    assert out.shape == (1, 5)


def test_mobilenet_v1_v2_forward():
    for ctor in (M.mobilenet_v1, M.mobilenet_v2):
        net = ctor(num_classes=4)
        out = net(np.zeros((1, 3, 64, 64), np.float32))
        assert out.shape == (1, 4)


def test_pretrained_requires_local_path():
    with pytest.raises(ValueError, match="no pretrained-weight download"):
        M.resnet18(pretrained=True)


# --- transforms -------------------------------------------------------------

def test_to_tensor_scales_and_chw():
    img = np.full((4, 6, 3), 255, np.uint8)
    out = TF.to_tensor(img)
    assert out.shape == (3, 4, 6)
    np.testing.assert_allclose(out, 1.0)


def test_resize_int_short_side():
    img = np.zeros((40, 80, 3), np.uint8)
    out = TF.resize(img, 20)
    assert out.shape[:2] == (20, 40)


def test_center_crop_and_crop():
    img = np.arange(5 * 5).reshape(5, 5, 1).astype(np.uint8)
    out = TF.center_crop(img, 3)
    np.testing.assert_array_equal(out[..., 0], img[1:4, 1:4, 0])


def test_flips():
    img = np.arange(6).reshape(2, 3, 1).astype(np.uint8)
    np.testing.assert_array_equal(TF.hflip(img)[..., 0], img[:, ::-1, 0])
    np.testing.assert_array_equal(TF.vflip(img)[..., 0], img[::-1, :, 0])


def test_normalize_chw():
    img = np.ones((3, 2, 2), np.float32)
    out = TF.normalize(img, mean=[1, 1, 1], std=[2, 2, 2])
    np.testing.assert_allclose(out, 0.0)


def test_pad_constant():
    img = np.ones((2, 2, 1), np.uint8)
    out = TF.pad(img, 1)
    assert out.shape == (4, 4, 1)
    assert out[0, 0, 0] == 0


def test_compose_pipeline():
    tf = T.Compose([
        T.Resize(8), T.CenterCrop(8), T.ToTensor(),
        T.Normalize(mean=[0.5, 0.5, 0.5], std=[0.5, 0.5, 0.5]),
    ])
    out = tf(np.random.RandomState(0).randint(0, 255, (16, 16, 3), dtype=np.uint8).astype(np.uint8))
    assert out.shape == (3, 8, 8)


def test_random_crop_shape():
    img = np.zeros((10, 10, 3), np.uint8)
    out = T.RandomCrop(6)._apply_image(img)
    assert TF._to_numpy(out).shape[:2] == (6, 6)


def test_color_jitter_runs():
    img = np.random.RandomState(0).randint(0, 255, (8, 8, 3)).astype(np.uint8)
    out = T.ColorJitter(0.4, 0.4, 0.4, 0.1)._apply_image(img)
    assert TF._to_numpy(out).shape == (8, 8, 3)


def test_base_transform_keys_passthrough():
    tf = T.RandomHorizontalFlip(prob=1.0, keys=("image", None))
    img = np.arange(6).reshape(2, 3, 1).astype(np.uint8)
    out_img, label = tf((img, 7))
    assert label == 7
    np.testing.assert_array_equal(TF._to_numpy(out_img)[..., 0], img[:, ::-1, 0])


# --- datasets ---------------------------------------------------------------

def _write_idx(tmpdir, n=32):
    rng = np.random.RandomState(0)
    images = rng.randint(0, 255, (n, 28, 28), dtype=np.uint8)
    labels = rng.randint(0, 10, (n,)).astype(np.uint8)
    img_path = os.path.join(tmpdir, "imgs.gz")
    lbl_path = os.path.join(tmpdir, "lbls.gz")
    with gzip.open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(images.tobytes())
    with gzip.open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    return img_path, lbl_path, images, labels


def test_mnist_reads_idx(tmp_path):
    img_path, lbl_path, images, labels = _write_idx(str(tmp_path))
    ds = D.MNIST(image_path=img_path, label_path=lbl_path, mode="train")
    assert len(ds) == 32
    img, label = ds[3]
    assert img.shape == (1, 28, 28)
    np.testing.assert_array_equal(img[0], images[3].astype(np.float32))
    assert int(label) == int(labels[3])


def test_mnist_missing_file_is_actionable(tmp_path):
    with pytest.raises(FileNotFoundError, match="no network egress"):
        D.MNIST(image_path=str(tmp_path / "nope.gz"),
                label_path=str(tmp_path / "nope2.gz"))


def test_cifar_reads_archive(tmp_path):
    import pickle
    import tarfile

    rng = np.random.RandomState(0)
    data = rng.randint(0, 255, (10, 3072), dtype=np.uint8)
    labels = rng.randint(0, 10, (10,)).tolist()
    batch = {b"data": data, b"labels": labels}
    batch_file = tmp_path / "data_batch_1"
    with open(batch_file, "wb") as f:
        pickle.dump(batch, f)
    archive = tmp_path / "cifar-10-python.tar.gz"
    with tarfile.open(archive, "w:gz") as tar:
        tar.add(batch_file, arcname="cifar-10-batches-py/data_batch_1")
    ds = D.Cifar10(data_file=str(archive), mode="train")
    assert len(ds) == 10
    img, label = ds[0]
    assert img.shape == (3, 32, 32)
    assert int(label) == labels[0]


def test_dataset_folder(tmp_path):
    from PIL import Image

    for cls in ("cat", "dog"):
        os.makedirs(tmp_path / cls)
        for i in range(3):
            Image.fromarray(
                np.random.RandomState(i).randint(0, 255, (8, 8, 3), dtype=np.uint8)
            ).save(tmp_path / cls / f"{i}.png")
    ds = D.DatasetFolder(str(tmp_path))
    assert ds.classes == ["cat", "dog"]
    assert len(ds) == 6
    img, label = ds[0]
    assert img.shape == (8, 8, 3)
    assert int(label) == 0


def test_image_folder(tmp_path):
    from PIL import Image

    for i in range(4):
        Image.fromarray(np.zeros((8, 8, 3), np.uint8)).save(tmp_path / f"{i}.jpg")
    ds = D.ImageFolder(str(tmp_path))
    assert len(ds) == 4
    (img,) = ds[0]
    assert img.shape == (8, 8, 3)


# --- convergence gate (book-test style) -------------------------------------

def test_resnet50_amp_dp_plan():
    """ResNet-50 trains AMP-O1 under an 8-device
    data-parallel fleet plan (batch sharded over the mesh, momentum with
    f32 master weights)."""
    from paddle_tpu import optimizer as popt
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import build_mesh, set_mesh

    fleet._initialized = False
    set_mesh(build_mesh())
    try:
        fleet.init(is_collective=True, strategy=fleet.DistributedStrategy())
        paddle.seed(0)
        net = M.resnet50(num_classes=4)
        opt = fleet.distributed_optimizer(
            popt.Momentum(learning_rate=0.01, momentum=0.9,
                          multi_precision=True))
        model = paddle.Model(net)
        model.prepare(optimizer=opt, loss=nn.CrossEntropyLoss(),
                      amp_configs="O1")
        assert model._plan is not None and model._plan.n_data_shards == 8

        rng = np.random.RandomState(0)
        labels = rng.randint(0, 4, (16,))
        x = rng.normal(0, 0.5, (16, 3, 64, 64)).astype(np.float32)
        for i, y in enumerate(labels):  # separable: class tints a channel
            x[i, int(y) % 3] += 1.0 + 0.5 * int(y)

        w_before = np.asarray(net.conv1.weight.value).copy()
        losses = [model.train_batch([x], [labels[:, None]])[0]
                  for _ in range(3)]
        assert all(np.isfinite(l) for l in losses), losses
        # the step actually trained (3 steps of a fresh BN net need not
        # decrease the loss yet — LeNet covers convergence)
        assert not np.allclose(w_before, np.asarray(net.conv1.weight.value))
        # AMP is engaged: conv compute runs in bf16 inside the traced step
        import jax
        import jax.numpy as jnp
        from paddle_tpu.amp import auto_cast

        params, _ = model._pull_state()

        def fwd(p):
            with auto_cast(level="O1"):
                return nn.functional_call(net, p, jnp.asarray(x),
                                          training=True)

        jaxpr = str(jax.make_jaxpr(fwd)(params))
        assert "bf16" in jaxpr, "O1 autocast left no bf16 compute in the step"
    finally:
        fleet._initialized = False
        fleet._strategy = None
        set_mesh(build_mesh())


def test_lenet_convergence_synthetic_digits():
    """Train LeNet on a synthetic separable 10-class image problem and
    assert the loss drops and accuracy rises
    (ref: tests/book/test_recognize_digits.py asserts acc within a run)."""
    from paddle_tpu import optimizer as popt
    from paddle_tpu.metric import Accuracy

    paddle.seed(0)
    rng = np.random.RandomState(0)
    n, n_classes = 256, 10
    labels = rng.randint(0, n_classes, (n,))
    # each class lights up one distinct 7x7 quadrant cell + noise
    images = rng.normal(0, 0.3, (n, 1, 28, 28)).astype(np.float32)
    for i, y in enumerate(labels):
        r, c = divmod(int(y), 4)
        images[i, 0, r * 7:(r + 1) * 7, c * 7:(c + 1) * 7] += 2.0

    net = M.LeNet()
    model = paddle.Model(net)
    model.prepare(optimizer=popt.Adam(learning_rate=1e-3),
                  loss=nn.CrossEntropyLoss(), metrics=[Accuracy()])
    first = None
    for epoch in range(6):
        order = rng.permutation(n)
        for start in range(0, n, 64):
            idx = order[start:start + 64]
            loss, _ = model.train_batch([images[idx]], [labels[idx][:, None]])
            if first is None:
                first = loss
    acc = model._metrics[0].accumulate()
    assert loss < first * 0.5, (first, loss)
    assert acc > 0.7, acc


def test_resnet_stem_space_to_depth_exact():
    """stem_space_to_depth rewrites the 7x7/s2 stem as the equivalent
    4x4/s1 conv on 2x2 space-to-depth input (tools/resnet_mfu_analysis.md)
    — same parameters, same math, bit-level parity up to matmul reorder."""
    import jax

    from paddle_tpu.vision.models import resnet18

    paddle.seed(0)
    net1 = resnet18(data_format="NHWC")
    net2 = resnet18(data_format="NHWC", stem_space_to_depth=True)
    net2.set_state_dict(net1.state_dict())
    net1.eval()
    net2.eval()
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0).randn(2, 224, 224, 3)
                    .astype(np.float32))
    o1, o2 = np.asarray(net1(x)), np.asarray(net2(x))
    np.testing.assert_allclose(o1, o2, atol=1e-3)
    # grads flow through the re-gathered stem weights
    from paddle_tpu.nn.layer_base import functional_call

    params = {k: v.value for k, v in net2.named_parameters()}
    g = jax.grad(lambda p: functional_call(net2, p, x).sum())(params)
    gw = np.asarray(g["conv1.weight"])
    assert np.abs(gw).sum() > 0
