"""Model zoo tests (reference: ``DLT/models/*Spec.scala`` — shape and
parameter-count checks per reference model)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import autoencoder, inception, lenet, resnet, vgg


def _fwd(model, shape, training=False, rng=None):
    p, s = model.init(jax.random.key(0))
    out, _ = model.apply(p, jnp.zeros(shape, jnp.float32), state=s, training=training, rng=rng)
    return p, out


def test_resnet_cifar_shapes():
    model = resnet.build_cifar(depth=20, class_num=10)
    p, out = _fwd(model, (2, 3, 32, 32))
    assert out.shape == (2, 10)
    assert model.n_parameters(p) == 269722  # golden for this build (~0.27M, He et al.)


@pytest.mark.parametrize("depth,count", [(18, 11689512), (50, 25557032)])
def test_resnet_imagenet_param_counts(depth, count):
    model = resnet.build_imagenet(depth, 1000)
    p, s = model.init(jax.random.key(0))
    assert model.n_parameters(p) == count


def test_resnet_shortcut_type_a_pads_channels():
    model = resnet.build_cifar(depth=8, class_num=10, shortcut_type="A")
    p, out = _fwd(model, (2, 3, 32, 32))
    assert out.shape == (2, 10)


def test_resnet_trains():
    model = resnet.build_cifar(depth=8, class_num=10)
    from bigdl_tpu.nn import CrossEntropyCriterion

    crit = CrossEntropyCriterion()
    p, s = model.init(jax.random.key(0))
    x = jnp.asarray(np.random.rand(4, 3, 32, 32), jnp.float32)
    y = jnp.asarray([1, 2, 3, 4], jnp.int32)

    def loss_fn(p):
        out, _ = model.apply(p, x, state=s, training=True)
        return crit(out, y)

    l0 = loss_fn(p)
    g = jax.grad(loss_fn)(p)
    p2 = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
    assert float(loss_fn(p2)) < float(l0)


def test_vgg16_param_count():
    model = vgg.build_vgg16(1000)
    p, s = model.init(jax.random.key(0))
    assert model.n_parameters(p) == 138357544  # canonical VGG-16


def test_vgg_cifar_forward():
    model = vgg.build_cifar(10)
    p, out = _fwd(model, (2, 3, 32, 32))
    assert out.shape == (2, 10)
    # LogSoftMax output: rows sum to 1 in prob space
    np.testing.assert_allclose(np.exp(np.asarray(out)).sum(1), 1.0, rtol=1e-4)


def test_inception_v1_forward():
    model = inception.build(1000, has_dropout=False)
    p, out = _fwd(model, (1, 3, 224, 224))
    assert out.shape == (1, 1000)
    assert model.n_parameters(p) == 6998552  # canonical GoogLeNet (no aux)


def test_autoencoder_reconstruction_shape():
    model = autoencoder.build(32)
    p, out = _fwd(model, (2, 1, 28, 28))
    assert out.shape == (2, 784)
    assert float(out.min()) >= 0.0 and float(out.max()) <= 1.0


@pytest.mark.slow
def test_graft_entry_contract():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # multichip dry run on the virtual CPU mesh
    mod.dryrun_multichip(4)


def test_graft_entry_refuses_a_shortfall_of_devices():
    """Asked for more devices than JAX sees, the dry run raises — it never
    re-runs itself on virtual CPU devices and reports ok."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(RuntimeError, match="found 8 cpu device"):
        mod.dryrun_multichip(16)


def test_resnet_nhwc_matches_nchw():
    """The TPU-preferred channels-last ResNet computes the same function
    as the NCHW build given transposed input and identical params (the
    param trees share shapes: conv weights stay OIHW in both layouts)."""
    import jax
    import numpy as np

    from bigdl_tpu.models import resnet

    m_nchw = resnet.build_imagenet(18, 7)
    m_nhwc = resnet.build_imagenet(18, 7, data_format="NHWC")
    params, state = m_nchw.init(jax.random.key(3))
    x = np.random.RandomState(0).rand(2, 3, 64, 64).astype(np.float32)
    out_c, _ = m_nchw.apply(params, x, state=state, training=True)
    out_l, _ = m_nhwc.apply(params, x.transpose(0, 2, 3, 1), state=state, training=True)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_l),
                               rtol=2e-4, atol=2e-4)


def test_alexnet_variants_forward():
    """Both AlexNet layouts (reference example/loadmodel/AlexNet.scala)
    produce class log-probs at their canonical input sizes."""
    import jax
    import numpy as np

    from bigdl_tpu.models import alexnet

    for build_fn, size in ((alexnet.build_owt, 224), (alexnet.build, 227)):
        m = build_fn(class_num=10, has_dropout=False)
        params, state = m.init(jax.random.key(0))
        x = np.random.RandomState(0).rand(2, 3, size, size).astype(np.float32)
        out, _ = m.apply(params, x, state=state, training=False)
        assert np.asarray(out).shape == (2, 10)
        np.testing.assert_allclose(np.exp(np.asarray(out)).sum(-1), 1.0,
                                   rtol=1e-4)


def test_resnet_mixed_layout_matches_nchw():
    """data_format="MIXED" (NCHW stem -> NHWC deep layers, PERF_NOTES
    round 3) is numerically identical to the NCHW model."""
    import jax
    import numpy as np

    from bigdl_tpu.models import resnet

    m1 = resnet.build_imagenet(18, 10)
    m2 = resnet.build_imagenet(18, 10, data_format="MIXED",
                               kernel_format="HWIO")
    p1, s1 = m1.init(jax.random.key(0))
    p2, s2 = m2.init(jax.random.key(0))
    x = np.random.RandomState(0).rand(2, 3, 64, 64).astype(np.float32)
    o1, _ = m1.apply(p1, x, state=s1, training=True)
    o2, _ = m2.apply(p2, x, state=s2, training=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-4, atol=1e-4)


def test_space_to_depth_stem_matches_conv1():
    """Conv1SpaceToDepth (MLPerf fold; build_imagenet(stem_s2d=True)) is
    mathematically identical to the 7x7/s2 stem convolution."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.resnet import Conv1SpaceToDepth

    conv = nn.SpatialConvolution(3, 64, 7, 7, 2, 2, 3, 3, with_bias=False)
    s2d = Conv1SpaceToDepth(64)
    p_ref, _ = conv.init(jax.random.key(1))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 3, 64, 64), jnp.float32)
    y_ref, _ = conv.apply(p_ref, x)
    y_s2d, _ = s2d.apply({"weight": p_ref["weight"]}, x)
    np.testing.assert_allclose(np.asarray(y_s2d), np.asarray(y_ref),
                               atol=1e-4)
    # and it trains: gradient flows to the canonical (64,3,7,7) weight
    g = jax.grad(lambda p: float(0) + jnp.sum(s2d.apply(p, x)[0] ** 2))(
        {"weight": p_ref["weight"]})
    assert g["weight"].shape == (64, 3, 7, 7)
    assert float(jnp.abs(g["weight"]).sum()) > 0
