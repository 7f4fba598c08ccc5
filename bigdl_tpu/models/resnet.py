"""ResNet for CIFAR-10 and ImageNet.

Reference: ``DL/models/resnet/ResNet.scala`` (CIFAR + ImageNet variants,
shortcut types A/B/C, basic vs bottleneck blocks, optimnet-style init),
``DL/models/resnet/Train.scala`` / ``TrainImageNet.scala`` (recipes:
warmup + multi-step / poly decay, momentum SGD, label smoothing option).

TPU-native notes: residual add + BN + ReLU fuse in XLA; blocks are built
with ``ConcatTable``/``CAddTable`` exactly like the reference's Sequential
composition, so the params tree mirrors the reference's module tree. The
ImageNet stem uses the 7x7/2 conv + 3x3/2 maxpool; bottleneck stride
placement follows the reference's "v1.5" choice (stride on the 3x3,
``ResNet.scala`` ``useConv`` path) which is also the better MXU mapping.
"""

from __future__ import annotations

from typing import Optional

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.init import MsraFiller, Zeros


def _conv(cin, cout, k, stride=1, pad=0, data_format="NCHW",
          kernel_format="OIHW"):
    return nn.SpatialConvolution(
        cin, cout, k, k, stride, stride, pad, pad,
        with_bias=False, weight_init=MsraFiller(), data_format=data_format,
        kernel_format=kernel_format,
    )


class Conv1SpaceToDepth(nn.Module):
    """The ImageNet stem conv (7x7/2, 3->64) computed via the MLPerf
    space-to-depth trick: fold 2x2 pixel blocks into channels so the
    MXU's contraction dim sees 12 input channels instead of 3, and run
    the mathematically IDENTICAL 4x4/1 convolution on the folded layout.

    Derivation: with original index ``2*oh + kh - 3`` (stride 2, pad 3)
    and ``kh = 2*kh' + p - 1`` (p the 2-pixel phase), the sum becomes a
    stride-1 conv over folded index ``oh + kh' - 2`` — kernel 4, padding
    (2, 1). Weights stay stored in the canonical (64, 3, 7, 7) layout
    (checkpoint/serializer compatible); the fold is a 9.4K-element
    pad+reshape recomputed per step (negligible). Zero-padded taps make
    the result exactly the original convolution up to fp summation
    order. NCHW only (the bench layout).
    """

    def __init__(self, cout: int = 64):
        super().__init__()
        self.cout = cout

    def build_params(self, rng):
        from bigdl_tpu.core.rng import fold_in_str
        w = MsraFiller()(fold_in_str(rng, "w"), (self.cout, 3, 7, 7),
                         3 * 49, self.cout * 49)
        return {"weight": w}

    def forward(self, ctx, x):
        import jax.numpy as jnp

        w = ctx.param("weight").astype(x.dtype)  # (O, 3, 7, 7)
        O = w.shape[0]
        B, C, H, W = x.shape
        xf = (x.reshape(B, C, H // 2, 2, W // 2, 2)
              .transpose(0, 1, 3, 5, 2, 4)
              .reshape(B, C * 4, H // 2, W // 2))  # channel order (c, p, q)
        wp = jnp.pad(w, ((0, 0), (0, 0), (1, 0), (1, 0)))  # tap -1 -> zero
        wf = (wp.reshape(O, C, 4, 2, 4, 2)
              .transpose(0, 1, 3, 5, 2, 4)
              .reshape(O, C * 4, 4, 4))
        import jax.lax as lax
        return lax.conv_general_dilated(
            xf, wf, (1, 1), [(2, 1), (2, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn(n, zero_init=False, data_format="NCHW"):
    # reference zero-inits the last BN gamma of each block when
    # optnet/warm-up recipes are on (ResNet.scala getShortcut/iChannels)
    return nn.SpatialBatchNormalization(
        n, weight_init=Zeros() if zero_init else None, data_format=data_format)


def shortcut(cin: int, cout: int, stride: int, shortcut_type: str = "B",
             data_format: str = "NCHW", kernel_format: str = "OIHW") -> nn.Module:
    """Shortcut types (reference ``ResNet.scala`` ``shortcut``):
    A = identity/zero-pad (CIFAR), B = 1x1 conv when shape changes,
    C = always 1x1 conv."""
    use_conv = shortcut_type == "C" or (shortcut_type == "B" and (cin != cout or stride != 1))
    if use_conv:
        return nn.Sequential(_conv(cin, cout, 1, stride, data_format=data_format,
                                   kernel_format=kernel_format),
                             _bn(cout, data_format=data_format))
    if cin != cout:
        # type A: stride then zero-pad channels (Pad on channel dim)
        ch_dim = 1 if data_format == "NCHW" else 3
        return nn.Sequential(
            nn.SpatialAveragePooling(1, 1, stride, stride,
                                     data_format=data_format),
            nn.Padding(ch_dim, cout - cin),
        )
    return nn.Identity()


def basic_block(cin: int, cout: int, stride: int, shortcut_type: str = "B",
                zero_init_residual: bool = False,
                data_format: str = "NCHW", kernel_format: str = "OIHW") -> nn.Module:
    df, kf = data_format, kernel_format
    block = nn.Sequential(
        _conv(cin, cout, 3, stride, 1, data_format=df, kernel_format=kf),
        _bn(cout, data_format=df),
        nn.ReLU(),
        _conv(cout, cout, 3, 1, 1, data_format=df, kernel_format=kf),
        _bn(cout, zero_init=zero_init_residual, data_format=df),
    )
    return nn.Sequential(
        nn.ConcatTable(block, shortcut(cin, cout, stride, shortcut_type, df, kf)),
        nn.CAddTable(),
        nn.ReLU(),
    )


def bottleneck(cin: int, planes: int, stride: int, shortcut_type: str = "B",
               zero_init_residual: bool = False,
               data_format: str = "NCHW", kernel_format: str = "OIHW") -> nn.Module:
    df, kf = data_format, kernel_format
    cout = planes * 4
    block = nn.Sequential(
        _conv(cin, planes, 1, data_format=df, kernel_format=kf),
        _bn(planes, data_format=df),
        nn.ReLU(),
        _conv(planes, planes, 3, stride, 1, data_format=df, kernel_format=kf),
        _bn(planes, data_format=df),
        nn.ReLU(),
        _conv(planes, cout, 1, data_format=df, kernel_format=kf),
        _bn(cout, zero_init=zero_init_residual, data_format=df),
    )
    return nn.Sequential(
        nn.ConcatTable(block, shortcut(cin, cout, stride, shortcut_type, df, kf)),
        nn.CAddTable(),
        nn.ReLU(),
    )


IMAGENET_CFG = {
    18: ("basic", [2, 2, 2, 2]),
    34: ("basic", [3, 4, 6, 3]),
    50: ("bottleneck", [3, 4, 6, 3]),
    101: ("bottleneck", [3, 4, 23, 3]),
    152: ("bottleneck", [3, 8, 36, 3]),
}


def build_imagenet(depth: int = 50, class_num: int = 1000, shortcut_type: str = "B",
                   zero_init_residual: bool = True,
                   data_format: str = "NCHW",
                   kernel_format: str = "OIHW",
                   stem_s2d: bool = False) -> nn.Sequential:
    """ImageNet ResNet (reference ``ResNet.apply`` dataset=ImageNet branch).

    ``data_format="NHWC"`` builds the channels-last variant (input
    (B, H, W, C)). ``data_format="MIXED"`` is the measured-fastest TPU
    layout (PERF_NOTES.md round 3): NCHW for the stem + 64-channel
    layer1 (narrow channels underfill the 128-lane dimension in NHWC,
    making those convs ~2x slower), one transpose, then NHWC for
    layers 2-4 where convs are up to 1.8x faster AND the BN statistic
    reductions become lane-minor accumulations. Input stays NCHW.
    """
    if depth not in IMAGENET_CFG:
        raise ValueError(f"unsupported imagenet resnet depth {depth}")
    kind, counts = IMAGENET_CFG[depth]
    block = basic_block if kind == "basic" else bottleneck
    expansion = 1 if kind == "basic" else 4
    mixed = data_format == "MIXED"
    df, kf = ("NCHW", kernel_format) if mixed else (data_format, kernel_format)

    if stem_s2d and df != "NCHW":
        raise ValueError("stem_s2d supports the NCHW layout only")
    stem_conv = (Conv1SpaceToDepth(64) if stem_s2d
                 else _conv(3, 64, 7, 2, 3, data_format=df,
                            kernel_format=kf))
    model = nn.Sequential(
        stem_conv.set_name("conv1"),
        _bn(64, data_format=df),
        nn.ReLU(),
        nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1, data_format=df),
    )
    cin = 64
    for stage, (planes, n_blocks) in enumerate(zip([64, 128, 256, 512], counts)):
        if mixed and stage == 1:
            # NCHW -> NHWC between layer1 and layer2
            model.add(nn.Transpose((1, 2), (2, 3)), name="to_nhwc")
            df = "NHWC"
        for i in range(n_blocks):
            stride = 2 if (stage > 0 and i == 0) else 1
            model.add(
                block(cin, planes, stride, shortcut_type, zero_init_residual,
                      df, kf),
                name=f"layer{stage + 1}_{i}",
            )
            cin = planes * expansion
    model.add(nn.GlobalAveragePooling2D(data_format=df))
    model.add(nn.Linear(cin, class_num, weight_init=MsraFiller()).set_name("fc"))
    return model


def build_cifar(depth: int = 20, class_num: int = 10, shortcut_type: str = "A") -> nn.Sequential:
    """CIFAR-10 ResNet: depth = 6n+2 basic blocks (reference ``ResNet.apply``
    CIFAR-10 branch)."""
    if (depth - 2) % 6 != 0:
        raise ValueError("cifar resnet depth must be 6n+2")
    n = (depth - 2) // 6
    model = nn.Sequential(
        _conv(3, 16, 3, 1, 1),
        _bn(16),
        nn.ReLU(),
    )
    cin = 16
    for stage, planes in enumerate([16, 32, 64]):
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            model.add(
                basic_block(cin, planes, stride, shortcut_type),
                name=f"stage{stage + 1}_{i}",
            )
            cin = planes
    model.add(nn.GlobalAveragePooling2D())
    model.add(nn.Linear(cin, class_num, weight_init=MsraFiller()).set_name("fc"))
    return model


def build(depth: int = 50, class_num: int = 1000, dataset: str = "imagenet",
          shortcut_type: Optional[str] = None) -> nn.Sequential:
    if dataset.lower() in ("imagenet", "i"):
        return build_imagenet(depth, class_num, shortcut_type or "B")
    return build_cifar(depth, class_num, shortcut_type or "A")


def main(argv=None):
    """Train CLI (reference: ``resnet/Train.scala`` CIFAR recipe /
    ``TrainImageNet.scala``)."""
    import numpy as np

    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.datasets import _synthetic_images, load_cifar10
    from bigdl_tpu.models.cli import fit, make_parser
    from bigdl_tpu.optim import SGD, optimizer
    from bigdl_tpu.optim.schedules import MultiStep

    parser = make_parser("resnet-train", batch_size=128, max_epoch=10,
                         learning_rate=0.1,
                         folder_help="cifar-10 dir (synthetic data if absent)")
    parser.add_argument("--depth", type=int, default=20)
    parser.add_argument("--dataset", default="cifar10", choices=["cifar10", "imagenet"])
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--weightDecay", type=float, default=1e-4)
    parser.add_argument("--dataFormat", default="NCHW",
                        choices=["NCHW", "NHWC"],
                        help="imagenet variant only; NHWC = channels-last")
    args = parser.parse_args(argv)

    if args.dataset == "imagenet":
        model = build_imagenet(args.depth if args.depth in IMAGENET_CFG else 50,
                               1000, data_format=args.dataFormat)
        # at least one full batch: the drop-last training stream refuses a
        # batch larger than the dataset
        x, y = _synthetic_images(max(64, args.batchSize), (3, 224, 224),
                                 1000, seed=1)
        if args.dataFormat == "NHWC":
            x = np.ascontiguousarray(np.transpose(x, (0, 2, 3, 1)))
    else:
        model = build_cifar(args.depth, 10)
        x, y = load_cifar10(args.folder, train=True)
        mean = np.asarray([125.3, 123.0, 113.9], np.float32).reshape(3, 1, 1)
        std = np.asarray([63.0, 62.1, 66.7], np.float32).reshape(3, 1, 1)
        x = (x - mean) / std
    ds = DataSet.tensors(x.astype("float32"), y)

    # reference CIFAR recipe: momentum SGD with multi-step decay
    opt = optimizer(model, ds, nn.CrossEntropyCriterion(), batch_size=args.batchSize)
    opt.set_optim_method(SGD(learning_rate=args.learningRate,
                             momentum=args.momentum,
                             weight_decay=args.weightDecay,
                             schedule=MultiStep([32000, 48000], 0.1)))
    return fit(opt, args)


if __name__ == "__main__":
    main()
