"""Standalone perf harness over the model zoo.

Reference: ``DL/models/utils/DistriOptimizerPerf.scala:82`` /
``LocalOptimizerPerf.scala`` (dummy-data training throughput for a
selectable model) and ``DL/nn/mkldnn/Perf.scala:56`` (fwd/bwd latency,
incl. int8 inference).

Usage::

    python -m bigdl_tpu.models.perf --model resnet50 -b 32 --mode train
    python -m bigdl_tpu.models.perf --model vgg16 --mode fwd --int8

Timing is as in ``bench.py``: warm up (compile), then the host clock around
a scan of ``--iters`` steps that ends in ``block_until_ready``; the median
of the repeats is reported.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from bigdl_tpu.core.rng import np_rng
import jax
import jax.numpy as jnp


def build_model(name: str, class_num: int):
    from bigdl_tpu.models import inception, lenet, resnet, vgg

    shapes = {"lenet": (1, 28, 28)}
    if name == "lenet":
        return lenet.build(class_num if class_num != 1000 else 10), (1, 28, 28)
    if name == "resnet50":
        return resnet.build_imagenet(50, class_num), (3, 224, 224)
    if name == "resnet18":
        return resnet.build_imagenet(18, class_num), (3, 224, 224)
    if name == "inception-v1":
        return inception.build(class_num), (3, 224, 224)
    if name == "vgg16":
        return vgg.build_vgg16(class_num=class_num), (3, 224, 224)
    if name == "vgg19":
        return vgg.build_vgg19(class_num=class_num), (3, 224, 224)
    if name == "alexnet":
        from bigdl_tpu.models import alexnet

        return alexnet.build_owt(class_num), (3, 224, 224)
    raise ValueError(f"unknown model {name}")


def timed_scan(body, carry, n, reps=3):
    """Seconds per iteration of ``body``, median over ``reps`` scans."""
    @jax.jit
    def multi(c):
        _, r = jax.lax.scan(lambda c, _: body(c), c, None, length=n)
        return r

    jax.block_until_ready(multi(carry))  # warm-up: compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(multi(carry))
        times.append((time.perf_counter() - t0) / n)
    return float(np.median(times))


def main(argv=None):
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD

    ap = argparse.ArgumentParser("perf")
    ap.add_argument("--model", default="resnet50",
                    choices=["lenet", "resnet18", "resnet50", "inception-v1",
                             "vgg16", "vgg19", "alexnet"])
    ap.add_argument("-b", "--batchSize", type=int, default=32)
    ap.add_argument("--mode", choices=["train", "fwd"], default="train")
    ap.add_argument("--int8", action="store_true",
                    help="quantize for the fwd mode (Perf.scala int8 path)")
    ap.add_argument("--classNum", type=int, default=1000)
    ap.add_argument("--iters", type=int, default=12,
                    help="steps per timed scan")
    args = ap.parse_args(argv)

    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32

    model, shape = build_model(args.model, args.classNum)
    params, mstate = model.init(jax.random.key(0))
    rng = np_rng(0)
    x = jnp.asarray(rng.random((args.batchSize, *shape)), dtype)
    y = jnp.asarray(rng.integers(0, args.classNum, (args.batchSize,)), jnp.int32)

    if args.mode == "fwd":
        if args.int8:
            from bigdl_tpu.nn.quantized import quantize

            model, params = quantize(model, params)
            x = x.astype(jnp.float32)

        def body(c):
            p, xx = c
            out, _ = model.apply(p, xx, state=mstate, training=False)
            s = out.astype(jnp.float32).mean()
            return (p, xx + (s * 1e-30).astype(xx.dtype)), s
        dt = timed_scan(body, (params, x), args.iters)
    else:
        crit = CrossEntropyCriterion()
        method = SGD(learning_rate=0.01, momentum=0.9)
        ostate = method.init_state(params)

        def body(c):
            p, ms, os_ = c

            def loss_fn(pp):
                # fixed dropout rng: fine for throughput (mask compute cost
                # is identical every step), required by Dropout-bearing
                # models (inception/vgg/alexnet) in training mode
                out, nms = model.apply(pp, x, state=ms, training=True,
                                       rng=jax.random.key(1))
                return crit.forward(out.astype(jnp.float32), y), nms

            (loss, nms), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
            np_, nos = method.update(g, p, os_, jnp.int32(1))
            return (np_, nms, nos), loss
        dt = timed_scan(body, (params, mstate, ostate), args.iters)

    print(json.dumps({
        "model": args.model, "mode": args.mode, "int8": args.int8,
        "batch": args.batchSize,
        "ms_per_iter": round(dt * 1e3, 2),
        "records_per_sec": round(args.batchSize / dt, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
