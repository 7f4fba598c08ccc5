"""Pallas residual add: a bandwidth-tuned two-operand elementwise sum.

Round-5 measurement (`perf/micro_resadd2.py`, `perf/artifacts/r5_resadd2.txt`):
XLA's STANDALONE materialized add of a (128,256,56,56) bf16 pair runs at
~269 GB/s on this v5e, while a Pallas block add with 64-row blocks over
a (rows, cols)-flattened view reaches ~464 GB/s — 1.7x. The ResNet-50
step carries 16 such standalone residual adds (~4.5 ms of the 44 ms
step, per the r5 profile), whose producers (conv outputs on both sides)
and consumers keep XLA from fusing them away. This op exists to claw
back part of that bucket; it is opt-in via ``BIGDL_RESIDUAL_ADD=pallas``
(read per-trace, like the other perf knobs) because it also BLOCKS any
fusion the surrounding graph might otherwise find.

Semantics: exact two-operand add of same-shape floating arrays;
``custom_vjp`` backward passes the cotangent to both operands (identical
to ``jnp.add``'s transpose for equal shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _flat2d(shape):
    """(rows, cols) view: split before the last two dims so the minor
    axis is large (NCHW (B,C,H,W) -> (B*C, H*W); (B,T,F) -> (B, T*F))."""
    if len(shape) == 2:
        return shape
    return int(np.prod(shape[:-2])), int(shape[-2] * shape[-1])


def _block_rows(rows, cols, itemsize):
    """Largest row block <= 64 dividing rows, kept under the VMEM budget
    (3 buffers x double buffering; 64 rows x 3136 cols bf16 ~= 0.4 MB)."""
    bs = 64
    while bs > 1 and rows % bs:
        bs //= 2
    while bs > 1 and bs * cols * itemsize * 6 > 12 * 1024 * 1024:
        bs //= 2
    return bs


def _pallas_add2(x2, y2, bs):
    rows, cols = x2.shape

    def kern(a_ref, b_ref, o_ref):
        o_ref[...] = a_ref[...] + b_ref[...]

    return pl.pallas_call(
        kern, grid=(rows // bs,),
        in_specs=[pl.BlockSpec((bs, cols), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((bs, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x2.dtype),
    )(x2, y2)


def _supported(x, y):
    if x.shape != y.shape or x.dtype != y.dtype:
        return False
    if not jnp.issubdtype(x.dtype, jnp.floating) or x.ndim < 2:
        return False
    if jax.default_backend() != "tpu":
        return False
    return x.size >= 1 << 20  # small adds: fusion beats a kernel call


@jax.custom_vjp
def _kernel_add(x, y):
    # only reached for _supported() inputs: same shape, same float dtype
    rows, cols = _flat2d(x.shape)
    bs = _block_rows(rows, cols, x.dtype.itemsize)
    out = _pallas_add2(x.reshape(rows, cols), y.reshape(rows, cols), bs)
    return out.reshape(x.shape)


def _fwd(x, y):
    return _kernel_add(x, y), None


def _bwd(_, g):
    # valid because _kernel_add's operands are guaranteed same-shape,
    # same-dtype (the add's transpose for equal shapes is (g, g))
    return g, g


_kernel_add.defvjp(_fwd, _bwd)


def residual_add(x, y):
    """``x + y`` through the tuned Pallas kernel when supported (TPU,
    same shape/dtype float, >=1M elements), else plain ``jnp.add``.

    Dispatch happens OUTSIDE the custom_vjp: the fallback's broadcasting
    / dtype promotion must use jnp.add's own autodiff (a blanket (g, g)
    backward would return cotangents of the wrong aval for broadcast or
    mixed-dtype operands)."""
    if not _supported(x, y):
        return x + y
    rows, cols = _flat2d(x.shape)
    if _block_rows(rows, cols, x.dtype.itemsize) <= 1:
        return x + y
    return _kernel_add(x, y)
