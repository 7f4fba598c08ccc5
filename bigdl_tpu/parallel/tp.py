"""Tensor (model) parallelism: Megatron-style sharded linears and blocks.

Absent from the reference (SURVEY.md §2.3 — no TP). TPU-native design:
weights carry ``PartitionSpec`` annotations (via ``Module.param_pspecs``)
and activations get ``with_sharding_constraint`` hints; XLA's GSPMD
partitioner inserts the all-gather / reduce-scatter collectives over the
``tp`` ICI axis. No explicit collective calls are needed in the forward —
the column-parallel -> row-parallel pairing means the only communication is
one psum at the row-parallel output, which GSPMD derives automatically.

Pattern (Megatron-LM, adapted to the jax/GSPMD idiom):

- ``ColumnParallelLinear``: weight (out, in) sharded on ``out`` -> output
  activation sharded on the feature dim; no comm.
- ``RowParallelLinear``: weight (out, in) sharded on ``in`` -> consumes a
  feature-sharded activation, produces a replicated (psum-ed) output.
- FFN = column(hidden->4h) . gelu . row(4h->hidden): one collective total.
- Attention: QKV projections column-parallel (heads shard over tp), output
  projection row-parallel.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.sharding import Mesh

from bigdl_tpu.nn.init import Xavier, Zeros
from bigdl_tpu.nn.layers.linear import Linear
from bigdl_tpu.nn.module import Context, Module
from bigdl_tpu.ops.attention import dot_product_attention
from bigdl_tpu.parallel.mesh import (
    UNCONSTRAINED,
    axis_size,
    constrain,
)


class ColumnParallelLinear(Linear):
    """Linear whose (out, in) weight is sharded along ``out`` over ``axis``."""

    def __init__(self, input_size: int, output_size: int, with_bias: bool = True,
                 axis: str = "tp", **kw):
        super().__init__(input_size, output_size, with_bias, **kw)
        self.axis = axis

    def build_param_pspecs(self):
        specs = {"weight": P(self.axis, None)}
        if self.with_bias:
            specs["bias"] = P(self.axis)
        return specs

    def forward(self, ctx: Context, x):
        w = ctx.param("weight")
        y = jnp.matmul(x, w.T.astype(x.dtype))
        if self.with_bias:
            y = y + ctx.param("bias").astype(y.dtype)
        # output features live on the tp axis; batch/seq dims left to GSPMD
        return constrain(y, *([UNCONSTRAINED] * (y.ndim - 1) + [self.axis]))


class RowParallelLinear(Linear):
    """Linear whose (out, in) weight is sharded along ``in`` over ``axis``."""

    def __init__(self, input_size: int, output_size: int, with_bias: bool = True,
                 axis: str = "tp", **kw):
        super().__init__(input_size, output_size, with_bias, **kw)
        self.axis = axis

    def build_param_pspecs(self):
        specs = {"weight": P(None, self.axis)}
        if self.with_bias:
            specs["bias"] = P()
        return specs

    def forward(self, ctx: Context, x):
        # input features arrive sharded on tp (from a column-parallel layer)
        x = constrain(x, *([UNCONSTRAINED] * (x.ndim - 1) + [self.axis]))
        w = ctx.param("weight")
        y = jnp.matmul(x, w.T.astype(x.dtype))  # GSPMD: partial sums -> psum
        # feature dim replicated (forces the psum here); batch/seq dims free
        y = constrain(y, *([UNCONSTRAINED] * (y.ndim - 1) + [None]))
        if self.with_bias:
            y = y + ctx.param("bias").astype(y.dtype)
        return y


class TensorParallelFFN(Module):
    """Transformer FFN with Megatron sharding: one collective per block.

    Mirrors the math of ``FeedForwardNetwork`` (reference:
    ``DL/nn/FeedForwardNetwork.scala``) with tp-sharded weights.
    """

    def __init__(self, hidden_size: int, filter_size: int, axis: str = "tp",
                 activation=None):
        super().__init__()
        self.up = ColumnParallelLinear(hidden_size, filter_size, axis=axis,
                                       weight_init=Xavier(), bias_init=Zeros())
        self.down = RowParallelLinear(filter_size, hidden_size, axis=axis,
                                      weight_init=Xavier(), bias_init=Zeros())
        self.activation = activation

    def forward(self, ctx: Context, x):
        h = self.run_child(ctx, "up", x)
        h = jnp.maximum(h, 0.0) if self.activation is None else self.activation(h)
        return self.run_child(ctx, "down", h)


class TensorParallelAttention(Module):
    """Multi-head attention with heads sharded over the tp axis.

    QKV projections are column-parallel (each tp shard owns
    ``num_heads / tp`` heads end-to-end), output projection row-parallel.
    The head-sharded layout also composes with sequence parallelism: pass
    ``sp_axis`` to additionally shard the sequence dim of activations.
    """

    def __init__(self, hidden_size: int, num_heads: int, axis: str = "tp",
                 sp_axis: Optional[str] = None, attention_dropout: float = 0.0):
        super().__init__()
        if hidden_size % num_heads:
            raise ValueError("num_heads must divide hidden_size")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.axis = axis
        self.sp_axis = sp_axis
        self.attention_dropout = attention_dropout
        for name in ("q", "k", "v"):
            self.add(ColumnParallelLinear(hidden_size, hidden_size, with_bias=False,
                                          axis=axis, weight_init=Xavier()), name)
        self.add(RowParallelLinear(hidden_size, hidden_size, with_bias=False,
                                   axis=axis, weight_init=Xavier()), "out")

    def _heads(self, t):
        b, s, _ = t.shape
        t = t.reshape(b, s, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        return constrain(t, UNCONSTRAINED, self.axis, self.sp_axis or UNCONSTRAINED,
                         UNCONSTRAINED)

    def forward(self, ctx: Context, x, bias=None, causal: bool = False):
        q = self._heads(self.run_child(ctx, "q", x))
        k = self._heads(self.run_child(ctx, "k", x))
        v = self._heads(self.run_child(ctx, "v", x))
        # under an active mesh the dispatcher takes the XLA einsum path
        # (ops/attention.py:_kernel_platform); single-chip keeps flash
        o = dot_product_attention(
            q, k, v, bias=bias, causal=causal,
            dropout_rate=self.attention_dropout if ctx.training else 0.0,
            dropout_rng=ctx.rng() if (ctx.training and self.attention_dropout) else None,
        )
        o = constrain(o, UNCONSTRAINED, self.axis, self.sp_axis or UNCONSTRAINED,
                      UNCONSTRAINED)
        b, h, s, d = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return self.run_child(ctx, "out", o)


# --------------------------------------------------------------------------
# Serving-side tensor parallelism: Megatron pspecs for an ``nn.Transformer``.
#
# The serving tier decodes through ``nn.Transformer``'s incremental API
# (prefill / decode_step and their paged twins), whose layers are plain
# ``Linear``s with no sharding annotations. These helpers map that exact
# parameter tree onto the column->row pattern the classes above implement
# for training, so an ``InferenceService``/``GenerationEngine`` can pjit
# the SAME kernels over tensor-parallel weights: q/k/v projections shard
# like :class:`ColumnParallelLinear` (each tp shard owns
# ``num_heads / tp`` heads end to end, which is also how the KV cache
# shards), attention output + FFN down projection like
# :class:`RowParallelLinear` (the two psums per block), embeddings and
# norms replicated. GSPMD derives every collective from the weight
# shardings alone — the serving model source is untouched.


def kv_cache_pspec(axis: str = "tp") -> P:
    """PartitionSpec for serving KV caches, dense or paged: both are
    ``(slots|pages, heads, rows, head_dim)`` per layer, sharded on the
    HEADS axis — the same per-head ownership the column-parallel q/k/v
    projections produce, so cache reads/writes need no collective."""
    return P(None, axis)


def kv_scale_pspec() -> P:
    """PartitionSpec for the int8 KV pools' per-token scale pools
    (``(num_pages, page_size)`` fp32, ``init_paged_cache`` with
    ``dtype="int8"``): REPLICATED. Scales are shared across heads, so
    they have no heads axis to shard on; the write-side cross-head absmax
    becomes one tiny all-reduce max GSPMD derives — an exact reduction,
    so sharded and single-device int8 quantization agree bitwise."""
    return P()


def transformer_tp_pspecs(model, mesh: Optional[Mesh] = None,
                          axis: str = "tp", params=None):
    """Sparse Megatron PartitionSpec tree for an ``nn.Transformer``'s
    params (LANGUAGE_MODEL mode — the serving decode surface).

    Returns only the sharded leaves (``parallel.mesh.tree_shardings``
    replicates everything else: embedding, norms, output biases). With a
    ``mesh``, validates that the ``axis`` size divides ``num_heads`` —
    attention is parallel over whole heads, never head fractions.

    Pass the actual ``params`` tree to cover an int8 serving tree
    (``nn.quantized.quantize_for_serving``): ``weight_q`` shards exactly
    like ``weight``, and the per-output-channel ``scale`` vector follows
    its channels — sharded over ``axis`` for column-parallel layers
    (each shard rescales the heads it owns), replicated for
    row-parallel ones (their output channels are not sharded; the s32
    partial sums psum exactly, so sharded int8 GEMMs stay bitwise equal
    to single-device).
    """
    from bigdl_tpu.nn.layers.attention import LANGUAGE_MODEL, Transformer

    if not isinstance(model, Transformer):
        raise TypeError(
            f"transformer_tp_pspecs needs an nn.Transformer, got "
            f"{type(model).__name__}; pass explicit param_pspecs for "
            f"other model families")
    if model.transformer_type != LANGUAGE_MODEL:
        raise ValueError("serving tensor parallelism covers language_model "
                         "(decoder-only) transformers")
    if mesh is not None:
        tp = axis_size(mesh, axis)
        if model.num_heads % tp:
            raise ValueError(
                f"mesh axis '{axis}' size {tp} must divide num_heads "
                f"{model.num_heads} (heads shard whole, like "
                f"TensorParallelAttention)")
    quantized = False
    if params is not None:
        first = next((n for n in model.modules
                      if n.startswith("decoder_")), None)
        try:
            leaf = params[first]["self_attention"]["inner"]["q_layer"]
            quantized = "weight_q" in leaf
        except (KeyError, TypeError):
            quantized = False
    if quantized:
        col = {"weight_q": P(axis, None), "scale": P(axis)}
        row = {"weight_q": P(None, axis), "scale": P()}
        ffn_up = {"weight_q": P(axis, None), "scale": P(axis),
                  "bias": P(axis)}
        ffn_down = {"weight_q": P(None, axis), "scale": P(), "bias": P()}
    else:
        col = {"weight": P(axis, None)}   # ColumnParallelLinear pattern
        row = {"weight": P(None, axis)}   # RowParallelLinear pattern
        ffn_up = {"weight": P(axis, None), "bias": P(axis)}
        ffn_down = {"weight": P(None, axis), "bias": P()}
    attn = {"inner": {"q_layer": col, "k_layer": col, "v_layer": col,
                      "output_layer": row}}
    ffn = {"inner": {"filter_layer": ffn_up, "output_layer": ffn_down}}
    layer = {"self_attention": attn, "ffn": ffn}
    return {name: layer for name in model.modules
            if name.startswith("decoder_")}
