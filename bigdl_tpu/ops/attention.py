"""Scaled-dot-product attention dispatch + attention-bias helpers.

Reference semantics: ``DL/nn/Attention.scala`` computes
softmax(QK^T / sqrt(d) + bias) V with an additive bias carrying both the
padding mask (``TransformerOperation.getPaddingBias``) and, for decoders,
the causal mask (``TransformerOperation.attentionBiasLowerTriangle``).
Here the same contract is a single functional op that routes to the Pallas
flash kernel on TPU (fused, no S×S materialisation) and to a plain XLA
einsum path elsewhere.
"""

from __future__ import annotations

import collections
import logging
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import flash_attention as _fa

_NEG = -1e9
_log = logging.getLogger(__name__)

# Auto-selection on a TPU that could NOT take the Pallas kernel, counted
# per reason at trace time (``chip_smoke.py`` prints it): the XLA path is
# correct but is not the kernel, and nobody should have to guess which ran.
kernel_fallbacks: collections.Counter = collections.Counter()


def _fallback(reason: str) -> bool:
    if not kernel_fallbacks[reason]:
        _log.warning("attention: XLA path on TPU (%s)", reason)
    kernel_fallbacks[reason] += 1
    return False


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _kernel_platform() -> bool:
    """Auto-selection's first question: a TPU, and no active mesh. A
    Mosaic call has no GSPMD partitioning rule ("Mosaic kernels cannot be
    automatically partitioned"), so under ``parallel.mesh.use_mesh`` XLA
    partitions the einsum path and inserts the collectives instead."""
    if not _on_tpu():
        return False
    from bigdl_tpu.parallel.mesh import current_mesh  # import cycle via tp

    return current_mesh() is None or _fallback(
        "active mesh: a Mosaic call cannot be partitioned")


def attention_bias_from_padding(padding_mask: jax.Array) -> jax.Array:
    """(B, S) 1-where-padding -> additive bias (B, 1, 1, S).

    Reference: ``TransformerOperation.getPaddingBias`` (pad positions get
    a large negative logit)."""
    return (padding_mask.astype(jnp.float32) * _NEG)[:, None, None, :]


def causal_bias(length: int) -> jax.Array:
    """(1, 1, S, S) additive lower-triangle bias.

    Reference: ``TransformerOperation.attentionBiasLowerTriangle``."""
    mask = jnp.tril(jnp.ones((length, length), jnp.float32))
    return ((1.0 - mask) * _NEG)[None, None, :, :]


def _flash_ok(q, k) -> bool:
    if q.shape[-1] > 256:
        return _fallback(f"flash: head_dim {q.shape[-1]} > 256")
    sq, sk = q.shape[-2], k.shape[-2]
    bq = min(128, sq)
    bk = min(128, sk)
    if sq % bq or sk % bk:
        return _fallback(f"flash: seq lens ({sq}, {sk}) not in 128-blocks")
    return True


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_map: jax.Array,
    positions: jax.Array,
    *,
    sm_scale: Optional[float] = None,
    use_kernel: Optional[bool] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode-step attention over a paged (block-table) KV cache.

    ``q``: (S, H, D) one query per slot; ``k_pages``/``v_pages``:
    (num_pages, H, page_size, D) shared pools; ``page_map``: (S, ppn)
    int32; ``positions``: (S,) — key column ``j`` valid iff
    ``j <= positions[s]``. ``use_kernel=None`` auto-selects the Pallas
    scalar-prefetch kernel on TPU and the pure-jnp gather reference
    elsewhere; the reference path is bit-identical to dense slot-table
    attention on the same backend (test-enforced), which is what lets
    the serving tier swap lanes for pages without changing one token.
    Int8 pools pass their per-token fp32 scale pools
    (``k_scales``/``v_scales``, shape (num_pages, page_size)): both
    paths dequantize on gather. ``interpret`` is for tests only: the
    auto path never interprets, and ``use_kernel=True`` off a TPU lowers
    the real kernel (and fails where there is no TPU compiler).
    """
    if use_kernel is None:
        use_kernel = _kernel_platform() and (
            q.shape[-1] <= 256
            or _fallback(f"paged: head_dim {q.shape[-1]} > 256"))
    if use_kernel:
        return _fa.paged_flash_attention(
            q, k_pages, v_pages, page_map, positions, sm_scale,
            interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    return _fa.paged_attention_reference(
        q, k_pages, v_pages, page_map, positions, sm_scale,
        k_scales=k_scales, v_scales=v_scales)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    use_flash: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention over (B, H, S, D) tensors.

    ``use_flash=None`` auto-selects: Pallas kernel on TPU when shapes allow
    and there is no attention dropout (dropout inside the probability matrix
    defeats the fused formulation; the reference's attentionDropout is only
    active in training, where the XLA path is used instead).
    ``interpret`` is for tests only; the auto path never interprets.
    """
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if use_flash is None:
        use_flash = (dropout_rate == 0.0 and _kernel_platform()
                     and _flash_ok(q, k))

    if use_flash and dropout_rate == 0.0:
        return _fa.flash_attention(
            q, k, v, bias, scale, causal, interpret=interpret)

    if dropout_rate > 0.0 and dropout_rng is None:
        raise ValueError("attention dropout needs dropout_rng")
    return _fa._xla_attention(
        q, k, v, bias, scale, causal,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng,
    )
