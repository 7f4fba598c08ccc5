"""Flash attention as a Pallas TPU kernel.

The reference computes attention as unfused matmul/softmax/matmul modules
(``DL/nn/Attention.scala:35`` builds a Graph of MM + SoftMax + CMulTable);
at sequence length S that materialises the (S, S) score matrix in memory.
On TPU the memory-bound softmax traffic dominates HBM bandwidth, so the
TPU-native design is the online-softmax (flash) formulation: stream K/V
blocks through VMEM, keep running max/sum statistics, never materialise the
score matrix. Forward is a Pallas kernel; backward recomputes attention
(rematerialisation — FLOPs are cheap on the MXU, HBM is not) with a plain
XLA implementation under ``jax.custom_vjp``.

Shapes follow (batch, heads, seq, head_dim) throughout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_MIN_LANE = 128


def _xla_attention(q, k, v, bias, sm_scale, causal,
                   dropout_rate=0.0, dropout_rng=None):
    """Reference XLA path (also the recompute used by the flash backward).

    Causal convention (shared with the kernel): END-aligned — query row i
    attends key cols j with ``j <= i + (klen - qlen)``, i.e. queries are the
    LAST ``qlen`` positions of the key sequence (the decode-time case; for
    qlen == klen this is the ordinary lower triangle).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        qlen, klen = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool), k=klen - qlen)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, causal, block_q, block_k, n_k, causal_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    should_run = True
    if causal:
        # end-aligned: row i may see cols <= i + causal_offset
        should_run = qi * block_q + block_q - 1 + causal_offset >= ki * block_k

    @pl.when(should_run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0].astype(jnp.float32)          # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                               # (block_q, block_k)
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows + causal_offset >= cols, s, _NEG_INF)

        m_prev = m_ref[:, :1]                      # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (block_q, 1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)                    # (block_q, block_k)
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows -> 0 output
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lens ({sq},{sk}) not divisible by blocks ({block_q},{block_k})")
    n_q, n_k = sq // block_q, sk // block_k

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
    ]
    args = [qr, kr, vr]
    if bias is not None:
        bias = jnp.broadcast_to(bias, (b, h, sq, sk)).reshape(b * h, sq, sk)
        in_specs.append(
            pl.BlockSpec((1, block_q, block_k), lambda bh, qi, ki: (bh, qi, ki))
        )
        args.append(bias)
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, n_k=n_k, causal_offset=sk - sq,
        )
    else:
        kernel = functools.partial(
            lambda qf, kf, vf, o, acc, m, l, **kw: _fwd_kernel(
                qf, kf, vf, None, o, acc, m, l, **kw),
            sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, n_k=n_k, causal_offset=sk - sq,
        )

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _MIN_LANE), jnp.float32),
            pltpu.VMEM((block_q, _MIN_LANE), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, sq, d)


# --------------------------------------------------------------------- #
# Paged (block-table) attention — the decode-side companion of the flash
# kernel. The serving tier's KV cache is a shared pool of fixed-size
# pages (vLLM's PagedAttention, SOSP '23 — PAPERS.md): per layer,
# K/V are (num_pages, heads, page_size, head_dim) and each slot owns a
# row of int32 page ids. Attention must therefore GATHER a slot's keys
# through its page map instead of slicing a dense lane. Two paths:
#
# - `paged_attention_reference`: pure-jnp `jnp.take` gather that
#   reconstitutes the logical (S, H, L, D) lanes and reuses the exact
#   dense attention ops — bit-identical to the dense slot-table path on
#   the same backend (gathering is data movement; the math that follows
#   is the same op sequence). This is the CPU/tier-1 path.
# - `paged_flash_attention`: a Pallas TPU kernel streaming pages through
#   VMEM with the page map scalar-prefetched, so the physical page id
#   feeds the K/V BlockSpec index_map directly (no materialised gather)
#   and pages wholly past a slot's position are skipped.


def gather_kv_lanes(pages: jax.Array, page_map: jax.Array) -> jax.Array:
    """(num_pages, H, page_size, D) pool + (..., ppn) int32 page map ->
    logical lanes (..., H, ppn * page_size, D). The gather is exact data
    movement: lane bytes equal the pooled page bytes, which is what the
    paged == dense bit-identity tests lean on."""
    h, ps, d = pages.shape[1:]
    lanes = jnp.take(pages, page_map, axis=0)  # (..., ppn, H, ps, D)
    perm = tuple(range(page_map.ndim - 1)) + (
        page_map.ndim, page_map.ndim - 1, page_map.ndim + 1,
        page_map.ndim + 2)
    lanes = lanes.transpose(perm)              # (..., H, ppn, ps, D)
    return lanes.reshape(page_map.shape[:-1] + (h, -1, d))


def gather_scale_lanes(scales: jax.Array, page_map: jax.Array) -> jax.Array:
    """Companion gather for int8 KV: (num_pages, page_size) per-token
    scale pool + (..., ppn) page map -> logical scale lanes
    (..., ppn * page_size), row-aligned with :func:`gather_kv_lanes`
    output so ``nn.int8.dequantize_lanes`` can broadcast them."""
    ps = scales.shape[1]
    lanes = jnp.take(scales, page_map, axis=0)   # (..., ppn, ps)
    return lanes.reshape(page_map.shape[:-1] + (page_map.shape[-1] * ps,))


def paged_attention_reference(q, k_pages, v_pages, page_map, positions,
                              sm_scale: Optional[float] = None,
                              k_scales=None, v_scales=None):
    """Decode-shaped paged attention, pure jnp (the XLA/tier-1 path).

    ``q``: (S, H, D) one query per slot; ``k_pages``/``v_pages``:
    (num_pages, H, page_size, D); ``page_map``: (S, ppn) int32 physical
    page per logical page; ``positions``: (S,) int32 — key column ``j``
    is valid for slot ``s`` iff ``j <= positions[s]`` (the row the
    current token was just written to). Returns (S, H, D).

    ``k_scales``/``v_scales`` (both or neither): int8 pools' per-token
    fp32 scale pools of shape (num_pages, page_size) — lanes are
    dequantized after the gather (``value = int8 * scale``); masked
    columns still contribute exact zeros whatever a recycled page or a
    stale scale holds, so the bit-identity argument of the float path
    carries over unchanged."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    lk = gather_kv_lanes(k_pages, page_map)    # (S, H, L, D)
    lv = gather_kv_lanes(v_pages, page_map)
    if k_scales is not None:
        from bigdl_tpu.nn.int8 import dequantize_lanes

        lk = dequantize_lanes(lk, gather_scale_lanes(k_scales, page_map))
        lv = dequantize_lanes(lv, gather_scale_lanes(v_scales, page_map))
    length = lk.shape[2]
    rows = positions[:, None]                  # one query row per slot
    cols = jnp.arange(length)
    validity = jnp.where(cols[None, None, :] <= rows[:, :, None],
                         0.0, -1e9)[:, None, :, :]
    out = _xla_attention(q[:, :, None, :], lk, lv, validity, scale, False)
    return out[:, :, 0, :]


def _paged_kernel(pm_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, sm_scale, page_size,
                  n_pages):
    s = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[s]

    @pl.when(pi * page_size <= pos)            # page holds >= 1 valid col
    def _compute():
        q = q_ref[...].reshape(1, -1).astype(jnp.float32)    # (1, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (ps, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            # int8 pages: per-token scales ride in their own (1, 1, ps)
            # block DMA'd through the same scalar-prefetched page id
            k = k * ks_ref[0, 0][:, None]
            v = v * vs_ref[0, 0][:, None]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                         # (1, ps)
        cols = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(cols <= pos, scores, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next)
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    @pl.when(pi == n_pages - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).reshape(o_ref.shape).astype(
            o_ref.dtype)


def paged_flash_attention(q, k_pages, v_pages, page_map, positions,
                          sm_scale: Optional[float] = None,
                          interpret: bool = False,
                          k_scales=None, v_scales=None):
    """Pallas paged gather-attention: online-softmax over a slot's mapped
    pages, page ids scalar-prefetched so each K/V block DMA reads the
    physical page directly. Same signature/semantics as
    :func:`paged_attention_reference` (q: (S, H, D) -> (S, H, D));
    int8 pools pass their per-token scale pools, each streamed as a
    (1, 1, page_size) block through the same prefetched page id and
    applied before the score matmul.

    The TPU lowering wants the last two dims of every block to be whole
    array dims (or (8k, 128k) tiles), so ``q``/the output get a unit
    second-minor axis ((S, H, 1, D), blocks (1, 1, 1, D)) and the scale
    pools likewise ((num_pages, 1, page_size)); both reshapes are free."""
    n_slots, heads, d = q.shape
    n_phys, _, page_size, _ = k_pages.shape
    ppn = page_map.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    int8_kv = k_scales is not None

    in_specs = [
        pl.BlockSpec((1, 1, 1, d), lambda s, h, p, pm, pos: (s, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda s, h, p, pm, pos: (pm[s, p], h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda s, h, p, pm, pos: (pm[s, p], h, 0, 0)),
    ]
    args = [q[:, :, None, :], k_pages, v_pages]
    if int8_kv:
        in_specs += [
            pl.BlockSpec((1, 1, page_size),
                         lambda s, h, p, pm, pos: (pm[s, p], 0, 0)),
            pl.BlockSpec((1, 1, page_size),
                         lambda s, h, p, pm, pos: (pm[s, p], 0, 0)),
        ]
        args += [k_scales.astype(jnp.float32)[:, None, :],
                 v_scales.astype(jnp.float32)[:, None, :]]
        kernel = functools.partial(
            _paged_kernel, sm_scale=scale, page_size=page_size, n_pages=ppn)
    else:
        kernel = functools.partial(
            lambda pm, pos, qf, kf, vf, o, acc, m, l, **kw: _paged_kernel(
                pm, pos, qf, kf, vf, None, None, o, acc, m, l, **kw),
            sm_scale=scale, page_size=page_size, n_pages=ppn)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_slots, heads, ppn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda s, h, p, pm, pos: (s, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, _MIN_LANE), jnp.float32),
            pltpu.VMEM((1, _MIN_LANE), jnp.float32),
        ],
    )
    out_dtype = jnp.float32 if int8_kv else q.dtype
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, 1, d), out_dtype),
        interpret=interpret,
    )(page_map.astype(jnp.int32), positions.astype(jnp.int32), *args)
    return out[:, :, 0, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, k, v, bias=None, sm_scale: Optional[float] = None,
                    causal: bool = False, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Fused online-softmax attention. q/k/v: (B, H, S, D)."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    return _flash_fwd(q, k, v, bias, scale, causal, block_q, block_k, interpret)


def _vjp_fwd(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret):
    out = flash_attention(q, k, v, bias, sm_scale, causal, block_q, block_k, interpret)
    return out, (q, k, v, bias)


def _vjp_bwd(sm_scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, bias = res
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5

    def ref(q, k, v, bias):
        if bias is None:
            return _xla_attention(q, k, v, None, scale, causal)
        return _xla_attention(q, k, v, bias, scale, causal)

    if bias is None:
        _, vjp = jax.vjp(lambda q, k, v: ref(q, k, v, None), q, k, v)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, None
    _, vjp = jax.vjp(ref, q, k, v, bias)
    return vjp(g)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
