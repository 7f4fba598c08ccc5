"""Ulysses-style sequence parallelism: all-to-all head/sequence resharding.

Complement to ring attention (``ring_attention.py``) for long-context
training — absent from the reference (SURVEY.md §5). Where ring attention
keeps the sequence sharded and rotates K/V, Ulysses re-shards: activations
arrive sequence-sharded (each chip holds S/n of every head), an all-to-all
over the ``sp`` axis converts them to head-sharded (each chip holds H/n
heads with the FULL sequence), ordinary (flash) attention runs locally, and
a second all-to-all restores sequence sharding. Two all-to-alls per
attention call, but the inner attention is completely local — best when
heads >= sp and the per-chip full-sequence K/V fits HBM.
"""

from __future__ import annotations

import functools

import jax
from jax import lax

from bigdl_tpu.ops.attention import dot_product_attention


def _a2a(x, axis_name, split_axis, concat_axis):
    """all_to_all keeping (b, h, s, d) rank: split ``split_axis`` across the
    axis group, concatenate the received shards along ``concat_axis``."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      bias=None):
    """Attention on sequence-sharded q/k/v via head<->sequence all-to-all.

    Call inside shard_map. Local shapes (b, h, s_local, d); h must be
    divisible by the size of ``axis_name``.
    """
    n = lax.psum(1, axis_name)
    if q.shape[1] % n:
        raise ValueError(
            f"num_heads ({q.shape[1]}) must be divisible by the "
            f"'{axis_name}' axis size ({n})"
        )
    # seq-sharded -> head-sharded: split heads (axis 1), gather seq (axis 2)
    qh = _a2a(q, axis_name, 1, 2)
    kh = _a2a(k, axis_name, 1, 2)
    vh = _a2a(v, axis_name, 1, 2)
    o = dot_product_attention(qh, kh, vh, bias=bias, causal=causal)
    # head-sharded -> seq-sharded
    return _a2a(o, axis_name, 2, 1)


def make_ulysses_attention(mesh, axis_name: str, causal: bool = False):
    """shard_map wrapper over GLOBAL (b, h, s, d) arrays, seq sharded."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
