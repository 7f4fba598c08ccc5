"""Ring attention: exact attention over sequences sharded across chips.

Absent from the reference — its longest-sequence story is padding +
single-node BPTT (SURVEY.md §5 "Long-context / sequence parallelism:
Absent", ``DL/dataset/MiniBatch.scala:523-587``). On TPU, long context is a
first-class axis: the sequence dim is sharded over the ``sp`` mesh axis,
each chip holds its local Q block permanently, and K/V blocks rotate around
the ring via ``ppermute`` while an online-softmax accumulator (running max
``m`` and normalizer ``l``, exactly the flash-attention statistics) merges
each visiting block. Peak memory per chip is O(S/n * S_block) instead of
O(S^2); communication is n-1 ppermute hops that overlap with compute on
real ICI rings.

Causal handling is by block index: a visiting K/V block strictly *after*
my Q block contributes nothing (skipped via masking), the diagonal block
applies the triangular mask, earlier blocks attend fully.

API: ``ring_attention(q, k, v, axis_name, causal=...)`` must be called
*inside* a ``shard_map`` whose mesh has ``axis_name``; q/k/v are the local
shards, shape (batch, heads, seq_local, head_dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


from bigdl_tpu.parallel.mesh import ring_perm


def _block_attend(q, k, v, scale, mask):
    """Scores + masked partial softmax stats for one (q_block, kv_block) pair.

    Returns (numerator [b,h,sq,d], row max m [b,h,sq], row sum l [b,h,sq]).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    num = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return num, m, l


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: float | None = None):
    """Exact attention with K/V rotated around the ``axis_name`` ring.

    Call inside shard_map; q/k/v: (b, h, s_local, d) local shards with the
    global sequence laid out contiguously along the mesh axis.
    """
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    b, h, sq, d = q.shape
    perm = ring_perm(n)

    qf = q.astype(jnp.float32)

    def step(carry, i):
        k_cur, v_cur, num, m, l = carry
        src = (my_idx - i) % n  # global block index of the visiting K/V

        s_mask = None
        if causal:
            # rows: global positions my_idx*sq + [0,sq); cols: src*sq + [0,sq)
            rows = my_idx * sq + jnp.arange(sq)
            cols = src * sq + jnp.arange(k_cur.shape[2])
            s_mask = rows[:, None] >= cols[None, :]

        bnum, bm, bl = _block_attend(qf, k_cur, v_cur, scale, s_mask)
        if causal:
            # a fully-masked block yields m = -inf rows; guard the merge
            dead = src * sq > my_idx * sq + sq - 1  # block strictly after mine
        else:
            dead = False

        new_m = jnp.maximum(m, bm)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(bm - new_m)
        num2 = num * alpha[..., None] + bnum * beta[..., None]
        l2 = l * alpha + bl * beta
        num2, m2, l2 = jax.tree_util.tree_map(
            lambda new, old: jnp.where(dead, old, new) if causal else new,
            (num2, new_m, l2), (num, m, l),
        )

        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, num2, m2, l2), None

    num0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    # mark the accumulators device-varying over the ring axis so the scan
    # carry types line up with the (varying) k/v shards
    num0, m0, l0 = (
        jax.lax.pcast(t, axis_name, to="varying") for t in (num0, m0, l0))
    (k_f, v_f, num, m, l), _ = lax.scan(
        step, (k, v, num0, m0, l0), jnp.arange(n)
    )
    out = num / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def make_ring_attention(mesh, axis_name: str, causal: bool = False):
    """Wrap ``ring_attention`` in a shard_map over ``mesh``.

    Returns a function (q, k, v) -> out operating on GLOBAL arrays whose
    sequence dim (axis 2) is sharded over ``axis_name``.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)
