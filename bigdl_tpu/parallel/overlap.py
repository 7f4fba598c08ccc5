"""Overlapped (layer-wise, bucketed) gradient synchronization.

Reference: the reference implements compute/communication overlap in
``DL/optim/ParallelOptimizer.scala:481`` (layer-wise gradient sync
launched as each layer's backward completes) and
``DL/utils/DistriParameterSynchronizer.scala:66-146`` (priority-queued
fetch/reduce threads moving per-layer fp16 blocks while the rest of the
backward still runs).

TPU-native redesign: there are no sync threads to write — the same
schedule property (early buckets' gradients on the wire while later
layers' backward computes) is obtained INSIDE one jitted SPMD program.
Parameters entering the loss are tagged with a ``jax.custom_vjp``
identity per bucket whose backward rule issues the collective — ``psum``
for DDP, ``psum_scatter`` for the ZeRO-1 flavor — at the exact dataflow
point where that bucket's cotangents come into existence. The
collectives therefore sit in the middle of the backward graph carrying
only their true dependencies; the scheduler is free to run the rest of
the backward while the wire is busy, instead of the auto-sharding
baseline where the AllReduceCombiner rolls every gradient into one
all-reduce AFTER the full backward. ``perf/overlap_sched.py``
AOT-compiles both flavors for a real v5e topology and records the
collective placement as the round-5 artifact.

Gradient-mean semantics: each shard computes the mean loss over its
LOCAL batch rows; the bucket collectives divide the summed cotangents by
the dp axis size, so the resulting gradients equal the global-batch mean
— identical math to the auto-sharded ``DistriOptimizer`` step (equality
tested on the 8-device CPU mesh, ``tests/test_overlap.py``).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------- bucketing ----

def make_buckets(leaves: Sequence[Any], num_buckets: int) -> List[List[int]]:
    """Group leaf indices into <= num_buckets CONTIGUOUS groups of roughly
    equal byte size. Contiguity in flatten order approximates usage order,
    so each bucket's cotangents become ready at adjacent points of the
    backward — the property layer-wise overlap needs (the reference
    buckets per layer; DistriParameterSynchronizer.scala:96)."""
    sizes = [int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
             if hasattr(l, "shape") else 1 for l in leaves]
    total = sum(sizes)
    if not leaves or num_buckets <= 1 or total == 0:
        return [list(range(len(leaves)))] if leaves else []
    target = total / num_buckets
    buckets: List[List[int]] = [[]]
    acc = 0
    for i, s in enumerate(sizes):
        remaining_buckets = num_buckets - len(buckets)
        if buckets[-1] and acc + s / 2 > target and remaining_buckets > 0:
            buckets.append([])
            acc = 0
        buckets[-1].append(i)
        acc += s
    return buckets


# ----------------------------------------------------- chain gating ----

def _zero_gate(x, dtype):
    """``min(|x|, 0)`` — exactly 0 at runtime but not provably so to the
    algebraic simplifier, so adding it creates a REAL dataflow edge on
    ``x`` that survives XLA's passes. This is the load-bearing
    anti-combiner trick behind the bucket chain: do not replace it with
    ``0.0 * x`` (the simplifier folds that) or ``optimization_barrier``
    (expanded away before the AllReduceCombiner runs, and its diff rule
    only exists on newer jax). Every gating site in this file must use
    this one helper so the idiom cannot drift."""
    return jnp.minimum(jnp.abs(x), 0.0).astype(dtype)


# --------------------------------------------------- DDP bucket psum ----

def _psum_tag(axis_name: str, n: int, wire_dtype=None):
    """custom_vjp identity over ``(token, *leaves)``; backward psums the
    leaf cotangents (one tuple all-reduce per bucket) and divides by the
    axis size — local-mean grads in, global-mean grads out.

    The token threads a data dependency BETWEEN buckets (see the inline
    note in ``bwd``) so the AllReduceCombiner cannot re-merge the
    buckets into one post-backward collective.

    ``wire_dtype`` (e.g. ``jnp.bfloat16``) compresses the collective
    payload — the reference ships per-layer fp16 blocks the same way
    (``DistriParameterSynchronizer.scala:96``); gradients are cast for
    the wire and accumulated back in their original dtype. None = exact.
    """

    @jax.custom_vjp
    def tag(tok, *leaves):
        return (tok, *leaves)

    def fwd(tok, *leaves):
        return (tok, *leaves), None

    def bwd(_, cots):
        tok_cot, *leaf_cots = cots
        dtypes = [g.dtype for g in leaf_cots]
        if wire_dtype is not None:
            leaf_cots = [g.astype(wire_dtype) for g in leaf_cots]
        # chain through the LEAF DATA: every leaf input of this bucket's
        # psum absorbs the zero gate of the token, so bucket i's
        # all-reduce depends directly on bucket i+1's output. Every leaf
        # must be gated: an AR-splitting pass was measured peeling
        # ungated elements out of the bucket and re-combining them.
        # (Three weaker schemes also measured and rejected: a token chain
        # beside the psums, optimization_barrier gating, and a token
        # element inside the psum tuple, which the splitter separated
        # back out; each time the leaf all-reduces were re-merged into
        # one 102 MB post-backward collective.)
        leaf_cots = [g + _zero_gate(tok_cot, g.dtype) for g in leaf_cots]
        summed = lax.psum(tuple(leaf_cots), axis_name)
        # ...and EVERY element's output feeds the outgoing token: with a
        # single-element token source, the combiner was measured peeling
        # the non-source elements out of the bucket (their outputs carry
        # no chain dependency) and merging them into a later bucket's AR
        tok_out = tok_cot + sum(
            _zero_gate(jnp.ravel(g)[0], tok_cot.dtype) for g in summed)
        return (tok_out, *(g.astype(dt) / n
                           for g, dt in zip(summed, dtypes)))

    tag.defvjp(fwd, bwd)
    return tag


def tag_grad_sync(params, axis_name: str, n: int, num_buckets: int = 4,
                  wire_dtype=None):
    """Tag a param pytree so its gradient is synchronized bucket-by-bucket
    during the backward pass. Must run inside ``shard_map`` over
    ``axis_name``. Returns ``(params, token)`` — params unchanged in
    value, plus a scalar token that MUST be folded into the loss (e.g.
    via :func:`fold_token`) so the bucket-chaining dependencies survive.

    Token direction: the forward chain visits buckets FIRST -> LAST, so
    in the backward (cotangent flow reverses it) the LAST bucket — later
    layers, whose cotangents exist earliest — fires first and hands the
    token to the next-earlier bucket as its cotangents become ready: a
    sequential wire schedule in cotangent-availability order, leaving the
    remaining backward free to overlap — exactly the reference's
    priority-queued layer order
    (``DistriParameterSynchronizer.scala:96``)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    out = list(leaves)
    tag = _psum_tag(axis_name, n, wire_dtype)
    tok = jnp.zeros((), leaves[0].dtype if leaves else jnp.float32)
    for idx_group in make_buckets(leaves, num_buckets):
        tok, *synced = tag(tok, *(out[i] for i in idx_group))
        for i, v in zip(idx_group, synced):
            out[i] = v
    return jax.tree_util.tree_unflatten(treedef, out), tok


def fold_token(loss, tok):
    """Attach the chain token to the loss without changing its value
    (:func:`_zero_gate` keeps the dependency alive through the
    simplifier and stays differentiable on every jax version)."""
    return loss + _zero_gate(tok, loss.dtype)


# ------------------------------------------------- ZeRO-1 RS bucket ----

class _BucketLayout:
    """Static flatten/concat layout of one bucket: leaf shapes, dtypes,
    offsets, and the padded per-shard chunk size."""

    def __init__(self, leaves, n):
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.total = sum(self.sizes)
        self.chunk = math.ceil(self.total / n) if self.total else 0
        self.padded = self.chunk * n

    def flatten(self, leaves):
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])
        if self.padded > self.total:
            flat = jnp.pad(flat, (0, self.padded - self.total))
        return flat

    def unflatten(self, flat):
        outs, off = [], 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            outs.append(lax.slice_in_dim(flat, off, off + size)
                        .reshape(shape).astype(dtype))
            off += size
        return tuple(outs)


def _rs_tag(axis_name: str, n: int, layout: _BucketLayout):
    """custom_vjp identity whose backward reduce-scatters the bucket's
    flattened cotangents (ZeRO-1 wire pattern: RS in backward, AG of
    updated weights after the optimizer). Each shard's returned cotangent
    holds ONLY its own chunk (zeros elsewhere) — the step slices the
    owned chunk back out; nothing ever reads the zeros. Token chaining as
    in :func:`_psum_tag` (anti-combiner + sequential wire order)."""

    @jax.custom_vjp
    def tag(tok, *leaves):
        return (tok, *leaves)

    def fwd(tok, *leaves):
        return (tok, *leaves), None

    def bwd(_, cots):
        tok_cot, *leaf_cots = cots
        flat = layout.flatten(leaf_cots)
        # chain the collective on the previous bucket's token
        # (:func:`_zero_gate`; see _psum_tag for the measured rationale):
        # the in-place add makes this reduce-scatter's input depend on
        # the previous one's output
        flat = flat.at[0].add(_zero_gate(tok_cot, flat.dtype))
        chunk = lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                 tiled=True) / n
        idx = lax.axis_index(axis_name)
        full = jnp.zeros((layout.padded,), flat.dtype)
        full = lax.dynamic_update_slice(full, chunk, (idx * layout.chunk,))
        tok_cot = tok_cot + _zero_gate(chunk[0], tok_cot.dtype)
        return (tok_cot, *layout.unflatten(full))

    tag.defvjp(fwd, bwd)
    return tag


# -------------------------------------------- module-state reduction ----

#: Per-leaf cross-shard reduction policy for module state after the step.
#: Keyed by the leaf's own dict key: leaves named here reduce with the
#: given collective; every other inexact leaf reduces with ``pmean``
#: (SyncBN-mean running stats). Running EXTREMA must not be averaged:
#: the int8 calibration absmax (``nn/quantized.py`` ``act_absmax``) is a
#: running max over observed activations, and a mean across shards would
#: shrink the calibrated scale as the shard count grows (ADVICE round 5).
STATE_REDUCE_POLICY: Dict[str, str] = {"act_absmax": "max"}


def _reduce_module_state(new_ms, axis_name: str):
    """Cross-shard module-state sync with the per-leaf policy above."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(new_ms)
    out = []
    for path, leaf in flat:
        if not jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            out.append(leaf)
            continue
        key = next((p.key for p in reversed(path)
                    if isinstance(p, jax.tree_util.DictKey)), None)
        how = STATE_REDUCE_POLICY.get(key, "mean")
        out.append(lax.pmax(leaf, axis_name) if how == "max"
                   else lax.pmean(leaf, axis_name))
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------ step builders ----

def make_ddp_overlap_step(model, criterion, method, mesh: Mesh,
                          axis: str = "dp", num_buckets: int = 4,
                          compute_dtype=None, cast_input=None,
                          grad_clip=None, with_rng: bool = False,
                          wire_dtype=None):
    """Data-parallel train step with bucketed overlap-eligible gradient
    all-reduce. Signature: ``step(params, mstate, ostate, x, y, it[, rng])
    -> (params, mstate, ostate, loss)`` with params/state replicated and
    x/y batch-sharded over ``axis``. This is also the engine behind
    ``DistriOptimizer(overlap_buckets=K)`` (which supplies ``cast_input``,
    ``grad_clip`` and ``with_rng`` — keep one implementation of the
    semantics).

    Criterion contract: the loss must be an UNWEIGHTED MEAN over local
    batch rows (``size_average=True``, no per-class ``weights``). The
    bucket collectives divide summed cotangents by the dp axis size,
    which equals the global-batch gradient only under that contract — a
    sum loss is mis-scaled by 1/n and a weighted mean normalizes by the
    local (not global) weight sum. ``DistriOptimizer._build_step``
    enforces this; callers using the builder directly must too.

    Module state (BN running stats) is synced across shards after the
    step with a per-leaf policy (:data:`STATE_REDUCE_POLICY`): means for
    running averages (SyncBN-mean running stats; batch statistics
    themselves stay per-shard — same semantics as torch DDP, a documented
    deviation from the auto-sharded path's exact global statistics), max
    for running extrema like the int8 calibration ``act_absmax``.
    """
    n = mesh.shape[axis]

    def _core(params, mstate, ostate, x, y, it, rng):
        if cast_input is not None:
            x = cast_input(x)
        elif compute_dtype is not None:
            x = x.astype(compute_dtype)

        def loss_fn(p):
            p, tok = tag_grad_sync(p, axis, n, num_buckets, wire_dtype)
            kw = {"rng": rng} if rng is not None else {}
            out, new_ms = model.apply(p, x, state=mstate, training=True, **kw)
            out = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, out)
            return fold_token(criterion.forward(out, y), tok), new_ms

        (loss, new_ms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        # grads are global means already (bucket psums fired in backward),
        # so grad_clip sees the same values as the auto-sharded path
        if grad_clip is not None:
            grads = grad_clip(grads)
        new_p, new_os = method.update(grads, params, ostate, it)
        new_ms = _reduce_module_state(new_ms, axis)
        return new_p, new_ms, new_os, lax.pmean(loss, axis)

    repl, shard = P(), P(axis)
    if with_rng:
        def _step(params, mstate, ostate, x, y, it, rng):
            # decorrelate per-shard dropout noise (the auto path draws
            # per-row noise from one global key; folding the shard index
            # keeps shards independent — not bit-identical, same law)
            rng = jax.random.fold_in(rng, lax.axis_index(axis))
            return _core(params, mstate, ostate, x, y, it, rng)
        in_specs = (repl, repl, repl, shard, shard, repl, repl)
    else:
        def _step(params, mstate, ostate, x, y, it):
            return _core(params, mstate, ostate, x, y, it, None)
        in_specs = (repl, repl, repl, shard, shard, repl)
    return jax.shard_map(
        _step, mesh=mesh,
        in_specs=in_specs,
        out_specs=(repl, repl, repl, repl),
        check_vma=False,
    )


def zero1_init_state(method, params, mesh: Mesh, axis: str = "dp",
                     num_buckets: int = 4):
    """Per-bucket CHUNKED optimizer state for the ZeRO-1 overlap step:
    each state leaf is a flat (n*chunk,) vector of which every shard owns
    one (chunk,) slice — the reference's PS-partitioned optimizer state
    (``DistriOptimizer.scala:383-390``) as sharded flat vectors. Place
    with :func:`zero1_state_sharding` before use."""
    n = mesh.shape[axis]
    leaves, _ = jax.tree_util.tree_flatten(params)
    states = {}
    for b, idx_group in enumerate(make_buckets(leaves, num_buckets)):
        layout = _BucketLayout([leaves[i] for i in idx_group], n)
        chunk_zeros = jnp.zeros((layout.padded,), jnp.float32)
        states[f"bucket{b}"] = method.init_state({"flat": chunk_zeros})
    return states


def zero1_state_sharding(state, mesh: Mesh, axis: str = "dp"):
    """Shard every (n*chunk,) state vector over the dp axis."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(
        lambda l: jax.device_put(l, sh) if hasattr(l, "ndim") and l.ndim == 1
        else l, state)


def make_zero1_overlap_step(model, criterion, method, mesh: Mesh,
                            ostate_template, axis: str = "dp",
                            num_buckets: int = 4, compute_dtype=None):
    """ZeRO-1 train step with reduce-scatter-in-backward overlap.

    Wire pattern per bucket: ``psum_scatter`` of the gradient the moment
    the bucket's backward completes (overlap-eligible), an ELEMENTWISE
    optimizer update on the owned 1/n chunk against chunked optimizer
    state, then an ``all_gather`` of the updated weights — exactly the
    reference protocol (gradient reduce-scatter -> per-partition update
    -> weight all-gather, ``DistriOptimizer.scala:323-418``) with XLA
    collectives instead of BlockManager fetches.

    Restriction: the optim method must be elementwise in params/grads
    (SGD/Adam/RMSprop/...); norm-based methods (LARS) would see chunk
    norms. That is the standard ZeRO-1 contract. The criterion contract
    of :func:`make_ddp_overlap_step` applies identically here: an
    unweighted mean loss, because the reduce-scatter divides summed
    cotangents by the dp axis size. Module state syncs with the same
    per-leaf :data:`STATE_REDUCE_POLICY` (mean for running averages, max
    for calibration extrema).

    Signature: ``step(params, mstate, ostate, x, y, it)`` with ``ostate``
    from :func:`zero1_init_state` sharded by :func:`zero1_state_sharding`
    (pass the same object as ``ostate_template`` — its tree structure
    determines the per-leaf shard_map specs: flat vectors dp-sharded,
    scalars like the step count replicated); params/mstate replicated,
    x/y sharded over ``axis``.
    """
    n = mesh.shape[axis]
    state_spec = jax.tree_util.tree_map(
        lambda l: P(axis) if getattr(l, "ndim", 0) >= 1 else P(),
        ostate_template)

    def _step(params, mstate, ostate, x, y, it):
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        buckets = make_buckets(leaves, num_buckets)
        layouts = [_BucketLayout([leaves[i] for i in g], n) for g in buckets]

        def loss_fn(p):
            p_leaves = list(jax.tree_util.tree_flatten(p)[0])
            tok = jnp.zeros((), jnp.float32)
            for g, layout in zip(buckets, layouts):
                tok, *synced = _rs_tag(axis, n, layout)(
                    tok, *(p_leaves[i] for i in g))
                for i, v in zip(g, synced):
                    p_leaves[i] = v
            p = jax.tree_util.tree_unflatten(treedef, p_leaves)
            out, new_ms = model.apply(p, x, state=mstate, training=True)
            out = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, out)
            return fold_token(criterion.forward(out, y), tok), new_ms

        (loss, new_ms), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)

        g_leaves = jax.tree_util.tree_flatten(grads)[0]
        idx = lax.axis_index(axis)
        new_leaves = list(leaves)
        new_ostate = {}
        for b, (group, layout) in enumerate(zip(buckets, layouts)):
            if layout.chunk == 0:
                new_ostate[f"bucket{b}"] = ostate[f"bucket{b}"]
                continue
            gflat = layout.flatten([g_leaves[i] for i in group])
            pflat = layout.flatten([leaves[i] for i in group])
            start = (idx * layout.chunk,)
            gchunk = lax.dynamic_slice(gflat, start, (layout.chunk,))
            pchunk = lax.dynamic_slice(pflat, start, (layout.chunk,))
            new_chunk, new_os = method.update(
                {"flat": gchunk}, {"flat": pchunk},
                ostate[f"bucket{b}"], it)
            new_ostate[f"bucket{b}"] = new_os
            full = lax.all_gather(new_chunk["flat"], axis, tiled=True)
            for i, v in zip(group, layout.unflatten(full)):
                new_leaves[i] = v

        new_p = jax.tree_util.tree_unflatten(treedef, new_leaves)
        new_ms = _reduce_module_state(new_ms, axis)
        return new_p, new_ms, new_ostate, lax.pmean(loss, axis)

    repl, shard = P(), P(axis)
    return jax.shard_map(
        _step, mesh=mesh,
        in_specs=(repl, repl, state_spec, shard, shard, repl),
        out_specs=(repl, repl, state_spec, repl),
        check_vma=False,
    )
