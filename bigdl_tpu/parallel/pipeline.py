"""Pipeline parallelism: GPipe-style microbatched stage execution.

Absent from the reference (SURVEY.md §2.3 — no PP). TPU-native design:
stage parameters are stacked along a leading ``[n_stages, ...]`` dim that is
sharded over the ``pp`` mesh axis, so each chip physically holds exactly one
stage's weights. A ``shard_map`` runs the classic GPipe schedule: for
``n_micro + n_stages - 1`` ticks, every chip applies its stage to the
activation it holds and ``ppermute``s the result to the next chip. The
schedule is a ``lax.scan`` (static trip count — XLA-friendly), and the whole
thing is reverse-differentiable: the transpose of ``ppermute`` is the
reverse ppermute, so ``jax.grad`` of a pipelined loss yields the standard
backward pipeline schedule automatically.

This mirrors the collective-pipelining recipe of the public scaling
literature rather than anything in the reference, whose only scale-out axis
is data parallelism over the BlockManager PS (SURVEY.md §3.1).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from bigdl_tpu.parallel.mesh import ring_perm


def _stage_body(stage_fn, n_stages, n_micro, axis_name, params, xs):
    """Per-chip GPipe schedule. ``params``: this chip's stage params (leading
    stage dim of size 1, squeezed). ``xs``: [n_micro, ...] microbatches
    (meaningful on stage 0; other chips carry zeros)."""
    stage = lax.axis_index(axis_name)
    n = n_stages
    total = n_micro + n - 1
    perm = ring_perm(n)

    micro_shape = xs.shape[1:]
    out0 = jnp.zeros((n_micro,) + micro_shape, xs.dtype)
    recv0 = jnp.zeros(micro_shape, xs.dtype)
    out0, recv0, xs = (lax.pcast(t, axis_name, to="varying")
                       for t in (out0, recv0, xs))

    def tick(carry, t):
        recv, outs = carry
        # stage 0 feeds microbatch t (clipped; masked out when t >= n_micro)
        feed = xs[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(stage == 0, feed, recv)
        y = stage_fn(params, x_in)
        # last stage banks output for microbatch t-(n-1)
        widx = t - (n - 1)
        wclip = jnp.clip(widx, 0, n_micro - 1)
        bank = jnp.where((stage == n - 1) & (widx >= 0), y, outs[wclip])
        outs = lax.dynamic_update_index_in_dim(outs, bank, wclip, 0)
        recv_next = lax.ppermute(y, axis_name, perm)
        return (recv_next, outs), None

    (recv, outs), _ = lax.scan(tick, (recv0, out0), jnp.arange(total))
    # deliver outputs from the last stage to every chip (so the caller can
    # compute a replicated loss); psum of a one-hot-masked bank
    outs = lax.psum(
        jnp.where(stage == n - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   mesh: Mesh,
                   stacked_params: Any,
                   x: jax.Array,
                   n_micro: int,
                   axis_name: str = "pp"):
    """Run ``x`` through a pipeline of stages over ``mesh[axis_name]``.

    - ``stage_fn(params_i, x_micro) -> y_micro`` — one stage's computation;
      every stage must map the same activation shape to itself.
    - ``stacked_params``: pytree whose leaves have leading dim n_stages,
      sharded over ``axis_name``.
    - ``x``: [batch, ...] global batch; must divide into ``n_micro``
      microbatches.

    Returns [batch, ...] outputs (replicated over the pp axis).
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible into {n_micro} microbatches")
    xs = x.reshape((n_micro, b // n_micro) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_params)
    body = functools.partial(_stage_body, stage_fn, n_stages, n_micro,
                             axis_name)

    def per_chip(params, xs_local):
        squeezed = jax.tree_util.tree_map(lambda a: a[0], params)
        return body(squeezed, xs_local)

    fn = jax.shard_map(per_chip, mesh=mesh,
                       in_specs=(param_specs, P()),
                       out_specs=P(), check_vma=False)
    ys = fn(stacked_params, xs)
    return ys.reshape((b,) + ys.shape[2:])


def _hetero_body(stage_fns, n_stages, n_micro, axis_name,
                 params, states, xs, rng, training):
    """Per-chip GPipe schedule for HETEROGENEOUS, STATEFUL stages.

    Differences from :func:`_stage_body`:

    - the stage computation is a ``lax.switch`` on the chip's pp index
      over per-stage branches, so stages may be arbitrary distinct
      modules (params/state held in a ``{"stage{i}": ...}`` dict,
      replicated — the memory trade documented in ``HeteroPipeline``);
    - module state (BN running stats, ...) is threaded through the scan
      carry, with updates COMMITTED only on valid ticks (a chip at pp
      index s is warming up while ``t < s`` and draining while
      ``t - s >= n_micro``; its garbage computations must not pollute
      running statistics);
    - a per-(stage, microbatch) rng is folded for dropout streams,
      matching the sequential-microbatch reference semantics.
    """
    stage = lax.axis_index(axis_name)
    n = n_stages
    total = n_micro + n - 1
    perm = ring_perm(n)

    micro_shape = xs.shape[1:]
    out0, recv0, xs, states = jax.tree_util.tree_map(
        lambda a: lax.pcast(a, axis_name, to="varying"),
        (jnp.zeros((n_micro,) + micro_shape, xs.dtype),
         jnp.zeros(micro_shape, xs.dtype), xs, states))

    def branches(i):
        def br(x, st, key):
            y, ns_i = stage_fns[i](params[f"stage{i}"], x,
                                   st[f"stage{i}"], key, training)
            return y, {**st, f"stage{i}": ns_i}
        return br

    brs = [branches(i) for i in range(n)]

    def tick(carry, t):
        recv, outs, st = carry
        feed = xs[jnp.clip(t, 0, n_micro - 1)]
        x_in = jnp.where(stage == 0, feed, recv)
        # the microbatch this chip touches at tick t, and its validity
        midx = jnp.clip(t - stage, 0, n_micro - 1)
        valid = (t >= stage) & (t - stage < n_micro)
        key = None
        if rng is not None:
            key = jax.random.fold_in(jax.random.fold_in(rng, stage), midx)
        y, new_st = lax.switch(stage, brs, x_in, st, key)
        st = jax.tree_util.tree_map(
            lambda a, b: jnp.where(valid, a, b), new_st, st)
        widx = t - (n - 1)
        wclip = jnp.clip(widx, 0, n_micro - 1)
        bank = jnp.where((stage == n - 1) & (widx >= 0), y, outs[wclip])
        outs = lax.dynamic_update_index_in_dim(outs, bank, wclip, 0)
        recv_next = lax.ppermute(y, axis_name, perm)
        return (recv_next, outs, st), None

    (recv, outs, states), _ = lax.scan(
        tick, (recv0, out0, states), jnp.arange(total))
    outs = lax.psum(
        jnp.where(stage == n - 1, outs, jnp.zeros_like(outs)), axis_name)
    # merge state: stage i's entries are authoritative on chip i only
    merged = {}
    for i in range(n):
        merged[f"stage{i}"] = jax.tree_util.tree_map(
            lambda a: lax.psum(jnp.where(stage == i, a, jnp.zeros_like(a)),
                               axis_name),
            states[f"stage{i}"])
    return outs, merged


class HeteroPipeline:
    """Trainable pipeline over a HETEROGENEOUS list of stage modules with
    mutable state (BatchNorm running stats), dropout rng, and an optional
    remat mode.

    Semantics: identical to running the microbatches SEQUENTIALLY through
    ``stages[0] .. stages[n-1]`` on one device with the module state
    threaded micro-by-micro (each microbatch is normalized by its own
    batch statistics — grad-accumulation/ghost-BN semantics; equality
    tested in ``tests/test_parallel.py``).

    Placement trade (documented): per-stage params are REPLICATED over
    the pp axis and selected by ``lax.switch`` — heterogeneous pytrees
    cannot be stacked-and-sharded like :class:`Pipeline`'s homogeneous
    stages, so this class buys arbitrary stage structure at the price of
    per-chip weight memory. Use :class:`Pipeline` when the stages are
    one repeated block; use this when they are not.

    ``remat=True`` wraps each stage application in ``jax.checkpoint`` so
    the backward pipeline (the scan's transpose — ppermutes reverse
    automatically) recomputes stage internals instead of saving them:
    per-tick residuals shrink to the stage INPUT, the memory property
    1F1B schedules exist for. A hand-interleaved 1F1B would fight XLA's
    scheduler for decisions it owns (SURVEY §7: static schedules belong
    to the compiler); the scan transpose already yields the reverse
    pipeline order.
    """

    def __init__(self, stages, mesh: Mesh, n_micro: int,
                 axis_name: str = "pp", remat: bool = False):
        self.stages = list(stages)
        self.mesh = mesh
        self.n_micro = n_micro
        self.axis_name = axis_name
        self.remat = remat
        self.n_stages = mesh.shape[axis_name]
        if len(self.stages) != self.n_stages:
            raise ValueError(
                f"{len(self.stages)} stage modules for a "
                f"{self.n_stages}-way '{axis_name}' mesh axis")

    def init(self, rng):
        params, states = {}, {}
        for i, (m, k) in enumerate(
                zip(self.stages, jax.random.split(rng, self.n_stages))):
            p, s = m.init(k)
            params[f"stage{i}"] = p
            states[f"stage{i}"] = s
        return params, states

    def _stage_fns(self):
        fns = []
        for m in self.stages:
            def fn(p, x, s, key, training, m=m):
                out, ns = m.apply(p, x, state=s, training=training, rng=key)
                return out, ns
            fns.append(jax.checkpoint(fn, static_argnums=(4,))
                       if self.remat else fn)
        return fns

    def apply(self, params, states, x, training: bool = False, rng=None):
        """Returns ``(outputs [batch, ...], new_states)`` — both
        replicated over the pp axis.

        Constraint (inherent to the ring schedule): every stage must map
        a microbatch to the SAME shape and dtype — the ppermute buffers
        are sized once from the input. Width-changing stages need an
        embedding into a common activation shape.
        """
        n = self.n_stages
        b = x.shape[0]
        if b % self.n_micro:
            raise ValueError(
                f"batch {b} not divisible into {self.n_micro} microbatches")
        mb = (b // self.n_micro,) + x.shape[1:]
        xm = jax.ShapeDtypeStruct(mb, x.dtype)
        for i, m in enumerate(self.stages):
            # probe in eval mode: shapes are identical and no rng is
            # needed (Dropout in training mode would demand one)
            out_sd = jax.eval_shape(
                lambda p, s, a, m=m: m.apply(p, a, state=s,
                                             training=False)[0],
                params[f"stage{i}"], states[f"stage{i}"], xm)
            if out_sd.shape != mb or out_sd.dtype != x.dtype:
                raise ValueError(
                    f"pipeline stage {i} maps {mb}/{x.dtype} -> "
                    f"{out_sd.shape}/{out_sd.dtype}; every stage must "
                    "preserve the microbatch shape and dtype (the ring "
                    "schedule's buffers are sized once from the input)")
        xs = x.reshape((self.n_micro, b // self.n_micro) + x.shape[1:])
        body = functools.partial(
            _hetero_body, self._stage_fns(), n, self.n_micro, self.axis_name)

        def per_chip(params, states, xs_local, rng_in):
            return body(params, states, xs_local, rng_in, training)

        repl = P()
        fn = jax.shard_map(per_chip, mesh=self.mesh,
                           in_specs=(repl, repl, repl, repl),
                           out_specs=(repl, repl),
                           check_vma=False)
        ys, new_states = fn(params, states, xs, rng)
        return ys.reshape((b,) + ys.shape[2:]), new_states


def make_pp_train_step(pipeline: "HeteroPipeline", criterion, method):
    """One jittable train step over a :class:`HeteroPipeline`:
    ``step(params, states, ostate, x, y, it[, rng]) ->
    (params, states, ostate, loss)``. Gradients flow through the
    ppermute schedule (its transpose is the reverse pipeline); cotangent
    psums for the replicated stage params are inserted by shard_map's
    transpose automatically."""

    def step(params, states, ostate, x, y, it, rng=None):
        def loss_fn(p):
            ys, ns = pipeline.apply(p, states, x, training=True, rng=rng)
            ys = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, ys)
            return criterion.forward(ys, y), ns

        (loss, new_states), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_p, new_os = method.update(grads, params, ostate, it)
        return new_p, new_states, new_os, loss

    return jax.jit(step)


class Pipeline:
    """Convenience wrapper: stack per-stage params and apply the schedule.

    ``Pipeline(module, mesh, n_micro)`` treats ``module`` as ONE repeated
    stage (the homogeneous-stage case — e.g. a transformer block repeated
    ``pp`` times). ``init`` builds per-stage params stacked on dim 0 with
    per-stage RNG streams; ``apply`` runs the GPipe schedule.
    """

    def __init__(self, stage_module, mesh: Mesh, n_micro: int,
                 axis_name: str = "pp"):
        self.stage = stage_module
        self.mesh = mesh
        self.n_micro = n_micro
        self.axis_name = axis_name
        self.n_stages = mesh.shape[axis_name]

    def init(self, rng):
        keys = jax.random.split(rng, self.n_stages)
        inits = [self.stage.init(k) for k in keys]
        if any(s for _, s in inits):
            raise ValueError(
                "Pipeline (stacked homogeneous stages) does not thread "
                "mutable state through the schedule. Use HeteroPipeline, "
                "which supports stateful stages (BatchNorm running stats), "
                "dropout rng, and heterogeneous stage lists."
            )
        ps = [p for p, _ in inits]
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *ps)
        sharding = jax.tree_util.tree_map(
            lambda _: jax.sharding.NamedSharding(self.mesh, P(self.axis_name)),
            stacked)
        return jax.tree_util.tree_map(jax.device_put, stacked, sharding)

    def apply(self, stacked_params, x):
        def stage_fn(p, xm):
            out, _ = self.stage.apply(p, xm)
            return out

        return pipeline_apply(stage_fn, self.mesh, stacked_params, x,
                              self.n_micro, self.axis_name)
