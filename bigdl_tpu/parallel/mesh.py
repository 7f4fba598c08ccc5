"""Device-mesh construction and sharding-constraint helpers.

The reference's notion of topology is ``Engine.nodeNumber x coreNumber``
(``DL/utils/Engine.scala:279,302``) wired into Spark partition placement.
The TPU-native topology is a named ``jax.sharding.Mesh``; every parallelism
strategy is an axis name, and placement is expressed as ``PartitionSpec``s
that XLA's GSPMD partitioner turns into collectives over ICI/DCN.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_local = threading.local()


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named mesh axes, e.g. ``MeshSpec(dp=2, tp=2, sp=2)``.

    Axis order follows the declaration order; put the fastest-varying
    (innermost-ICI) axis last — on real slices, XLA maps trailing mesh dims
    to the most tightly coupled devices, so ``tp``/``sp`` (which carry
    per-layer collectives) should come after ``dp``/``pp``.
    """

    axes: Tuple[Tuple[str, int], ...]

    def __init__(self, axes: Optional[Sequence[Tuple[str, int]]] = None, **kw: int):
        entries = tuple(axes or ()) + tuple(kw.items())
        object.__setattr__(self, "axes", entries)

    @property
    def size(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    def names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.axes)


def make_mesh(spec: MeshSpec, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if spec.size > len(devices):
        raise ValueError(f"mesh needs {spec.size} devices, have {len(devices)}")
    arr = np.asarray(devices[: spec.size]).reshape([s for _, s in spec.axes])
    return Mesh(arr, spec.names())


def factor_devices(n: int, want: Sequence[str]) -> Dict[str, int]:
    """Greedily factor ``n`` devices over the requested axis names.

    Each axis gets the smallest prime factor still available (so e.g.
    n=8, want=(dp, tp, sp) -> {dp: 2, tp: 2, sp: 2}); leftover factors fold
    into the first axis. Axes that can't get a factor >1 get size 1.
    """
    sizes = {name: 1 for name in want}
    rem = n
    for name in want:
        for f in (2, 3, 5, 7):
            if rem % f == 0:
                sizes[name] = f
                rem //= f
                break
    if rem > 1 and want:
        sizes[want[0]] *= rem
    return sizes


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for `constrain` calls in this thread."""
    prev = getattr(_local, "mesh", None)
    _local.mesh = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _local.mesh = prev


def current_mesh() -> Optional[Mesh]:
    return getattr(_local, "mesh", None)


UNCONSTRAINED = P.UNCONSTRAINED


def constrain(x, *spec_parts):
    """``with_sharding_constraint`` that degrades to a no-op.

    Spec-part semantics per dim:

    - an axis name (or tuple of names): shard over those mesh axes;
    - ``None``: explicitly REPLICATED over all mesh axes;
    - ``UNCONSTRAINED``: leave the dim's layout to GSPMD (use this for
      batch/sequence dims so a tp constraint never un-shards dp/sp).

    Degrades: with no active mesh the call is a no-op; axis names missing
    from the active mesh become UNCONSTRAINED (not replicated), so
    tensor-parallel layers run unchanged on a single chip or a pure-dp
    mesh; if after degradation every dim is UNCONSTRAINED, no constraint
    is emitted at all.
    """
    mesh = getattr(_local, "mesh", None)
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def keep(part):
        if part is None or part is UNCONSTRAINED:
            return part
        if isinstance(part, (tuple, list)):
            kept = tuple(p for p in part if p in names)
            return kept if kept else UNCONSTRAINED
        return part if part in names else UNCONSTRAINED

    cleaned = [keep(p) for p in spec_parts]
    if all(c is UNCONSTRAINED for c in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*cleaned)))


def tree_shardings(mesh: Mesh, tree, pspecs=None):
    """Expand a SPARSE PartitionSpec tree into a full ``NamedSharding``
    tree mirroring ``tree``.

    ``pspecs`` follows ``Module.param_pspecs()`` conventions: nested dicts
    holding ``PartitionSpec`` leaves for the annotated parameters only.
    Every leaf of ``tree`` with no spec (missing key, or ``pspecs=None``)
    gets ``P()`` — explicitly REPLICATED over the whole mesh, the safe
    default for embeddings / norms / biases. The result is what
    ``jax.device_put(tree, tree_shardings(...))`` and a reload both need:
    one sharding per leaf, structurally identical to the value tree.
    """
    def walk(node, spec, path):
        if isinstance(node, dict):
            if spec is not None and not isinstance(spec, dict):
                # a P() attached to a SUBTREE would otherwise silently
                # replicate every leaf under it — a memory/perf regression
                # with no symptom; specs apply to leaves (or tuple nodes)
                raise ValueError(
                    f"pspec at {'/'.join(path) or '<root>'} is "
                    f"{spec!r} but the params tree has a dict there; "
                    f"attach PartitionSpecs to leaves")
            sub = spec or {}
            extra = set(sub) - set(node)
            if extra:
                raise ValueError(
                    f"pspec keys {sorted(extra)} at "
                    f"{'/'.join(path) or '<root>'} match no parameter")
            return {k: walk(v, sub.get(k), path + (k,))
                    for k, v in node.items()}
        if isinstance(node, (list, tuple)) and not isinstance(node, P):
            if isinstance(spec, P) or spec is None:
                sub = [spec] * len(node)  # one spec covers homogeneous kids
            else:
                sub = list(spec)
                if len(sub) != len(node):
                    raise ValueError(
                        f"pspec list at {'/'.join(path) or '<root>'} has "
                        f"{len(sub)} entries for {len(node)} children")
            out = [walk(v, s, path + (str(i),))
                   for i, (v, s) in enumerate(zip(node, sub))]
            return type(node)(out)
        return NamedSharding(mesh, spec if spec is not None else P())

    return walk(tree, pspecs, ())


def shard_tree(mesh: Mesh, tree, pspecs=None):
    """``(sharded tree, sharding tree)``: place every leaf of ``tree``
    per the sparse ``pspecs`` (unannotated leaves replicated). The
    returned sharding tree is the reload contract — hot-swapped weights
    must be ``device_put`` with exactly these shardings or the jitted
    step would miss its executable cache."""
    shardings = tree_shardings(mesh, tree, pspecs)
    return jax.device_put(tree, shardings), shardings


def axis_size(mesh: Mesh, axis: str) -> int:
    """Size of named ``axis`` in ``mesh`` (1 when absent — the degraded
    single-chip case every tp layer must tolerate)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(sizes.get(axis, 1))


def serving_meshes(n_replicas: int, tp: int = 1, *, axis: str = "tp",
                   devices=None):
    """``n_replicas`` disjoint single-axis meshes of ``tp`` devices each —
    the replica-group topology for sharded + replicated serving: every
    replica runs its tensor-parallel engine on its own device set, so one
    replica's death or reload never touches a sibling's chips.

    Raises when ``n_replicas * tp`` exceeds the available devices
    (serving replicas must not share chips; for CPU tests use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    if n_replicas < 1 or tp < 1:
        raise ValueError("n_replicas and tp must be >= 1")
    devices = list(devices if devices is not None else jax.devices())
    need = n_replicas * tp
    if need > len(devices):
        raise ValueError(
            f"{n_replicas} replicas x tp={tp} needs {need} devices, have "
            f"{len(devices)} (CPU: set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need})")
    return [make_mesh(MeshSpec(**{axis: tp}), devices[i * tp:(i + 1) * tp])
            for i in range(n_replicas)]


def ring_perm(n: int):
    """Neighbor permutation for ``lax.ppermute`` ring shifts."""
    return [(i, (i + 1) % n) for i in range(n)]
