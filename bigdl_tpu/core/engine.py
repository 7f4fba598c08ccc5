"""Execution engine: device topology and mesh management.

The reference's ``Engine`` (``DL/utils/Engine.scala:41``) holds global
node/core topology (``coreNumber()``, ``nodeNumber()``), an engine-type enum
(MklBlas/MklDnn) and thread pools used for intra-node model replicas. On TPU
all of that collapses into a ``jax.sharding.Mesh``: one XLA program per chip,
intra-chip parallelism handled by the compiler, inter-chip parallelism by
collectives over ICI/DCN. ``Engine`` here owns mesh construction and the
default sharding axes.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.core.config import EngineConfig

log = logging.getLogger("bigdl_tpu")


def enable_compile_cache() -> Optional[str]:
    """Keep compiled programs across processes; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set JAX reads it and nothing is set in code. Otherwise the cache
    lives at ``<checkout>/.jax_cache`` — a FIXED path (a directory that
    moves never hits). The thresholds drop to zero so the serving
    engine's small steps are kept too, not only the minute-long train
    step. A process held to the CPU (``JAX_PLATFORMS=cpu``) gets no cache
    and ``None``: XLA:CPU compiles in seconds and reloads cached code with
    a page of machine-feature warnings per program."""
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class Engine:
    """Singleton-ish engine (reference: ``Engine.init``, ``Engine.scala:106``).

    Unlike the reference there is no node/core bookkeeping: ``node_number``
    maps to ``jax.process_count()`` and ``core_number`` to
    ``jax.local_device_count()``.
    """

    _lock = threading.Lock()
    _instance: Optional["Engine"] = None

    def __init__(self, config: Optional[EngineConfig] = None):
        self.config = config or EngineConfig()
        self._mesh: Optional[Mesh] = None

    # ---- topology (reference: Engine.nodeNumber/coreNumber) ----
    @staticmethod
    def node_number() -> int:
        return jax.process_count()

    @staticmethod
    def core_number() -> int:
        return jax.local_device_count()

    @staticmethod
    def device_count() -> int:
        return jax.device_count()

    # ---- init / singleton ----
    @classmethod
    def init(cls, config: Optional[EngineConfig] = None) -> "Engine":
        enable_compile_cache()
        with cls._lock:
            if cls._instance is None or config is not None:
                cls._instance = Engine(config)
            return cls._instance

    @classmethod
    def get(cls) -> "Engine":
        return cls.init()

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._instance = None

    @classmethod
    def init_multihost(cls, coordinator_address: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None,
                       config: Optional[EngineConfig] = None) -> "Engine":
        """Multi-host initialization (the reference's cluster entry:
        ``Engine.init(nodeNumber, coreNumber, onSpark=true)``,
        ``Engine.scala:106``).

        Wraps ``jax.distributed.initialize`` — each host process calls
        this before any other JAX use; afterwards ``jax.devices()`` spans
        the whole slice and every mesh built by this Engine covers all
        hosts, with XLA routing collectives over ICI within a slice and
        DCN across slices. On Cloud TPU the three arguments are
        auto-detected from the metadata server; pass them explicitly for
        manual clusters (coordinator ``host:port``, world size, rank).
        """
        import os

        # IMPORTANT: decide whether to initialize WITHOUT touching any
        # jax backend API — jax.distributed.initialize must run before
        # the backend is created. Distributed init engages when the
        # caller passed explicit topology args OR a cluster environment
        # is detectable; a plain single-process call is an ordinary init.
        explicit = any(a is not None
                       for a in (coordinator_address, num_processes, process_id))
        # TPU_WORKER_HOSTNAMES is set even on single-host TPU-VMs: only a
        # multi-entry list means a real multi-host slice
        cluster_env = (
            os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS")
            or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
            or "," in os.environ.get("TPU_WORKER_HOSTNAMES", ""))
        if explicit or cluster_env:
            kwargs = {}
            if coordinator_address is not None:
                kwargs["coordinator_address"] = coordinator_address
            if num_processes is not None:
                kwargs["num_processes"] = num_processes
            if process_id is not None:
                kwargs["process_id"] = process_id
            jax.distributed.initialize(**kwargs)
        return cls.init(config)

    # ---- mesh ----
    def mesh(self, mesh_shape: Optional[Sequence[Tuple[str, int]]] = None) -> Mesh:
        """Build (and cache) the device mesh.

        Default: all devices on the data-parallel axis — the TPU-native
        equivalent of the reference's one-model-replica-per-core data
        parallelism (``DistriOptimizer.initThreadModels``,
        ``DL/optim/DistriOptimizer.scala:564-567``).
        """
        shape = tuple(mesh_shape or self.config.mesh_shape or ((self.config.dp_axis, jax.device_count()),))
        if self._mesh is not None and tuple(zip(self._mesh.axis_names, self._mesh.devices.shape)) == shape:
            return self._mesh
        names = tuple(n for n, _ in shape)
        sizes = tuple(s for _, s in shape)
        n = int(np.prod(sizes))
        if n > jax.device_count():
            raise ValueError(
                f"mesh {dict(shape)} needs {n} devices, only {jax.device_count()} available"
            )
        devices = np.asarray(jax.devices()[:n]).reshape(sizes)
        self._mesh = Mesh(devices, names)
        return self._mesh

    def data_sharding(self, mesh: Optional[Mesh] = None) -> NamedSharding:
        """Batch-dimension sharding over the dp axis."""
        mesh = mesh or self.mesh()
        return NamedSharding(mesh, P(self.config.dp_axis))

    def replicated_sharding(self, mesh: Optional[Mesh] = None) -> NamedSharding:
        mesh = mesh or self.mesh()
        return NamedSharding(mesh, P())
