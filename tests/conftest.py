"""Test harness: force a virtual 8-device CPU platform before JAX import.

Mirrors the reference's trick of simulating multi-node behavior with Spark
``local[N]`` masters inside specs (``DLT/optim/DistriOptimizerSpec.scala:139``)
— here N virtual XLA host devices stand in for N TPU chips so mesh/sharding
code paths run without hardware.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
# the persistent compile cache stays off under test: thousands of tiny
# entries cost more than they save, and a described-topology compile
# (test_chip_compile.py) writes entries no CPU process can read back
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

# Concurrency sanitizers (graftlint's dynamic half): installed AFTER the
# jax import so jax-internal locks stay untracked, BEFORE any bigdl_tpu
# module allocates a lock.  The two autouse fixtures re-exported here run
# the per-test lock-order-cycle and leaked-thread checks.
import _sanitizers  # noqa: E402

_sanitizers.install()

from _sanitizers import (  # noqa: E402,F401
    _leaked_thread_sanitizer,
    _lock_order_sanitizer,
)


@pytest.fixture
def rng():
    return jax.random.key(0)


@pytest.fixture(autouse=True)
def _reset_engine():
    from bigdl_tpu.core.engine import Engine

    Engine.reset()
    yield
    Engine.reset()


@pytest.fixture(autouse=True)
def _reset_faults():
    # the fault injector is process-global by design; a site left armed
    # by one test must never fire inside another
    from bigdl_tpu import faults

    faults.reset()
    yield
    faults.reset()
