"""Async-collective / latency-hiding flag experiment (VERDICT r3 item 5).

The round-3 AOT schedule (perf/overlap_probe.py) showed ONE bucketed
102 MB gradient all-reduce, synchronous, after backward. The reference
*implements* layer-wise overlap (``ParallelOptimizer.scala:481``,
``DistriParameterSynchronizer.scala:66``); XLA gates the equivalent —
async conversion + latency-hiding placement — behind TPU compiler flags.

This experiment tries every channel this environment has for reaching
those flags on the v5e:2x2x1 AOT pipeline:

1. ``compiler_options`` on ``lowered.compile()`` — goes straight to the
   TPU compiler, bypassing host XLA_FLAGS parsing (the channel that
   crashed in rounds 2-3).
2. ``XLA_FLAGS`` env in a fresh subprocess — expected host-hostile;
   captured verbatim either way.

For each configuration that compiles, the final schedule is scanned for
``all-reduce-start``/``-done`` pairs and the count of compute
(fusion/convolution/dot) instructions placed inside each window — >0
means the collective is genuinely overlapped with backward compute.

Appends an "async attempt" section to perf/artifacts/overlap_async.txt.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from overlap_probe import analyze, build_step  # noqa: E402


CONFIGS = [
    ("baseline", {}),
    ("async_cf", {
        "xla_tpu_enable_async_collective_fusion": "true",
        "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": "true",
    }),
    ("async_cf+lhs", {
        "xla_tpu_enable_async_collective_fusion": "true",
        "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": "true",
        "xla_tpu_enable_latency_hiding_scheduler": "true",
    }),
    ("async_ar_only", {
        "xla_enable_async_all_reduce": "true",
    }),
]


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2x1")
    devs = topo.devices
    mesh = Mesh(np.asarray(devs).reshape(len(devs)), ("dp",))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("dp"))

    step, params, mstate, ostate = build_step()
    batch = 32 * len(devs)

    def shaped(tree, sh):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=sh),
            tree)

    args = (shaped(params, repl), shaped(mstate, repl), shaped(ostate, repl),
            jax.ShapeDtypeStruct((batch, 3, 224, 224), jnp.bfloat16,
                                 sharding=data),
            jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=data))
    lowered = jax.jit(step, out_shardings=(repl, repl, repl, repl)).lower(*args)

    report = []
    for name, opts in CONFIGS:
        try:
            compiled = lowered.compile(compiler_options=opts) if opts \
                else lowered.compile()
            txt = compiled.as_text()
            pairs, sync, biggest = analyze(txt)
            overl = [p for p in pairs if p[2] > 0]
            line = (f"{name:16s} OK: async pairs={len(pairs)} "
                    f"(overlapped={len(overl)}, compute-in-windows="
                    f"{sum(p[2] for p in pairs)}), sync collectives={sync} "
                    f"(largest {biggest / 1e6:.1f} MB)")
            report.append(line)
            print(line, flush=True)
            for pname, dist, between in sorted(pairs, key=lambda p: -p[2])[:8]:
                detail = (f"    {pname[:56]:56s} sched-dist={dist:5d} "
                          f"compute-between={between}")
                report.append(detail)
                print(detail, flush=True)
            if name != "baseline" and opts:
                with open(f"/tmp/overlap_hlo_{name}.txt", "w") as f:
                    f.write(txt)
        except Exception as e:
            msg = str(e).replace("\n", " ")[:500]
            line = f"{name:16s} FAILED: {type(e).__name__}: {msg}"
            report.append(line)
            print(line, flush=True)
    return report


if __name__ == "__main__":
    sys.path.insert(0, "/root/repo/perf")
    main()
