"""Host-infeed roofline, stage by stage.

Measures each stage of the feed path separately on the machine it runs on
and checks that the end-to-end overlapped pipeline achieves ~min(stage
rates) — i.e., that the double-buffered ``device_prefetch`` genuinely
overlaps and the observed number is the bottleneck stage, not a pipeline
defect.

Stages (ImageNet-shape b128 uint8 NCHW batches, 0.147 MB/image):
  1. produce   — TensorDataSet sliced fast path, host only
  2. stage     — same through the host_prefetch background thread
  3. transfer  — jax.device_put bandwidth, batch-sized payloads
  4. compute   — resident-batch train-step rate (from bench.py, given)
  5. end2end   — bench.py's run_host_pipeline (device_prefetch overlap)

Also measures a transform-chain produce rate (pad-4 crop augmentation)
as the decode/augment analogue for the host-CPU side of the roofline —
single-thread AND through the round-6 parallel transformer pool
(``BIGDL_POOL_WORKERS``, default 4) — with every host stage counted
through the shared ``PipelineStats`` plumbing (the same counters
``bench.py --mode pipeline`` and the optimizer's step metrics report),
so the artifact carries queue occupancy / stall / starve alongside the
rates.

Appends to perf/artifacts/feeder_roofline.txt.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ART = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "artifacts", "feeder_roofline.txt")


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.parallel_pipeline import PipelineStats
    from bigdl_tpu.dataset.prefetch import host_prefetch

    stats = PipelineStats()
    out = []

    def emit(s):
        print(s, flush=True)
        out.append(s)

    platform = jax.devices()[0].platform
    emit(f"=== r5 feeder roofline (platform={platform}, "
         f"{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}) ===")

    batch = 128
    n = 8 * batch
    x = (np.random.rand(n, 3, 224, 224) * 255).astype(np.uint8)
    y = np.random.randint(0, 1000, (n,)).astype(np.int32)
    img_mb = x[0].nbytes / 1e6

    # 1. produce: sliced fast path, host only
    ds = DataSet.tensors(x, y)
    it = ds.batches(batch, train=True)
    next(it)
    t0 = time.perf_counter()
    for _ in range(32):
        next(it)
    produce_rate = 32 * batch / (time.perf_counter() - t0)
    emit(f"1. produce (TensorDataSet slice):        {produce_rate:10.0f} img/s")

    # 1b. augmentation-chain produce (decode/augment analogue):
    # per-sample pad-4 random crop on 224x224 uint8, Python-side
    from bigdl_tpu.core.rng import RandomGenerator
    from bigdl_tpu.dataset.image import RandomCropper

    elems = [(x[i], int(y[i])) for i in range(256)]
    crop = RandomCropper(224, 224, pad=4, rng=RandomGenerator(3))

    def aug_iter():
        while True:
            yield from crop.apply(iter(elems))

    ait = aug_iter()
    next(ait)
    t0 = time.perf_counter()
    for _ in range(512):
        next(ait)
    aug_rate = 512 / (time.perf_counter() - t0)
    emit(f"1b. augment chain (pad4 crop, 1 thread): {aug_rate:10.0f} img/s")

    # 1c. the same chain through the parallel transformer pool (round 6):
    # on a TPU-VM host this is the stage that must out-run the chip
    def raw_iter():
        while True:
            yield from elems

    n_workers = int(os.environ.get("BIGDL_POOL_WORKERS", "4"))
    pool_chain = crop.parallel(n_workers, chunk=8, base_seed=3, stats=stats)
    pit = pool_chain.apply(raw_iter())
    for _ in range(2 * n_workers * 2 * 8):  # warm past the pool buffers
        next(pit)
    t0 = time.perf_counter()
    for _ in range(1024):
        next(pit)
    pool_rate = 1024 / (time.perf_counter() - t0)
    pit.close()
    emit(f"1c. augment pool  (pad4 crop, x{n_workers}):     "
         f"{pool_rate:10.0f} img/s ({pool_rate / aug_rate:.2f}x 1-thread)")

    # 2. stage: through the host_prefetch thread (stats-instrumented)
    it = host_prefetch(ds.batches(batch, train=True), depth=4, stats=stats)
    next(it)
    t0 = time.perf_counter()
    for _ in range(32):
        next(it)
    stage_rate = 32 * batch / (time.perf_counter() - t0)
    it.close()
    emit(f"2. stage (host_prefetch thread):         {stage_rate:10.0f} img/s")

    # 3. transfer: device_put bandwidth at batch size. Measured BEFORE
    # and (below) AFTER the end-to-end leg, so a swing in host->device
    # bandwidth between sub-windows cannot mis-attribute the ratio.
    probe = x[:batch]
    fetch = jax.jit(lambda a: jnp.float32(a).sum())
    float(fetch(jax.device_put(probe)))

    def xfer_probe():
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            float(fetch(jax.device_put(probe)))
            best = min(best, time.perf_counter() - t0)
        return probe.nbytes / best / 1e6

    xfer_mbps = xfer_probe()
    xfer_rate = xfer_mbps / img_mb
    emit(f"3. transfer before e2e (device_put b{batch}): {xfer_rate:8.0f} img/s "
         f"({xfer_mbps:.1f} MB/s)")

    # 5. end2end: bench.py's overlapped host pipeline (includes compute)
    from bench import run_host_pipeline
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD

    on_tpu = platform == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    model = resnet.build_imagenet(50, 1000,
                                  kernel_format="HWIO" if on_tpu else "OIHW")
    e2e = run_host_pipeline(model, CrossEntropyCriterion(),
                            SGD(learning_rate=0.1, momentum=0.9),
                            batch, 24, dtype)
    emit(f"5. end-to-end overlapped host pipeline:  {e2e:10.0f} img/s")
    xfer_mbps2 = xfer_probe()
    xfer_rate2 = xfer_mbps2 / img_mb
    emit(f"3b. transfer after e2e:                  {xfer_rate2:10.0f} img/s "
         f"({xfer_mbps2:.1f} MB/s)")

    bound = min(produce_rate, stage_rate, (xfer_rate + xfer_rate2) / 2)
    emit(f"   bottleneck bound = min(1,2,3) =       {bound:10.0f} img/s")
    emit(f"   end2end / bound ratio: {e2e / bound:.2f}  -> >=0.8 means the "
         f"double-buffered pipeline really overlaps; the observed number "
         f"IS the bottleneck stage, not pipeline overhead")
    emit("   host augment/decode: "
         f"~{aug_rate:.0f} img/s/thread measured here; the parallel "
         f"transformer pool (1c: x{n_workers} -> {pool_rate:.0f} img/s on "
         f"this host's {os.cpu_count()} core(s)) is the TPU-native "
         "MTLabeledBGRImgToBatch.")
    emit("   per-stage pipeline stats (shared plumbing with bench.py "
         "--mode pipeline):")
    for line in stats.format_table().splitlines():
        emit("     " + line)
    with open(ART, "a") as f:
        f.write("\n".join(out) + "\n\n")


if __name__ == "__main__":
    main()
