"""Headline benchmark: ResNet-50 ImageNet-shape training throughput.

Mirrors the reference's perf harnesses (`DistriOptimizerPerf` /
`LocalOptimizerPerf`, ``DL/models/utils/DistriOptimizerPerf.scala:82`` —
dummy-data throughput, canonical metric the driver "Throughput is N
records/second" line, ``DistriOptimizer.scala:410-417``).

Measurement methodology: warm up (compile + first steps), then the host
clock around windows of donated train steps, each window ending in
``block_until_ready``; the median window is reported. MFU is against the
published peak of the chip (``SPEC_PEAK``, keyed by ``device_kind``); a
device that is not in the table is an error. The default (train) mode
measures a TPU and fails without one. Sanity checks: first-step loss must
be ~ln(class_num) (the model computes a real cross-entropy before we time
it) and 0 < MFU <= 1.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import argparse
import json
import math
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

def measure_peak_flops(dtype=jnp.bfloat16, n=4096, short=128, long=512):
    """Empirical peak FLOP/s: dependency-chained n x n matmuls, differential.

    The differential is taken per-rep and the MEDIAN is reported — a single
    contaminated short run would otherwise report an impossibly high peak.
    """
    w = (jax.random.normal(jax.random.key(1), (n, n), jnp.float32) / np.sqrt(n)).astype(dtype)
    x = (jax.random.normal(jax.random.key(2), (n, n), jnp.float32) / np.sqrt(n)).astype(dtype)

    def chain(iters):
        @jax.jit
        def f(x, w):
            y = jax.lax.fori_loop(0, iters, lambda i, x: jnp.dot(x, w), x)
            return jnp.float32(y).sum()

        return f

    f_short, f_long = chain(short), chain(long)
    float(f_short(x, w)); float(f_long(x, w))  # compile
    peaks = []
    for _ in range(5):
        t0 = time.perf_counter(); float(f_short(x, w)); ts = time.perf_counter() - t0
        t0 = time.perf_counter(); float(f_long(x, w)); tl = time.perf_counter() - t0
        peaks.append(2 * n**3 * (long - short) / (tl - ts))
    return float(np.median(peaks))


def measure_peak_int8_flops(n=4096, short=128, long=512):
    """Empirical peak int8 OP/s: dependency-chained s8 x s8 -> s32
    ``dot_general`` (the MXU's native int8 path — round 5 measured
    ~1.9x the bf16 peak). The int32 accumulator is renarrowed to int8
    between links with a shift+cast (cheap VPU work that preserves the
    data dependency; no float rescale, so the chain stays integer).
    Same differential-median scheme as ``measure_peak_flops`` — the lm
    bench divides the int8 leg's MFU by THIS peak, never the float one
    (an int8 dot over the bf16 denominator would report MFU > 1)."""
    rs = np.random.RandomState(3)
    w = jnp.asarray(rs.randint(-127, 128, (n, n)), jnp.int8)
    x = jnp.asarray(rs.randint(-127, 128, (n, n)), jnp.int8)

    def chain(iters):
        @jax.jit
        def f(x, w):
            def link(i, x):
                acc = jax.lax.dot_general(
                    x, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                return (acc >> 8).astype(jnp.int8)

            y = jax.lax.fori_loop(0, iters, link, x)
            return jnp.int32(y).sum()

        return f

    f_short, f_long = chain(short), chain(long)
    int(f_short(x, w)); int(f_long(x, w))  # compile
    peaks = []
    for _ in range(5):
        t0 = time.perf_counter(); int(f_short(x, w)); ts = time.perf_counter() - t0
        t0 = time.perf_counter(); int(f_long(x, w)); tl = time.perf_counter() - t0
        peaks.append(2 * n**3 * (long - short) / (tl - ts))
    return float(np.median(peaks))


# Published per-chip peaks keyed by ``device_kind``, the MFU denominator.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s.
SPEC_PEAK = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def spec_peak(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unlisted device is an
    error, never a default."""
    try:
        return SPEC_PEAK[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench.py has no published peak for device_kind "
            f"{device_kind!r} (known: {sorted(SPEC_PEAK)}); add it to "
            "SPEC_PEAK with its source") from None


def build_step(model, criterion, method):
    """One jittable train step: fwd + bwd + SGD update."""

    def step(carry, batch_xy):
        params, mstate, ostate = carry
        x, y = batch_xy

        def loss_fn(p):
            out, new_ms = model.apply(p, x, state=mstate, training=True)
            return criterion.forward(out.astype(jnp.float32), y), new_ms

        (loss, new_ms), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_p, new_os = method.update(grads, params, ostate, jnp.int32(1))
        return (new_p, new_ms, new_os), loss

    return step


def run_host_pipeline(model, criterion, method, batch, n_iters, compute_dtype,
                      chunk=1):
    """Measured data->device training throughput: batches come from the
    host input pipeline (TensorDataSet sliced fast path + background
    feeder thread + async device_put), NOT a resident device batch.

    ``chunk`` superbatches the infeed: ONE device_put and ONE scanned
    step dispatch per ``chunk`` batches (the reference's
    MTLabeledBGRImgToBatch amortizes per-batch overhead the same way).
    Default 1; the knob trades dispatch overhead against latency (not
    measured on the present chip)."""
    from bigdl_tpu.dataset.dataset import DataSet
    from bigdl_tpu.dataset.prefetch import device_prefetch

    n = 4 * batch * chunk
    # feed uint8 images and normalize ON DEVICE — 4x fewer host->device
    # bytes than fp32, exactly how the image pipeline feeds real training
    x = (np.random.rand(n, 3, 224, 224) * 255).astype(np.uint8)
    y = np.random.randint(0, 1000, (n,)).astype(np.int32)
    ds = DataSet.tensors(x, y)

    params, mstate = model.init(jax.random.key(0))
    ostate = method.init_state(params)
    step = build_step(model, criterion, method)

    @jax.jit
    def many(params, mstate, ostate, xs, ys):
        xs = (xs.astype(compute_dtype) - 127.0) / 128.0
        (p, ms, os), losses = jax.lax.scan(
            step, (params, mstate, ostate),
            (xs.reshape((chunk, batch) + xs.shape[1:]),
             ys.reshape((chunk, batch))))
        return p, ms, os, losses[-1]

    iters = max(2, n_iters // chunk)
    it = device_prefetch(ds.batches(batch * chunk, train=True), host_depth=4)
    t0 = None
    loss = None
    for i, (xb, yb) in enumerate(it):
        params, mstate, ostate, loss = many(params, mstate, ostate, xb, yb)
        if i == 0:
            jax.block_until_ready(loss)  # compile boundary: clock starts
            t0 = time.perf_counter()
        if i >= iters:
            break
    jax.block_until_ready(loss)
    return batch * chunk * iters / (time.perf_counter() - t0)


def _write_metrics_out(args, sources):
    """``--metrics-out PATH``: dump an ``obs.MetricsRegistry`` JSON
    ``collect()`` over everything this run touched — the machine-
    readable capture path behind the "columns bench.py grew in PRs 3-10
    but BENCH_r* never recorded" debt (CI uploads these from the smoke
    steps). ``sources`` maps registry names to metric sources (None
    entries skip); the process fault injector and flight recorder ride
    along in every mode."""
    path = getattr(args, "metrics_out", None)
    if not path:
        return
    from bigdl_tpu import faults
    from bigdl_tpu.obs import MetricsRegistry, flight_recorder, to_json

    reg = MetricsRegistry()
    for name, src in sources.items():
        if src is None:
            continue
        reg.register(name, src)
    reg.register("faults", faults.default())
    reg.register("flight_recorder", flight_recorder())
    with open(path, "w") as fh:
        fh.write(to_json(reg.collect(), indent=2) + "\n")
    print(f"metrics-out: wrote {path}", file=sys.stderr)


def _wait_until(pred, timeout, interval=0.05):
    """Deadline-bounded wait on a predicate over FOREIGN state (another
    object's gauges, a prober's side effects) that exposes no Condition
    to hook. Parks on an ``Event.wait`` slice per check instead of a
    bare sleep — interruptible, never waits past the deadline, and
    returns the predicate's final value."""
    gate = threading.Event()
    deadline = time.monotonic() + timeout
    while not pred():
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return bool(pred())
        gate.wait(min(interval, remaining))
    return True


def _join_threads(prefixes, timeout):
    """Join every live thread whose name starts with ``prefixes``, under
    one shared deadline — condition-woken (``join`` returns the instant
    the thread exits), so a clean drain costs no polling interval."""
    deadline = time.monotonic() + timeout
    for t in threading.enumerate():
        if t.name.startswith(prefixes) and t is not threading.main_thread():
            t.join(timeout=max(0.0, deadline - time.monotonic()))


def run_serving_bench(args):
    """Serving-tier benchmark: N client threads of single-image requests
    against ``bigdl_tpu.serving.InferenceService`` (dynamic batching).
    Reports requests/sec and client-observed latency percentiles at fixed
    concurrency — the BENCH serving column.

    Latency here is honest end-to-end (submit -> host-fetched row): the
    batcher's scatter forces a host fetch per batch, so dispatch overhead
    is part of every request's latency, as it would be for a real remote
    client. Throughput is wall-clock over completed requests."""
    import threading

    from bigdl_tpu.models import resnet
    from bigdl_tpu.serving import InferenceService

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    n_requests = args.requests or (256 if on_tpu else 32)
    concurrency = args.concurrency
    model = resnet.build_imagenet(50, 1000,
                                  kernel_format="HWIO" if on_tpu else "OIHW")
    params, mstate = model.init(jax.random.key(0))
    rs = np.random.RandomState(0)
    pool = (rs.rand(64, 3, 224, 224).astype(np.float32) - 0.5) * 2

    svc = InferenceService(
        model, params, mstate,
        max_batch_size=args.serve_max_batch,
        max_wait_ms=args.serve_max_wait_ms,
        max_queue=max(64, 4 * concurrency))
    svc.warmup(pool[0])  # all bucket shapes compiled before the clock starts

    def client(cid):
        # stride partition: exactly n_requests total, busy clients for the
        # whole run whatever the concurrency/requests ratio
        for i in range(cid, n_requests, concurrency):
            svc.predict(pool[i % len(pool)], timeout=600)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    svc.close()

    snap = svc.metrics.snapshot()
    lat = snap["latency_ms"] or {}
    _write_metrics_out(args, {"serving": svc.metrics})
    print(json.dumps({
        "metric": "resnet50_serving_requests_per_sec",
        "value": round(snap["served"] / wall, 2),
        "unit": "requests/sec",
        "vs_baseline": None,
        "concurrency": concurrency,
        "requests": n_requests,
        "max_batch_size": args.serve_max_batch,
        "max_wait_ms": args.serve_max_wait_ms,
        "p50_ms": lat.get("p50"),
        "p95_ms": lat.get("p95"),
        "p99_ms": lat.get("p99"),
        "forwards": snap["forwards"],
        "mean_batch_size": round(snap["mean_batch_size"], 2),
        "padding_waste": round(snap["padding_waste"], 4),
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "timing": "wall-clock end-to-end (scatter forces host fetch per batch)",
    }))


class _FixedCostKernels:
    """Paged-kernels wrapper adding a fixed per-call cost — stands in
    for a real chip's step time on CPU smoke runs, exactly like the test
    suite's slow-kernels shim: the replicated-vs-single gate measures the
    SCHEDULING/PLACEMENT win (replica loops step concurrently), which a
    microsecond-fast CPU step would drown in Python bookkeeping and a
    1-core runner could not otherwise show. Both sides of the comparison
    run the same cost, so the ratio is honest."""

    def __init__(self, inner, step_sleep_s, prompt_sleep_s=None):
        self.inner = inner
        self.step_sleep_s = float(step_sleep_s)
        self.prompt_sleep_s = (self.step_sleep_s if prompt_sleep_s is None
                               else float(prompt_sleep_s))
        self.cache_sharding = getattr(inner, "cache_sharding", None)

    def prefill(self, *a, **kw):
        time.sleep(self.prompt_sleep_s)
        return self.inner.prefill(*a, **kw)

    def chunk(self, *a, **kw):
        time.sleep(self.prompt_sleep_s)
        return self.inner.chunk(*a, **kw)

    def decode(self, *a, **kw):
        time.sleep(self.step_sleep_s)
        return self.inner.decode(*a, **kw)

    @property
    def prefill_traces(self):
        return self.inner.prefill_traces

    @property
    def chunk_traces(self):
        return self.inner.chunk_traces

    @property
    def decode_traces(self):
        return self.inner.decode_traces


class _FixedCostSpecKernels:
    """Speculative-kernels wrapper with SEPARATE fixed per-call costs
    for the draft step and the target verify — the modeled cost ratio
    c_draft = draft_ms / target_ms is what the speculative speedup
    formula E[speedup] = (E[accepted] + 1) / (1 + (k+1) * c_draft)
    prices in, and a CPU smoke run cannot show it without modeling
    (both models' real CPU steps are microseconds apart). The prompt
    path (prefill/chunk/draft_write) runs UNPRICED on both legs —
    speculation targets the decode loop, and pricing two identical
    prefill paths only dilutes the measured ratio with constant
    time."""

    def __init__(self, inner, draft_sleep_s, target_sleep_s):
        self.inner = inner
        self.draft_sleep_s = float(draft_sleep_s)
        self.target_sleep_s = float(target_sleep_s)
        self.cache_sharding = getattr(inner, "cache_sharding", None)

    def prefill(self, *a, **kw):
        return self.inner.prefill(*a, **kw)

    def chunk(self, *a, **kw):
        return self.inner.chunk(*a, **kw)

    def draft_write(self, *a, **kw):
        return self.inner.draft_write(*a, **kw)

    def draft(self, *a, **kw):
        time.sleep(self.draft_sleep_s)
        return self.inner.draft(*a, **kw)

    def verify(self, *a, **kw):
        time.sleep(self.target_sleep_s)
        return self.inner.verify(*a, **kw)

    @property
    def prefill_traces(self):
        return self.inner.prefill_traces

    @property
    def chunk_traces(self):
        return self.inner.chunk_traces

    @property
    def draft_write_traces(self):
        return self.inner.draft_write_traces

    @property
    def draft_traces(self):
        return self.inner.draft_traces

    @property
    def verify_traces(self):
        return self.inner.verify_traces

    @property
    def decode_traces(self):
        return self.inner.decode_traces


class _LazyValue:
    """Device-future stand-in (PR 19): ``np.asarray`` on it blocks
    until a deadline set at dispatch, then yields the wrapped array —
    exactly how a jax device future behaves on a real accelerator
    (dispatch returns immediately, materialization waits for the
    step). ``_FixedCostKernels`` sleeps on the DISPATCHING thread,
    which would serialize the async scheduler's overlap window and
    make the A/B comparison measure nothing."""

    def __init__(self, value, ready_at):
        self._value = value
        self._ready_at = ready_at

    def __array__(self, dtype=None, copy=None):
        wait = self._ready_at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        arr = np.asarray(self._value)
        return arr.astype(dtype) if dtype is not None else arr


class _AsyncCostKernels:
    """Paged-kernels wrapper whose decode cost is paid at
    MATERIALIZATION, not dispatch — the modeled device for the
    async-scheduling column. ``decode`` returns immediately with its
    token/key outputs wrapped in :class:`_LazyValue` (ready at
    t_dispatch + step_cost); the cache result passes through unwrapped
    because it feeds back into the next jitted call. Both legs of the
    A/B run this same shim, so the ratio isolates the SCHEDULER: the
    sync loop materializes right after dispatch and pays step + host
    serially, the async loop does its host work under the in-flight
    step. Prompt kernels run unpriced — overlap targets the decode
    loop."""

    def __init__(self, inner, step_cost_s):
        self.inner = inner
        self.step_cost_s = float(step_cost_s)
        self.cache_sharding = getattr(inner, "cache_sharding", None)

    def prefill(self, *a, **kw):
        return self.inner.prefill(*a, **kw)

    def chunk(self, *a, **kw):
        return self.inner.chunk(*a, **kw)

    def decode(self, *a, **kw):
        ready_at = time.perf_counter() + self.step_cost_s
        toks, keys, cache = self.inner.decode(*a, **kw)
        return _LazyValue(toks, ready_at), _LazyValue(keys, ready_at), cache

    @property
    def prefill_traces(self):
        return self.inner.prefill_traces

    @property
    def chunk_traces(self):
        return self.inner.chunk_traces

    @property
    def decode_traces(self):
        return self.inner.decode_traces


def _bench_cache_sharding(mesh, kv_dtype_name):
    """Cache sharding for a sharded bench engine: pages on the heads
    axis, plus the replicated scale-pool sharding when KV is int8 (the
    engine's exact-match check requires the pair)."""
    from jax.sharding import NamedSharding

    from bigdl_tpu.parallel import kv_cache_pspec, kv_scale_pspec

    cs = NamedSharding(mesh, kv_cache_pspec())
    if kv_dtype_name == "int8":
        return (cs, NamedSharding(mesh, kv_scale_pspec()))
    return cs


def run_generation_bench(args):
    """Generation serving benchmark: continuous batching
    (``serving.GenerationEngine``) vs run-to-completion static batching
    (``serving.static_generate``) over the SAME jitted prefill/decode
    kernels, on a mixed-length workload — the BENCH generation column.

    The workload alternates short and long generations, which is the
    shape that kills static batching: every short sequence idles its
    slot until the longest in its batch finishes, while the engine
    retires it and admits the next prompt between decode steps. The win
    is scheduling (slot occupancy), not parallelism, so the >= 1.5x
    ``--smoke`` gate holds even on a 1-core runner. Tokens/sec counts
    generated tokens only (prompt prefill tokens are reported
    separately via the metrics snapshot).

    PR 6: both schedulers run over the PAGED + sampling kernels
    (``PagedDecodeKernels`` — block-table KV cache, in-step sampling,
    chunked prefill). New columns: the CAPACITY comparison — at the
    KV-byte budget of ``slots`` dense lanes, how many concurrent
    sequences of a 4:1 short:long mix the page pool admits (measured by
    replaying admission through the real ``PagePool``; smoke gate
    >= 2x) — and ``--sample``, which runs the whole workload with
    temperature/top-k/top-p per request. Sampled streams derive their
    seed from the request, so continuous and static MUST still produce
    identical tokens (the mismatch gate covers sampling too).

    PR 7 — sharded + replicated columns:

    - ``--tp K`` runs the ENGINE tensor-parallel over a K-device mesh
      (Megatron pspecs from ``parallel.tp``, KV pools sharded on heads)
      while the timed static baseline stays single-device, so the
      existing mismatch gate becomes the sharded-vs-single-device
      bit-identity check (the 1.5x scheduling gate then applies only at
      tp=1 — sharded and unsharded step times are not comparable on CPU);
    - ``--replicas R`` adds the scale-out column: R engines on disjoint
      device groups behind a ``ReplicaSet`` vs ONE engine fed the same
      total traffic at the same per-step cost (``--step-cost-ms``,
      default 8 ms under ``--smoke`` — see ``_FixedCostKernels``). The
      smoke gate requires replicated tokens/sec >= 1.5x single-replica,
      plus per-replica occupancy rows from each replica's own
      ``ServingMetrics``.

    PR 9 — the quantized tier: ``--kv-dtype int8`` stores KV pages int8
    with per-token fp32 scale pools and adds the capacity-at-fixed-BYTES
    column vs bf16 (replayed through the real allocator with
    ``paging.page_bytes`` pricing the scale overhead; smoke gate
    >= 1.8x); ``--quantize int8`` runs every GEMM as s8 x s8 -> s32
    with per-channel rescale. Both schedulers quantize identically, so
    the zero-mismatch gate covers the whole int8 tier — engine vs
    static, sharded vs single-device, greedy and sampled.

    PR 10 — ``--speculate K``: the draft-verified column. A speculative
    engine (K draft proposals per round, one target verify forward)
    runs the same workload as a plain paged engine at fixed per-model
    step costs (``--step-cost-ms`` prices the target, ``--draft-cost-ms``
    the draft — the modeled cost ratio of a distilled cheap draft).
    Gates under ``--smoke``: tokens/sec >= 1.5x plain at the modeled
    ratio, ZERO greedy mismatches (speculative greedy is lossless), and
    no kernel re-traces after warmup (acceptance lengths are data).
    Composes with ``--kv-dtype int8`` / ``--quantize int8``.

    PR 19 — ``--async-sched``: the async-scheduling A/B column. The
    same workload slice runs through a sync engine and an
    ``async_scheduling=True`` engine over a modeled device whose step
    cost is paid at MATERIALIZATION (``_AsyncCostKernels`` — dispatch
    returns immediately, exactly like real async dispatch), plus a
    fixed per-step host cost slept on the loop thread. Sync pays
    step + host serially; async folds the host share into the
    in-flight step's window. Gates under ``--smoke``: zero output
    mismatches (byte-exact streams), ``step_overlap_frac`` > 0.5,
    and async >= 1.2x sync tokens/sec at the 8 ms / 3 ms defaults."""
    from bigdl_tpu.nn.layers.attention import Transformer
    from bigdl_tpu.parallel import serving_meshes
    from bigdl_tpu.serving import (
        GenerationEngine,
        PagePool,
        PagedDecodeKernels,
        ReplicaSet,
        ServingMetrics,
        static_generate,
    )

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    smoke = args.smoke
    slots = args.serve_slots
    page_size = args.page_size
    kv_dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                "int8": "int8"}[args.kv_dtype]
    quantize = None if args.quantize == "none" else args.quantize
    # smoke/CPU: a model small enough to compile in seconds but large
    # enough that the jitted step dwarfs the loop's Python bookkeeping
    if on_tpu:
        model = Transformer(vocab_size=8192, hidden_size=512, num_heads=8,
                            filter_size=2048, num_hidden_layers=4)
        max_len, short_new, long_new = 256, 8, 96
    else:
        model = Transformer(vocab_size=256, hidden_size=160, num_heads=4,
                            filter_size=320, num_hidden_layers=2)
        max_len, short_new, long_new = 104, 3, 72
    max_prompt = 16
    params, _ = model.init(jax.random.key(0))
    kernels = PagedDecodeKernels(model)  # single-device triple: the
    # static baseline AND the identity reference for sharded runs
    mesh = None
    engine_kernels = kernels
    if args.tp > 1:
        if args.tp * max(1, args.replicas) > len(jax.devices()):
            raise SystemExit(
                f"--tp {args.tp} x --replicas {max(1, args.replicas)} needs "
                f"{args.tp * max(1, args.replicas)} devices, have "
                f"{len(jax.devices())} (CPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N)")
        mesh = serving_meshes(1, args.tp)[0]
        engine_kernels = PagedDecodeKernels(
            model, cache_sharding=_bench_cache_sharding(mesh, args.kv_dtype))

    rs = np.random.RandomState(0)
    n_requests = args.requests or 4 * slots
    requests = []
    for i in range(n_requests):
        plen = int(rs.randint(3, max_prompt + 1))
        prompt = rs.randint(1, 200 if not on_tpu else 8000, (plen,)).tolist()
        # 3:1 short:long — the production-shaped mix (most requests are
        # short, a tail is long). Every static group of `slots` catches a
        # long and idles its short slots for the whole tail, so the
        # deterministic step-count gap is ~3x and the 1.5x wall-clock
        # gate keeps a wide margin against scheduler jitter on shared
        # CI runners (a 50/50 mix measured 1.44-1.62x — too close).
        # Long positions alternate parity (3 then 6 per 8) so they do not
        # alias with 2-replica least-loaded placement — i % 4 == 3 put
        # every long at an odd submit index, i.e. ALL of them on one of
        # two replicas, and the replicated column measured placement skew
        # instead of throughput
        requests.append((prompt,
                         long_new if i % 8 in (3, 6) else short_new))
    sample_spec = (dict(temperature=0.8, top_k=40, top_p=0.95)
                   if args.sample else {})

    engine = GenerationEngine(
        model, params, max_slots=slots, max_len=max_len,
        max_prompt_len=max_prompt, max_queue=max(64, 2 * n_requests),
        kernels=engine_kernels, page_size=page_size, seed=0, mesh=mesh,
        cache_dtype=kv_dtype, quantize=quantize)
    engine.warmup()

    # continuous: submit everything, the engine packs slots between steps
    t0 = time.perf_counter()
    streams = [engine.submit(p, max_new_tokens=m, **sample_spec)
               for p, m in requests]
    outs = [s.result(timeout=600) for s in streams]
    cont_wall = time.perf_counter() - t0
    cont_tokens = sum(len(o) for o in outs)
    snap = engine.metrics.snapshot()
    engine.close()

    # static: same kernels, and the ENGINE's prompt buckets — otherwise a
    # workload whose longest prompt misses a bucket size would compile a
    # fresh prefill shape inside the timed static region
    t0 = time.perf_counter()
    souts, static_steps = static_generate(
        model, params, requests, max_slots=slots, max_len=max_len,
        kernels=kernels, prompt_buckets=engine.prompt_buckets,
        page_size=page_size, seed=0, cache_dtype=kv_dtype,
        quantize=quantize,
        sampling=[sample_spec] * n_requests if args.sample else None)
    static_wall = time.perf_counter() - t0
    static_tokens = sum(len(o) for o in souts)

    # capacity column: at the KV-byte budget of `slots` DENSE lanes, how
    # many concurrent sequences of a 4:1 short:long mix does the page
    # pool admit? Replayed through the real allocator (full reservation
    # at admission, exactly what the engine commits to). Byte math is
    # dtype-aware (paging.page_bytes): a page is priced in the ACTUAL
    # cache dtype including, for int8, its per-token fp32 scale rows —
    # capacity claims never assume pages are free to describe.
    from bigdl_tpu.serving.paging import page_bytes, pages_per_lane

    heads, head_dim = model.num_heads, model.hidden_size // model.num_heads

    def replay_capacity(n_pages):
        """Admissions of the 4:1 mix a pool of ``n_pages`` accepts —
        same deterministic request sequence for every dtype leg."""
        pool = PagePool(n_pages, page_size, max_len)
        cap_rs = np.random.RandomState(1)
        admitted = 0
        while True:
            plen = int(cap_rs.randint(3, max_prompt + 1))
            new = long_new if admitted % 5 == 4 else short_new
            need = pool.pages_for(min(plen + new - 1, max_len))
            if not pool.can_reserve(need):
                return admitted
            pool.alloc(need)
            admitted += 1

    ppn = pages_per_lane(max_len, page_size)
    run_page_bytes = page_bytes(
        page_size, heads, head_dim,
        "int8" if args.kv_dtype == "int8" else kv_dtype)
    # same-dtype ratio (the PR-6 paging win): budget = `slots` dense
    # lanes in the run's own dtype, so the byte width cancels and the
    # page-count replay is unchanged
    capacity_paged = replay_capacity(slots * ppn)
    capacity_ratio = capacity_paged / slots
    # int8-vs-bf16 at FIXED BYTES (the PR-9 compounding win): price a
    # bf16 dense-lane budget, ask how many pages each dtype fits —
    # scale pools included — and replay the same mix through both
    int8_fields = {}
    if args.kv_dtype == "int8":
        bf16_pb = page_bytes(page_size, heads, head_dim, jnp.bfloat16)
        int8_pb = page_bytes(page_size, heads, head_dim, "int8")
        budget_bytes = slots * ppn * bf16_pb
        # the bf16 leg's budget cancels to the dense page count
        # (budget_bytes // bf16_pb == slots * ppn), which is exactly the
        # replay capacity_paged already measured — reuse it
        cap_bf16 = capacity_paged
        cap_int8 = replay_capacity(budget_bytes // int8_pb)
        int8_fields = {
            "kv_budget_bytes_per_layer": budget_bytes,
            "capacity_bf16_seqs": cap_bf16,
            "capacity_int8_seqs": cap_int8,
            "capacity_int8_vs_bf16": round(cap_int8 / max(cap_bf16, 1), 3),
            "int8_scale_overhead": round(
                int8_pb / (bf16_pb / 2) - 1.0, 4),
        }

    # greedy decode is deterministic: both schedulers must produce the
    # SAME tokens — a throughput number from divergent outputs is bogus.
    # With --tp this is ALSO the sharded-vs-single-device identity check:
    # the engine ran tensor-parallel, the static baseline on one device.
    mismatches = sum(1 for a, b in zip(outs, souts) if a != b)

    # scale-out column: R replicas on disjoint device groups behind a
    # ReplicaSet vs ONE engine fed the same total traffic at the same
    # fixed per-step cost (sleeps overlap across replica loop threads;
    # compute overlaps across cores on multicore hosts)
    # default 8 ms: must comfortably dominate the ~1.5 ms CPU step +
    # Python bookkeeping, which CANNOT overlap on a 1-core runner — the
    # 2-replica wall-clock ceiling there is 2(s+c)/(s+2c), i.e. ~1.5x at
    # s=3ms but ~1.7x at s=8ms (multicore runners overlap c too and land
    # higher)
    step_cost_ms = args.step_cost_ms
    if step_cost_ms is None:
        step_cost_ms = 8.0 if (smoke and args.replicas > 1) else 0.0
    rep_fields = {}
    if args.replicas > 1:
        if args.tp > 1:
            rep_meshes = serving_meshes(args.replicas, args.tp)
        else:
            rep_meshes = [None] * args.replicas
        # the replicated column runs every engine at HALF the slots: a
        # replica only pays off once a single engine is capacity-bound
        # (that is why production adds replicas), and at `slots` lanes one
        # engine already fits every long generation of a wave concurrently
        # — the sequential decode critical path would cap the ratio at
        # ~1.3x however many replicas overlap. Same slots on both sides,
        # same total traffic, same per-step cost: the ratio isolates
        # placement + loop overlap.
        rep_slots = max(2, slots // 2)

        def build_replica(mesh_i):
            if mesh_i is None:
                kern = kernels  # share the compiled single-device triple
            else:
                kern = PagedDecodeKernels(
                    model,
                    cache_sharding=_bench_cache_sharding(mesh_i,
                                                         args.kv_dtype))
            if step_cost_ms > 0:
                kern = _FixedCostKernels(kern, step_cost_ms / 1e3)
            eng = GenerationEngine(
                model, params, max_slots=rep_slots, max_len=max_len,
                max_prompt_len=max_prompt, max_queue=max(64, 2 * n_requests),
                kernels=kern, page_size=page_size, seed=0, mesh=mesh_i,
                cache_dtype=kv_dtype, quantize=quantize,
                metrics=ServingMetrics())
            eng.warmup()
            return eng

        single = build_replica(rep_meshes[0])
        t0 = time.perf_counter()
        ss = [single.submit(p, max_new_tokens=m, **sample_spec)
              for p, m in requests]
        single_tokens = sum(len(s.result(timeout=600)) for s in ss)
        single_wall = time.perf_counter() - t0
        single.close()

        replicas = [build_replica(m_) for m_ in rep_meshes]
        rset = ReplicaSet(replicas, metrics=ServingMetrics(), name="bench")
        t0 = time.perf_counter()
        rstreams = [rset.submit(p, max_new_tokens=m, **sample_spec)
                    for p, m in requests]
        rep_tokens = sum(len(s.result(timeout=600)) for s in rstreams)
        rep_wall = time.perf_counter() - t0
        per_replica = {}
        for i, e in enumerate(replicas):
            rsnap = e.metrics.snapshot()
            per_replica[f"r{i}"] = {
                "served": rsnap["served"],
                "tokens_out": rsnap["tokens_out"],
                "slot_occupancy": round(rsnap["slot_occupancy"], 4),
            }
        rset.close()
        rep_tps = rep_tokens / rep_wall
        single_tps_c = single_tokens / single_wall
        rep_fields = {
            "replica_slots": rep_slots,
            "replicated_tokens_per_sec": round(rep_tps, 2),
            "single_replica_tokens_per_sec": round(single_tps_c, 2),
            "replicated_vs_single": round(rep_tps / single_tps_c, 3),
            "per_replica": per_replica,
        }

    # speculative column (PR 10): a draft-verified engine proposing
    # --speculate K tokens per round vs the plain paged engine on the
    # SAME workload at fixed per-model step costs. The draft runs the
    # target's own weights — the in-family acceptance upper bound,
    # standing in for a distilled draft — but is PRICED at the modeled
    # cheap-draft cost (--draft-cost-ms vs --step-cost-ms), which is
    # the ratio the speedup formula E[speedup] = (E[accepted] + 1) /
    # (1 + (k+1) * c_draft) actually depends on; the measured
    # acceptance rate is reported so the formula can be re-priced at
    # any draft quality. Both legs run GREEDY (speculative sampling is
    # keyed per output position, plain sampling per step — sampled
    # streams are deterministic within each scheme but not across
    # them), so the zero-mismatch gate is the lossless-greedy check.
    spec_fields = {}
    if args.speculate > 0:
        from bigdl_tpu.serving import SpeculativeKernels

        spec_k = args.speculate
        # 24 ms: the modeled costs must dominate the real CPU compute of
        # the tiny bench models (a few ms/call, and the speculative leg
        # makes k+1 more calls per round) or runner noise eats the ratio
        spec_target_ms = step_cost_ms if step_cost_ms > 0 else 24.0
        spec_draft_ms = args.draft_cost_ms

        plain = GenerationEngine(
            model, params, max_slots=slots, max_len=max_len,
            max_prompt_len=max_prompt, max_queue=max(64, 2 * n_requests),
            kernels=_FixedCostKernels(kernels, spec_target_ms / 1e3,
                                      prompt_sleep_s=0.0),
            page_size=page_size, seed=0, cache_dtype=kv_dtype,
            quantize=quantize, metrics=ServingMetrics())
        plain.warmup()
        t0 = time.perf_counter()
        ps = [plain.submit(p, max_new_tokens=m) for p, m in requests]
        plain_outs = [s.result(timeout=600) for s in ps]
        plain_wall = time.perf_counter() - t0
        plain_tokens = sum(len(o) for o in plain_outs)
        plain.close()

        skern = SpeculativeKernels(model, model)
        spec_eng = GenerationEngine(
            model, params, max_slots=slots, max_len=max_len,
            max_prompt_len=max_prompt, max_queue=max(64, 2 * n_requests),
            kernels=_FixedCostSpecKernels(skern, spec_draft_ms / 1e3,
                                          spec_target_ms / 1e3),
            page_size=page_size, seed=0, cache_dtype=kv_dtype,
            quantize=quantize, metrics=ServingMetrics(),
            speculate=(model, params, spec_k))
        spec_eng.warmup()
        warm_traces = (skern.draft_traces, skern.verify_traces,
                       skern.chunk_traces, skern.prefill_traces,
                       skern.draft_write_traces)
        t0 = time.perf_counter()
        ss = [spec_eng.submit(p, max_new_tokens=m) for p, m in requests]
        spec_outs = [s.result(timeout=600) for s in ss]
        spec_wall = time.perf_counter() - t0
        spec_tokens = sum(len(o) for o in spec_outs)
        spec_snap = spec_eng.metrics.snapshot()
        post_traces = (skern.draft_traces, skern.verify_traces,
                       skern.chunk_traces, skern.prefill_traces,
                       skern.draft_write_traces)
        spec_eng.close()

        spec_tps = spec_tokens / spec_wall
        plain_tps = plain_tokens / plain_wall
        spec_mismatches = sum(1 for a, b in zip(plain_outs, spec_outs)
                              if a != b)
        acc = spec_snap["acceptance_rate"]
        c_draft = spec_draft_ms / spec_target_ms
        spec_fields = {
            "speculate_k": spec_k,
            "speculative_tokens_per_sec": round(spec_tps, 2),
            "plain_tokens_per_sec": round(plain_tps, 2),
            "speculative_vs_plain": round(spec_tps / plain_tps, 3),
            "acceptance_rate": round(acc, 4),
            "verify_steps": spec_snap["verify_steps"],
            "draft_tokens": spec_snap["draft_tokens"],
            "accepted_tokens": spec_snap["accepted_tokens"],
            "spec_target_cost_ms": spec_target_ms,
            "spec_draft_cost_ms": spec_draft_ms,
            # the formula's prediction at the MEASURED acceptance and
            # the modeled cost ratio — decode-loop only, so the
            # measured end-to-end ratio (which also pays prefill)
            # should land at or below it
            "modeled_speedup": round(
                (acc * spec_k + 1) / (1 + (spec_k + 1) * c_draft), 3),
            "speculative_mismatches": spec_mismatches,
            "speculative_compile_once": warm_traces == post_traces,
        }

    # prefix-cache column (PR 12): replay the workload prefix caching
    # exists for — ONE shared system prompt (3 full pages) x N requests
    # with unique tails, arriving one after another (multi-turn /
    # templated traffic) — through a prefix-caching engine vs the same
    # engine cache-off. The first request publishes the prompt's pages
    # at retirement; every later one attaches them by reference and
    # prefills only its tail, so the gated wins are (a) >= 2x fewer
    # chunk/prefill kernel invocations and (b) a TTFT p50 reduction at
    # hit-rate >= 0.9. Prompt kernels carry a fixed modeled cost
    # (prefill is what the cache removes; the tiny CPU model's real
    # microseconds would drown the ratio in Python bookkeeping), decode
    # is unpriced on both legs, and greedy decode being deterministic
    # the zero-mismatch gate doubles as the cache-on-vs-off
    # bit-identity check.
    prefix_fields = {}
    prefix_cache_obj = None
    if args.prefix_cache:
        pfx_requests = args.requests or (16 if smoke else 32)
        sys_len = 3 * page_size
        hi = 200 if not on_tpu else 8000
        pfx_rs = np.random.RandomState(2)
        system = pfx_rs.randint(1, hi, (sys_len,)).tolist()
        pfx_prompts = [system + pfx_rs.randint(1, hi, (3,)).tolist()
                       for _ in range(pfx_requests)]
        pfx_new = short_new + 2
        prompt_cost_ms = 4.0

        def run_prefix_leg(enabled):
            eng = GenerationEngine(
                model, params, max_slots=slots, max_len=max_len,
                max_prompt_len=sys_len + 8,
                max_queue=max(64, 2 * pfx_requests),
                kernels=_FixedCostKernels(kernels, 0.0,
                                          prompt_cost_ms / 1e3),
                page_size=page_size, prefill_chunk=page_size, seed=0,
                cache_dtype=kv_dtype, quantize=quantize,
                metrics=ServingMetrics(), prefix_cache=enabled)
            eng.warmup()
            t0 = time.perf_counter()
            outs = [eng.submit(p, max_new_tokens=pfx_new,
                               **sample_spec).result(timeout=600)
                    for p in pfx_prompts]
            wall = time.perf_counter() - t0
            leg_snap = eng.metrics.snapshot()
            pcache = eng._prefix
            eng.close()
            return outs, leg_snap, wall, pcache

        off_outs, off_snap, off_wall, _ = run_prefix_leg(False)
        on_outs, on_snap, on_wall, prefix_cache_obj = run_prefix_leg(True)
        pfx_mismatches = sum(1 for a, b in zip(off_outs, on_outs)
                             if a != b)
        inv_off = off_snap["prefill_chunks"] + off_snap["prefills"]
        inv_on = on_snap["prefill_chunks"] + on_snap["prefills"]
        ttft_off = (off_snap["ttft_ms"] or {}).get("p50")
        ttft_on = (on_snap["ttft_ms"] or {}).get("p50")
        prefix_fields = {
            "prefix_requests": pfx_requests,
            "prefix_system_pages": sys_len // page_size,
            "prefix_hit_rate": round(on_snap["prefix_hit_rate"], 4),
            "prefix_hits": on_snap["prefix_hits"],
            "prefix_misses": on_snap["prefix_misses"],
            "prefix_prefill_invocations_off": inv_off,
            "prefix_prefill_invocations_on": inv_on,
            "prefix_invocation_reduction": round(
                inv_off / max(inv_on, 1), 3),
            "prefix_chunks_skipped": on_snap["prefill_chunks_skipped"],
            "prefix_ttft_p50_off_ms": ttft_off,
            "prefix_ttft_p50_on_ms": ttft_on,
            "prefix_ttft_reduction": round(ttft_off / ttft_on, 3)
            if ttft_off and ttft_on else None,
            "prefix_wall_off_s": round(off_wall, 3),
            "prefix_wall_on_s": round(on_wall, 3),
            "prefix_prompt_cost_ms": prompt_cost_ms,
            "prefix_mismatches": pfx_mismatches,
        }

    # disaggregation column (PR 15): the prompt-heavy interference
    # replay disaggregation exists for — a 1:1 short:long prompt mix
    # (long prompts chunk-prefill) through a monolithic engine vs the
    # DisaggregatedEngine at the SAME modeled costs. The monolithic
    # loop runs admitted prompt chunks BETWEEN decode steps, so every
    # in-flight stream's next token pays ~(step + chunking_slots x
    # chunk); the decode role never runs a prompt kernel, so its
    # inter-token latency stays ~step whatever the admission traffic.
    # The prompt cost is 2x the step cost (a chunk of prompt tokens is
    # strictly more work than one decode token), which is what makes
    # the mix "prompt-heavy" — the interference term dominates.
    # Gates under --smoke: decode ITL p99 <= 0.7x monolithic at equal
    # costs, ZERO output mismatches (the handoff must be bit-exact),
    # and both role pools drained.
    disagg_fields = {}
    disagg_metrics = None
    if args.disaggregate:
        from bigdl_tpu.serving import DisaggregatedEngine

        dz_requests = args.requests or (16 if smoke else 32)
        dz_step_ms = args.step_cost_ms if args.step_cost_ms else 4.0
        dz_prompt_ms = 2 * dz_step_ms
        dz_chunk = page_size
        dz_short, dz_long = 6, (5 * page_size) // 2   # 1 vs 3 chunks
        dz_new = 24
        hi = 200 if not on_tpu else 8000
        dz_rs = np.random.RandomState(4)
        dz_reqs = [dz_rs.randint(
            1, hi, (dz_long if i % 2 else dz_short,)).tolist()
            for i in range(dz_requests)]
        dz_kw = dict(max_slots=slots, max_len=max(max_len, dz_long + dz_new),
                     max_prompt_len=3 * page_size,
                     max_queue=max(64, 2 * dz_requests),
                     page_size=page_size, prefill_chunk=dz_chunk, seed=0,
                     cache_dtype=kv_dtype, quantize=quantize)

        dz_mono = GenerationEngine(
            model, params,
            kernels=_FixedCostKernels(kernels, dz_step_ms / 1e3,
                                      dz_prompt_ms / 1e3),
            metrics=ServingMetrics(), **dz_kw)
        dz_mono.warmup()
        t0 = time.perf_counter()
        ms = [dz_mono.submit(p, max_new_tokens=dz_new, **sample_spec)
              for p in dz_reqs]
        dz_mono_outs = [s.result(timeout=600) for s in ms]
        dz_mono_wall = time.perf_counter() - t0
        dz_mono_snap = dz_mono.metrics.snapshot()
        dz_mono.close()

        dz = DisaggregatedEngine(
            model, params,
            prefill_overrides={"kernels": _FixedCostKernels(
                kernels, 0.0, dz_prompt_ms / 1e3)},
            decode_overrides={"kernels": _FixedCostKernels(
                kernels, dz_step_ms / 1e3, 0.0)},
            metrics=ServingMetrics(), **dz_kw)
        dz.warmup()
        t0 = time.perf_counter()
        ds = [dz.submit(p, max_new_tokens=dz_new, **sample_spec)
              for p in dz_reqs]
        dz_outs = [s.result(timeout=600) for s in ds]
        dz_wall = time.perf_counter() - t0
        dz_snap = dz.metrics.snapshot()
        dz_pool = dz.decode_engine._pool.snapshot()
        dz_drained = (dz.prefill_engine.pages_in_use == 0
                      and dz.decode_engine.pages_in_use == 0)
        disagg_metrics = dz.metrics
        dz.close()

        dz_mismatches = sum(1 for a, b in zip(dz_mono_outs, dz_outs)
                            if a != b)
        mono_itl = dz_mono_snap["itl_ms"] or {}
        dz_itl = dz_snap["itl_ms"] or {}
        disagg_fields = {
            "disagg_requests": dz_requests,
            "disagg_step_cost_ms": dz_step_ms,
            "disagg_prompt_cost_ms": dz_prompt_ms,
            "disagg_prefill_chunk": dz_chunk,
            "mono_itl_p50_ms": mono_itl.get("p50"),
            "mono_itl_p99_ms": mono_itl.get("p99"),
            "disagg_itl_p50_ms": dz_itl.get("p50"),
            "disagg_itl_p99_ms": dz_itl.get("p99"),
            "disagg_itl_p99_vs_mono": (
                round(dz_itl["p99"] / mono_itl["p99"], 3)
                if dz_itl.get("p99") and mono_itl.get("p99") else None),
            "disagg_handoffs": dz_pool["pages_adopted"]
            + dz_pool["pages_adopt_shared"],
            "disagg_pages_adopted": dz_pool["pages_adopted"],
            "disagg_pages_drained": dz_drained,
            "disagg_mismatches": dz_mismatches,
            "mono_wall_s": round(dz_mono_wall, 3),
            "disagg_wall_s": round(dz_wall, 3),
        }

    # KV-tier column (PR 18): the working set the host tier exists for —
    # a prefix library ~10x the DEVICE pool (20 two-page families vs a
    # 4-page pool), replayed twice. Round one publishes each family and
    # the pool's LRU pressure evicts every one of them; with
    # --host-pages the evictions offload to the HostPageStore instead of
    # vanishing, so round two's revisits restore host->device and skip
    # their covered chunks, where the no-host leg re-prefills from
    # scratch. Prompt kernels carry the same fixed modeled cost as the
    # prefix leg; TTFT is measured client-side on the revisit round
    # only. Gates under --smoke: effective hit-rate > 0 where the
    # no-host leg scores ~0, restored-prefix TTFT p50 < full re-prefill
    # TTFT p50, ZERO mismatches between the legs, and both tiers
    # drained at close.
    host_fields = {}
    host_store_obj = None
    if args.host_pages > 0:
        kv_fams, kv_fam_pages = 20, 2
        kv_fam_len = kv_fam_pages * page_size
        kv_device_pages = 4          # one 3-page lane + 1 spare
        hi = 200 if not on_tpu else 8000
        kv_rs = np.random.RandomState(6)
        kv_families = [kv_rs.randint(1, hi, (kv_fam_len,)).tolist()
                       for _ in range(kv_fams)]
        kv_round1 = [f + kv_rs.randint(1, hi, (3,)).tolist()
                     for f in kv_families]
        kv_round2 = [f + kv_rs.randint(1, hi, (3,)).tolist()
                     for f in kv_families]
        kv_new = short_new + 2
        kv_prompt_cost_ms = 4.0

        def run_kv_leg(host_pages):
            eng = GenerationEngine(
                model, params, max_slots=1,
                max_len=max(max_len, kv_fam_len + 8 + kv_new),
                max_prompt_len=kv_fam_len + 8,
                max_queue=max(64, 4 * kv_fams),
                kernels=_FixedCostKernels(kernels, 0.0,
                                          kv_prompt_cost_ms / 1e3),
                page_size=page_size, prefill_chunk=page_size, seed=0,
                cache_dtype=kv_dtype, quantize=quantize,
                metrics=ServingMetrics(), prefix_cache=True,
                num_pages=kv_device_pages, host_pages=host_pages)
            eng.warmup()
            outs = [eng.submit(p, max_new_tokens=kv_new,
                               **sample_spec).result(timeout=600)
                    for p in kv_round1]
            ttfts = []
            for p in kv_round2:
                t0 = time.perf_counter()
                s = eng.submit(p, max_new_tokens=kv_new, **sample_spec)
                it = iter(s)
                toks = [next(it)]
                ttfts.append((time.perf_counter() - t0) * 1e3)
                toks.extend(it)
                outs.append(toks)
            leg_snap = eng.metrics.snapshot()
            host = eng.host_store
            eng.close()
            drained = (eng.pages_in_use == 0 and eng.shared_pages == 0
                       and (host is None or host.pages == 0))
            ttft_p50 = sorted(ttfts)[len(ttfts) // 2]
            return outs, leg_snap, ttft_p50, host, drained

        kv_off_outs, kv_off_snap, kv_off_ttft, _, kv_off_drained = \
            run_kv_leg(None)
        kv_on_outs, kv_on_snap, kv_on_ttft, host_store_obj, \
            kv_on_drained = run_kv_leg(args.host_pages)
        kv_mismatches = sum(1 for a, b in zip(kv_off_outs, kv_on_outs)
                            if a != b)
        host_fields = {
            "host_pages": args.host_pages,
            "host_device_pages": kv_device_pages,
            "host_working_set_pages": kv_fams * kv_fam_pages,
            "host_working_set_vs_device": round(
                kv_fams * kv_fam_pages / kv_device_pages, 2),
            "host_offloaded_pages": kv_on_snap["kv_offload_pages"],
            "host_restored_pages": kv_on_snap["kv_restore_pages"],
            "host_pages_peak": kv_on_snap["host_pages_peak"],
            "host_hit_rate_on": round(kv_on_snap["prefix_hit_rate"], 4),
            "host_hit_rate_off": round(kv_off_snap["prefix_hit_rate"], 4),
            "host_revisit_ttft_p50_on_ms": round(kv_on_ttft, 3),
            "host_revisit_ttft_p50_off_ms": round(kv_off_ttft, 3),
            "host_ttft_reduction": round(kv_off_ttft / kv_on_ttft, 3)
            if kv_on_ttft else None,
            "host_prompt_cost_ms": kv_prompt_cost_ms,
            "host_mismatches": kv_mismatches,
            "host_tiers_drained": kv_on_drained and kv_off_drained,
        }

    # async-scheduling column (PR 19): the first 2*slots requests of
    # the same workload through a sync engine vs an
    # async_scheduling=True engine, both over _AsyncCostKernels (the
    # modeled step cost is paid at MATERIALIZATION, like a real
    # accelerator's async dispatch) plus a fixed per-step host cost
    # slept on the loop thread by the metrics hook below. The sync
    # loop pays step + host serially every iteration (~11 ms at the
    # 8/3 defaults); the async loop lands step N, dispatches N+1, and
    # does the host share inside the in-flight window (~8 ms), so
    # tokens/sec and ITL improve by ~host/step while the streams stay
    # byte-exact. Gates under --smoke: ZERO mismatches,
    # step_overlap_frac > 0.5, async >= 1.2x sync tokens/sec.
    async_fields = {}
    if args.async_sched:
        as_step_ms = step_cost_ms if step_cost_ms > 0 else 8.0
        as_host_ms = args.host_cost_ms
        as_requests = requests[:2 * slots]

        class _CostedMetrics(ServingMetrics):
            # the modeled HOST share of one engine iteration
            # (scheduling, delivery, stream pushes), slept on the loop
            # thread where the real host work runs: record_decode_step
            # fires once per decode step from inside the sync decode
            # pass / the async landed-step processing, which is
            # exactly the serial-vs-overlapped placement under test
            def record_decode_step(self, *a, **kw):
                time.sleep(as_host_ms / 1e3)
                return super().record_decode_step(*a, **kw)

        def run_async_leg(async_sched):
            eng = GenerationEngine(
                model, params, max_slots=slots, max_len=max_len,
                max_prompt_len=max_prompt,
                max_queue=max(64, 2 * len(as_requests)),
                kernels=_AsyncCostKernels(kernels, as_step_ms / 1e3),
                page_size=page_size, seed=0, cache_dtype=kv_dtype,
                quantize=quantize, metrics=_CostedMetrics(),
                async_scheduling=async_sched)
            eng.warmup()
            t0 = time.perf_counter()
            ss = [eng.submit(p, max_new_tokens=m, **sample_spec)
                  for p, m in as_requests]
            leg_outs = [s.result(timeout=600) for s in ss]
            wall = time.perf_counter() - t0
            leg_snap = eng.metrics.snapshot()
            eng.close()
            return leg_outs, leg_snap, wall

        as_sync_outs, as_sync_snap, as_sync_wall = run_async_leg(False)
        as_outs, as_snap, as_wall = run_async_leg(True)
        as_mismatches = sum(1 for a, b in zip(as_sync_outs, as_outs)
                            if a != b)
        as_tps = sum(len(o) for o in as_outs) / as_wall
        as_sync_tps = sum(len(o) for o in as_sync_outs) / as_sync_wall
        sync_itl = as_sync_snap["itl_ms"] or {}
        async_itl = as_snap["itl_ms"] or {}
        async_fields = {
            "async_step_cost_ms": as_step_ms,
            "async_host_cost_ms": as_host_ms,
            "async_requests": len(as_requests),
            "async_tokens_per_sec": round(as_tps, 2),
            "sync_tokens_per_sec": round(as_sync_tps, 2),
            "async_vs_sync": round(as_tps / as_sync_tps, 3),
            "sync_itl_p50_ms": sync_itl.get("p50"),
            "sync_itl_p99_ms": sync_itl.get("p99"),
            "async_itl_p50_ms": async_itl.get("p50"),
            "async_itl_p99_ms": async_itl.get("p99"),
            "async_overlapped_steps": as_snap["overlapped_steps"],
            "async_step_overlap_frac": round(
                as_snap["step_overlap_frac"], 4),
            "async_mismatches": as_mismatches,
        }

    # structured-generation column (PR 20): the same prompts run
    # CONSTRAINED by a token-level grammar automaton (--grammar
    # regex|json) through the same kernels. Finite grammars only — the
    # parse gate is 1.0, so the grammar must guarantee termination
    # under greedy (fixed-length regex / enum+boolean-only schema; an
    # unbounded [0-9]* integer field can legally out-digit any token
    # budget and turn the gate into a coin flip). Columns: constrained
    # tokens/sec, parse rate, masked-vocab fraction, engine-vs-static
    # and speculative-vs-plain mismatches, and the speculative
    # ACCEPTANCE-RATE DELTA vs unconstrained on the same prompts — the
    # mask zeroes every illegal token's target probability, so
    # rejections rise exactly where the draft would have wandered
    # off-grammar. Gates under --smoke: parse rate 1.0 on BOTH
    # constrained legs, zero mismatches, and compile-once (the mask is
    # data riding the existing bias argument, never a new shape).
    grammar_fields = {}
    grammar_metrics = None
    if args.grammar:
        from bigdl_tpu.grammar import (
            compile_grammar,
            json_schema_grammar,
            regex_grammar,
        )
        from bigdl_tpu.serving import SpeculativeKernels

        # toy tokenizer over the bench vocab: printable ASCII at its
        # codepoint (single-char tokens), everything else a placeholder
        # string no character DFA can step through
        gr_eos = 3
        gr_vocab = [chr(i) if 32 <= i < 127 else f"<tok{i}>"
                    for i in range(model.vocab_size)]
        if args.grammar == "regex":
            gr_spec = regex_grammar("id-[0-9][0-9][0-9]")
        else:
            gr_spec = json_schema_grammar({
                "type": "object",
                "properties": {"tool": {"enum": ["search", "calc"]},
                               "ok": {"type": "boolean"}},
                "required": ["tool", "ok"],
            })
        g = compile_grammar(gr_spec, gr_vocab, eos_id=gr_eos)
        # longest legal emission + EOS with headroom; the grammar
        # terminates every stream via EOS long before this budget
        gr_new = 48

        geng = GenerationEngine(
            model, params, max_slots=slots, max_len=max_len,
            max_prompt_len=max_prompt, max_queue=max(64, 2 * n_requests),
            kernels=kernels, page_size=page_size, seed=0, eos_id=gr_eos,
            cache_dtype=kv_dtype, quantize=quantize,
            metrics=ServingMetrics())
        geng.warmup()
        gr_warm = (kernels.prefill_traces, kernels.chunk_traces,
                   kernels.decode_traces)
        t0 = time.perf_counter()
        gstreams = [geng.submit(p, max_new_tokens=gr_new, grammar=g)
                    for p, _ in requests]
        gouts = [s.result(timeout=600) for s in gstreams]
        gr_wall = time.perf_counter() - t0
        gr_snap = geng.metrics.snapshot()
        gr_buckets = geng.prompt_buckets
        grammar_metrics = geng.metrics
        geng.close()
        gr_tokens = sum(len(o) for o in gouts)
        gr_parse = sum(1 for o in gouts if g.matches(o))

        # engine vs static under the grammar: the schedule-invariance
        # contract extends to constrained streams (same kernels, same
        # automaton, same per-slot bias rows)
        gsouts, _ = static_generate(
            model, params, [(p, gr_new) for p, _ in requests],
            max_slots=slots, max_len=max_len, eos_id=gr_eos,
            kernels=kernels, prompt_buckets=gr_buckets,
            page_size=page_size, seed=0, cache_dtype=kv_dtype,
            quantize=quantize,
            sampling=[{"grammar": g}] * n_requests)
        gr_post = (kernels.prefill_traces, kernels.chunk_traces,
                   kernels.decode_traces)
        gr_static_mismatches = sum(1 for a, b in zip(gouts, gsouts)
                                   if a != b)

        # speculative A/B on the same prompts: constrained vs
        # unconstrained acceptance over one shared kernel set (the
        # draft IS the target here, so unconstrained acceptance is the
        # in-family ceiling and the delta isolates the mask's cost)
        gr_k = args.speculate if args.speculate > 0 else 3
        gr_skern = SpeculativeKernels(model, model)

        def run_grammar_spec_leg(grammar):
            eng = GenerationEngine(
                model, params, max_slots=slots, max_len=max_len,
                max_prompt_len=max_prompt,
                max_queue=max(64, 2 * n_requests),
                kernels=gr_skern, page_size=page_size, seed=0,
                eos_id=gr_eos, cache_dtype=kv_dtype, quantize=quantize,
                metrics=ServingMetrics(),
                speculate=(model, params, gr_k))
            eng.warmup()
            ss = [eng.submit(p, max_new_tokens=gr_new, grammar=grammar)
                  for p, _ in requests]
            leg_outs = [s.result(timeout=600) for s in ss]
            leg_snap = eng.metrics.snapshot()
            eng.close()
            return leg_outs, leg_snap

        gspec_outs, gspec_snap = run_grammar_spec_leg(g)
        gr_spec_warm = (gr_skern.draft_traces, gr_skern.verify_traces,
                        gr_skern.chunk_traces, gr_skern.prefill_traces)
        uspec_outs, uspec_snap = run_grammar_spec_leg(None)
        gr_spec_post = (gr_skern.draft_traces, gr_skern.verify_traces,
                        gr_skern.chunk_traces, gr_skern.prefill_traces)
        acc_con = gspec_snap["acceptance_rate"]
        acc_unc = uspec_snap["acceptance_rate"]
        gr_spec_parse = sum(1 for o in gspec_outs if g.matches(o))
        # speculative constrained greedy must be token-identical to
        # plain constrained greedy — the masked-verify losslessness
        gr_spec_mismatches = sum(1 for a, b in zip(gouts, gspec_outs)
                                 if a != b)

        grammar_fields = {
            "grammar_kind": args.grammar,
            "grammar_key": g.key,
            "grammar_states": g.n_states,
            "constrained_tokens_per_sec": round(gr_tokens / gr_wall, 2),
            "constrained_tokens": gr_tokens,
            "grammar_parse_rate": round(gr_parse / n_requests, 4),
            "grammar_spec_parse_rate": round(gr_spec_parse / n_requests, 4),
            "grammar_masked_vocab_frac": round(
                gr_snap["masked_vocab_frac"], 4),
            "grammar_constrained_streams": gr_snap["constrained_streams"],
            "grammar_compile_cache_hits": gr_snap[
                "grammar_compile_cache_hits"],
            "grammar_static_mismatches": gr_static_mismatches,
            "grammar_spec_vs_plain_mismatches": gr_spec_mismatches,
            "grammar_speculate_k": gr_k,
            "grammar_acceptance_constrained": round(acc_con, 4),
            "grammar_acceptance_unconstrained": round(acc_unc, 4),
            "grammar_acceptance_delta": round(acc_con - acc_unc, 4),
            "grammar_compile_once": (gr_warm == gr_post
                                     and gr_spec_warm == gr_spec_post),
        }

    cont_tps = cont_tokens / cont_wall
    static_tps = static_tokens / static_wall
    ttft = snap["ttft_ms"] or {}
    result = {
        "metric": "generation_tokens_per_sec",
        "value": round(cont_tps, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "static_tokens_per_sec": round(static_tps, 2),
        "continuous_vs_static": round(cont_tps / static_tps, 3),
        "ttft_p50_ms": ttft.get("p50"),
        "ttft_p99_ms": ttft.get("p99"),
        "slot_occupancy": round(snap["slot_occupancy"], 4),
        "decode_steps": snap["decode_steps"],
        "static_decode_steps": static_steps,
        "tokens": cont_tokens,
        "requests": n_requests,
        "slots": slots,
        "max_len": max_len,
        "output_mismatches": mismatches,
        "page_size": page_size,
        "pages_total": snap["pages_total"],
        "pages_peak": snap["pages_peak"],
        "prefill_chunks": snap["prefill_chunks"],
        "sampled": bool(args.sample),
        "sampled_tokens": snap["sampled_tokens"],
        "capacity_dense_slots": slots,
        "capacity_paged_seqs": capacity_paged,
        "capacity_paged_vs_dense": round(capacity_ratio, 3),
        "kv_dtype": args.kv_dtype,
        "quantize": args.quantize,
        "kv_page_bytes_per_layer": run_page_bytes,
        "kv_bytes_peak": snap["pages_peak"] * run_page_bytes
        * model.num_hidden_layers,
        "quantized_gemms": snap["quantized_gemms"],
        **int8_fields,
        "tp": args.tp,
        "replicas": args.replicas,
        "step_cost_ms": step_cost_ms,
        "speculate": args.speculate,
        "prefix_cache": bool(args.prefix_cache),
        "disaggregate": bool(args.disaggregate),
        "async_sched": bool(args.async_sched),
        "grammar": args.grammar or "none",
        **rep_fields,
        **spec_fields,
        **prefix_fields,
        **disagg_fields,
        **host_fields,
        **async_fields,
        **grammar_fields,
        "smoke": smoke,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "timing": "wall-clock submit-all -> last stream done; same jitted "
                  "kernels for both schedulers",
    }
    _write_metrics_out(args, {"serving": engine.metrics,
                              "pages": engine._pool,
                              "timeline": engine.timeline,
                              "prefix": prefix_cache_obj,
                              "disagg": disagg_metrics,
                              "kv_host": host_store_obj,
                              "grammar": grammar_metrics,
                              "bench": result})
    print(json.dumps(result))
    if smoke:
        required = ("value", "static_tokens_per_sec", "continuous_vs_static",
                    "ttft_p50_ms", "ttft_p99_ms")
        missing = [k for k in required if result.get(k) in (None, {})]
        if missing:
            raise SystemExit(f"generation smoke: missing fields {missing}")
        if mismatches:
            raise SystemExit(
                f"generation smoke: {mismatches} request(s) decoded "
                "different tokens under continuous vs static scheduling"
                + (" (tp>1: the continuous side ran SHARDED — sharded "
                   "decode must be bit-identical to single-device)"
                   if args.tp > 1 else "")
                + " — decode (greedy AND seeded sampling) must be "
                "schedule-invariant")
        if args.tp == 1 and result["continuous_vs_static"] < 1.5:
            # tp>1 pits a sharded engine against a single-device static
            # baseline: wall-clocks are not comparable there (CPU emulates
            # the collectives); the identity gate above covers tp>1
            raise SystemExit(
                "generation smoke: continuous batching %.2fx static "
                "(gate: >= 1.5x on mixed lengths — the scheduling win "
                "should not depend on core count)"
                % result["continuous_vs_static"])
        if args.replicas > 1 and result["replicated_vs_single"] < 1.5:
            raise SystemExit(
                "generation smoke: %d replicas sustain only %.2fx a single "
                "replica's tokens/sec on the same total traffic at the "
                "same per-step cost (gate: >= 1.5x — replica loops must "
                "overlap)" % (args.replicas, result["replicated_vs_single"]))
        if result["capacity_paged_vs_dense"] < 2.0:
            raise SystemExit(
                "generation smoke: paged KV admits only %.2fx the dense "
                "concurrent sequences at a fixed KV-byte budget (gate: "
                ">= 2x on the 4:1 short:long mix)"
                % result["capacity_paged_vs_dense"])
        if args.speculate > 0:
            if result["speculative_mismatches"]:
                raise SystemExit(
                    "generation smoke: %d request(s) decoded different "
                    "tokens speculatively vs plain greedy — speculative "
                    "greedy decode must be LOSSLESS (token-identical), "
                    "whatever the draft proposes"
                    % result["speculative_mismatches"])
            if not result["speculative_compile_once"]:
                raise SystemExit(
                    "generation smoke: a speculative kernel re-traced "
                    "after warmup — acceptance lengths are data, not "
                    "shapes; compile-once must hold across admissions/"
                    "retirements/acceptance lengths")
            if result["speculative_vs_plain"] < 1.5:
                raise SystemExit(
                    "generation smoke: speculative decoding sustains only "
                    "%.2fx plain tokens/sec at the modeled %.2f draft/"
                    "target cost ratio (gate: >= 1.5x — k accepted drafts "
                    "must amortize the memory-bound target step)"
                    % (result["speculative_vs_plain"],
                       result["spec_draft_cost_ms"]
                       / result["spec_target_cost_ms"]))
        if args.kv_dtype == "int8" and result["capacity_int8_vs_bf16"] < 1.8:
            raise SystemExit(
                "generation smoke: int8 KV pages admit only %.2fx the "
                "bf16 concurrent sequences at the same byte budget "
                "(gate: >= 1.8x with scale pools priced in — the int8 "
                "byte saving must survive its own overhead)"
                % result["capacity_int8_vs_bf16"])
        if args.prefix_cache:
            if result["prefix_mismatches"]:
                raise SystemExit(
                    "prefix smoke: %d request(s) decoded different tokens "
                    "with the prefix cache on vs off — cached pages hold "
                    "the same bits a fresh prefill writes; output must be "
                    "BIT-identical" % result["prefix_mismatches"])
            if result["prefix_hit_rate"] < 0.9:
                raise SystemExit(
                    "prefix smoke: hit rate %.2f on the shared-prefix "
                    "replay (gate: >= 0.9 — one miss to publish, every "
                    "later request must attach)"
                    % result["prefix_hit_rate"])
            if result["prefix_invocation_reduction"] < 2.0:
                raise SystemExit(
                    "prefix smoke: only %.2fx fewer chunk/prefill kernel "
                    "invocations with the cache on (gate: >= 2x — hits "
                    "must SKIP the covered chunks, not just count them)"
                    % result["prefix_invocation_reduction"])
            if (result["prefix_ttft_reduction"] is None
                    or result["prefix_ttft_p50_on_ms"]
                    > 0.8 * result["prefix_ttft_p50_off_ms"]):
                raise SystemExit(
                    "prefix smoke: TTFT p50 %.2f ms cache-on vs %.2f ms "
                    "cache-off (gate: on <= 0.8x off at the modeled "
                    "prompt cost — skipped prefill must shorten "
                    "time-to-first-token)"
                    % (result["prefix_ttft_p50_on_ms"] or -1,
                       result["prefix_ttft_p50_off_ms"] or -1))
        if args.disaggregate:
            if result["disagg_mismatches"]:
                raise SystemExit(
                    "disagg smoke: %d request(s) decoded different tokens "
                    "disaggregated vs monolithic — the handoff carries the "
                    "first token and the post-prefill PRNG key; streams "
                    "must be BIT-identical across the role split"
                    % result["disagg_mismatches"])
            if not result["disagg_pages_drained"]:
                raise SystemExit(
                    "disagg smoke: a role pool still holds pages after "
                    "every stream resolved — export/adopt must keep the "
                    "refcount/owner gauges byte-exact")
            if (result["disagg_itl_p99_vs_mono"] is None
                    or result["disagg_itl_p99_vs_mono"] > 0.7):
                raise SystemExit(
                    "disagg smoke: decode ITL p99 %.2f ms disaggregated vs "
                    "%.2f ms monolithic (ratio %s, gate: <= 0.7x at equal "
                    "modeled costs — a dedicated decode role must stop "
                    "paying for its neighbours' prompt chunks)"
                    % (result["disagg_itl_p99_ms"] or -1,
                       result["mono_itl_p99_ms"] or -1,
                       result["disagg_itl_p99_vs_mono"]))
        if args.host_pages > 0:
            if result["host_mismatches"]:
                raise SystemExit(
                    "kv-tier smoke: %d request(s) decoded different tokens "
                    "with the host tier on vs off — an offloaded page must "
                    "restore the same bits a fresh prefill writes; output "
                    "must be BIT-identical" % result["host_mismatches"])
            if not result["host_tiers_drained"]:
                raise SystemExit(
                    "kv-tier smoke: a tier still holds pages after every "
                    "stream resolved — offload/restore/swap must drain "
                    "BOTH tiers' gauges to zero at close")
            if result["host_restored_pages"] < 1 or \
                    result["host_hit_rate_on"] <= 0:
                raise SystemExit(
                    "kv-tier smoke: %d pages restored, effective hit rate "
                    "%.2f at a %.0fx-device working set (gate: restores "
                    "> 0 and hit rate > 0 — the host tier must actually "
                    "serve the revisits the device pool evicted)"
                    % (result["host_restored_pages"],
                       result["host_hit_rate_on"],
                       result["host_working_set_vs_device"]))
            if result["host_revisit_ttft_p50_on_ms"] >= \
                    result["host_revisit_ttft_p50_off_ms"]:
                raise SystemExit(
                    "kv-tier smoke: revisit TTFT p50 %.2f ms with the host "
                    "tier vs %.2f ms re-prefilling (gate: restored < "
                    "re-prefill — a restore must skip the covered chunks, "
                    "not just move bytes)"
                    % (result["host_revisit_ttft_p50_on_ms"],
                       result["host_revisit_ttft_p50_off_ms"]))
        if args.async_sched:
            if result["async_mismatches"]:
                raise SystemExit(
                    "async smoke: %d request(s) decoded different tokens "
                    "under async vs sync scheduling — the one-step "
                    "scheduling lag discards rider tokens and the double "
                    "buffer isolates in-flight dispatches; streams must "
                    "be BYTE-exact" % result["async_mismatches"])
            if result["async_step_overlap_frac"] <= 0.5:
                raise SystemExit(
                    "async smoke: only %.0f%% of engine steps ran host "
                    "work under an in-flight decode step (gate: > 50%% — "
                    "the overlap window must actually absorb the host "
                    "share)" % (100 * result["async_step_overlap_frac"]))
            if result["async_vs_sync"] < 1.2:
                raise SystemExit(
                    "async smoke: async scheduling sustains only %.2fx "
                    "sync tokens/sec at the modeled %.0f ms step / "
                    "%.0f ms host cost (gate: >= 1.2x — the host share "
                    "must fold into the in-flight step's window)"
                    % (result["async_vs_sync"],
                       result["async_step_cost_ms"],
                       result["async_host_cost_ms"]))
        if args.grammar:
            if result["grammar_parse_rate"] < 1.0 \
                    or result["grammar_spec_parse_rate"] < 1.0:
                raise SystemExit(
                    "grammar smoke: parse rate %.2f plain / %.2f "
                    "speculative (gate: 1.0 on BOTH — every constrained "
                    "stream must parse; a finite grammar terminates via "
                    "EOS inside any reasonable budget)"
                    % (result["grammar_parse_rate"],
                       result["grammar_spec_parse_rate"]))
            if result["grammar_static_mismatches"]:
                raise SystemExit(
                    "grammar smoke: %d request(s) decoded different "
                    "tokens under the engine vs static batching with the "
                    "same grammar — constrained greedy is argmax over the "
                    "legal set and must stay schedule-invariant"
                    % result["grammar_static_mismatches"])
            if result["grammar_spec_vs_plain_mismatches"]:
                raise SystemExit(
                    "grammar smoke: %d request(s) decoded different "
                    "tokens speculatively vs plain under the same grammar "
                    "— the mask zeroes illegal target probability, so "
                    "masked speculative greedy must stay LOSSLESS"
                    % result["grammar_spec_vs_plain_mismatches"])
            if not result["grammar_compile_once"]:
                raise SystemExit(
                    "grammar smoke: a kernel re-traced after warmup with "
                    "grammar masks in flight — the mask is DATA riding "
                    "the existing per-slot bias argument, never a new "
                    "traced shape")


def run_lm_bench(args):
    """LM throughput + empirical MFU (``--mode lm``): jitted
    full-sequence forward and engine-shaped decode steps over the
    serving ``nn.Transformer``, with a ``--quantize int8`` A/B leg.

    BENCH has tracked only the conv-heavy ResNet-50 step while the MFU
    north star talks about MXU-rate compute; this mode measures the
    GEMM-shaped workload directly. Same differential-timing scheme as
    ``perf/lm_perf.py``: two scan lengths, slope = per-step time, so
    dispatch overhead cancels. MFU counts USEFUL flops (GEMMs + the
    attended context, not pad/masked lanes) against the measured
    matmul peak of the same precision family — the int8 leg divides by
    a measured s8 x s8 -> s32 peak (``measure_peak_int8_flops``), the
    float leg by the float/bf16 peak, so on the MXU (int8 ~1.9x bf16)
    the int8 MFU reports actual int8-path utilization instead of a
    >1.0 number priced against the wrong family. On CPU the column is
    a smoke-level sanity number; the on-chip round is where it binds.
    The int8 leg reports its ratio vs float: on the MXU the int8 dot
    runs ~1.9x bf16 (round 5); on CPU it is typically SLOWER (no VNNI
    path through XLA) — the A/B column exists so the on-chip number
    lands somewhere."""
    from bigdl_tpu.nn.layers.attention import Transformer
    from bigdl_tpu.nn.quantized import quantize_for_serving

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        vocab, hidden, heads, filt, layers = 8192, 512, 8, 2048, 4
        batch, seq, slots, dec_steps = 8, 128, 16, 32
        peak = measure_peak_flops(jnp.bfloat16)
        peak_int8 = (measure_peak_int8_flops()
                     if args.quantize == "int8" else None)
    else:
        vocab, hidden, heads, filt, layers = 256, 128, 4, 256, 2
        batch, seq, slots, dec_steps = 4, 64, 8, 16
        peak = measure_peak_flops(jnp.float32, n=512, short=16, long=48)
        peak_int8 = (measure_peak_int8_flops(n=512, short=16, long=48)
                     if args.quantize == "int8" else None)

    model = Transformer(vocab_size=vocab, hidden_size=hidden,
                        num_heads=heads, filter_size=filt,
                        num_hidden_layers=layers)
    params, _ = model.init(jax.random.key(0))
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(1, vocab, (batch, seq)), jnp.int32)

    # useful flops per token: the 6 GEMMs + lm head (2*N*K each) plus
    # score/value attention matmuls over the actually-attended context
    gemm_tok = 2 * (4 * hidden * hidden + 2 * hidden * filt) * layers \
        + 2 * hidden * vocab
    fwd_attn_tok = 4 * hidden * (seq / 2) * layers     # avg causal ctx
    fwd_flops_tok = gemm_tok + fwd_attn_tok

    def time_slope(make_runner, n1, n2, reps=5):
        """Best-of differential: (t(n2) - t(n1)) / (n2 - n1)."""
        r1, r2 = make_runner(n1), make_runner(n2)

        def best(r):
            jax.block_until_ready(r())
            b = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(r())
                b = min(b, time.perf_counter() - t0)
            return b

        return (best(r2) - best(r1)) / (n2 - n1)

    toks0 = jnp.asarray(rs.randint(1, vocab, (slots,)), jnp.int32)
    pos0 = jnp.full((slots,), seq // 2, jnp.int32)

    def leg(p, peak_denom):
        def fwd_runner(n):
            # each iteration's input depends on the previous argmax so
            # XLA cannot hoist the loop-invariant forward out of the
            # scan (a constant-input scan times as ONE forward)
            @jax.jit
            def f(p, ids):
                def step(ids, _):
                    lg, _ = model.apply(p, ids, training=False)
                    nxt = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)
                    ids = jnp.roll(ids, -1, axis=1).at[:, -1].set(nxt)
                    return ids, None
                ids, _ = jax.lax.scan(step, ids, None, length=n)
                return ids
            return lambda: f(p, ids)

        fwd_dt = time_slope(fwd_runner, 2, 6)
        fwd_tps = batch * seq / fwd_dt

        cache = model.init_cache(slots, seq)

        def dec_runner(n):
            @jax.jit
            def f(p, cache, toks, pos):
                def step(carry, _):
                    cache, toks, pos = carry
                    lg, cache = model.decode_step(p, cache, toks, pos)
                    toks = jnp.argmax(lg, -1).astype(jnp.int32)
                    return (cache, toks, pos + 1), None
                (cache, toks, _), _ = jax.lax.scan(
                    step, (cache, toks, pos), None, length=n)
                return toks
            return lambda: f(p, cache, toks0, pos0)

        dec_dt = time_slope(dec_runner, 2, 2 + dec_steps)
        dec_tps = slots / dec_dt
        dec_attn_tok = 4 * hidden * (seq // 2) * layers
        return {
            "forward_tokens_per_sec": round(fwd_tps, 1),
            "forward_mfu": round(fwd_tps * fwd_flops_tok / peak_denom, 4),
            "decode_tokens_per_sec": round(dec_tps, 1),
            "decode_mfu": round(
                dec_tps * (gemm_tok + dec_attn_tok) / peak_denom, 4),
        }

    result = {
        "metric": "lm_forward_tokens_per_sec",
        "unit": "tokens/sec",
        "vs_baseline": None,
        "model": {"vocab": vocab, "hidden": hidden, "heads": heads,
                  "filter": filt, "layers": layers, "batch": batch,
                  "seq": seq, "decode_slots": slots},
        "matmul_peak_flops": peak,
        **leg(params, peak),
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "timing": "differential scan slope (dispatch cancels), best-of-5",
    }
    result["value"] = result["forward_tokens_per_sec"]
    if args.quantize == "int8":
        qparams = quantize_for_serving(params)
        # the int8 leg's MFU denominator is the measured int8 peak —
        # "same precision family" for real (the mixed float attention
        # inside the leg makes this slightly conservative on-chip)
        q = leg(qparams, peak_int8)
        result["int8_matmul_peak_flops"] = peak_int8
        result.update({f"int8_{k}": v for k, v in q.items()})
        result["int8_vs_float_forward"] = round(
            q["forward_tokens_per_sec"]
            / result["forward_tokens_per_sec"], 3)
        result["int8_vs_float_decode"] = round(
            q["decode_tokens_per_sec"]
            / result["decode_tokens_per_sec"], 3)
    _write_metrics_out(args, {"bench": result})
    print(json.dumps(result))
    if args.smoke:
        need = ["forward_tokens_per_sec", "forward_mfu",
                "decode_tokens_per_sec", "decode_mfu"]
        if args.quantize == "int8":
            need += ["int8_vs_float_forward", "int8_vs_float_decode",
                     "int8_matmul_peak_flops",
                     "int8_forward_mfu", "int8_decode_mfu"]
        bad = [k for k in need
               if not np.isfinite(result.get(k, float("nan")))
               or result[k] <= 0]
        if bad:
            raise SystemExit(f"lm smoke: non-finite/non-positive {bad}")


def run_checkpoint_bench(args):
    """Checkpoint-cost benchmark: per-step overhead of blocking vs async
    saves through ``bigdl_tpu.ckpt.CheckpointManager`` on the resnet bench
    model, plus restore latency.

    Three identically-shaped step loops run with a host fetch per step
    (the same sync a real driver loop performs for its loss/metrics): no
    saves, blocking saves every K steps, async saves every K steps. The
    headline overhead is the time the ``save()`` call itself blocks the
    loop, summed and amortized per step — blocking saves pay
    serialize+sha256+fsync inline, async saves pay only the device->host
    snapshot. (Whole-loop deltas vs the no-save run are reported too, but
    on jittery rigs step-time noise can swamp them; the blocked-time
    measurement is exact by construction.) The async drain (commits
    completing after the loop) is timed separately: it overlaps training
    in real runs and only gates shutdown."""
    import shutil
    import tempfile

    from bigdl_tpu.ckpt import CheckpointManager
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    batch = args.batch or (64 if on_tpu else 4)
    compute_dtype = jnp.bfloat16 if on_tpu else jnp.float32
    if on_tpu:
        # the bench model: same ResNet-50 the train mode measures
        depth, class_num, side = 50, 1000, 224
        model = resnet.build_imagenet(depth, class_num, kernel_format="HWIO")
    else:
        # dev smoke on CPU: a small CIFAR resnet keeps compile time sane
        depth, class_num, side = args.ckpt_depth, 10, 32
        model = resnet.build_cifar(depth, class_num)
    criterion = CrossEntropyCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9)

    params, mstate = model.init(jax.random.key(0))
    ostate = method.init_state(params)
    x = jnp.asarray(np.random.rand(batch, 3, side, side), compute_dtype)
    y = jnp.asarray(np.random.randint(0, class_num, (batch,)), jnp.int32)

    step = build_step(model, criterion, method)
    jit_step = jax.jit(lambda c, xx, yy: step(c, (xx, yy)))

    iters, save_every = args.ckpt_iters, args.ckpt_save_every
    n_saves = iters // save_every
    if n_saves < 1:
        raise SystemExit(
            f"--ckpt-iters {iters} < --ckpt-save-every {save_every}: "
            "no save would ever fire")

    def loop(saver=None):
        c = (params, mstate, ostate)
        c, loss = jit_step(c, x, y)
        float(loss)  # compile + warm caches before the clock starts
        blocked = 0.0
        t0 = time.perf_counter()
        for i in range(1, iters + 1):
            c, loss = jit_step(c, x, y)
            float(loss)  # the per-step host sync every real driver loop does
            if saver is not None and i % save_every == 0:
                s0 = time.perf_counter()
                saver(i, c)
                blocked += time.perf_counter() - s0
        return time.perf_counter() - t0, blocked

    t_plain, _ = loop()

    root = tempfile.mkdtemp(prefix="bigdl_ckpt_bench_")
    try:
        with CheckpointManager(os.path.join(root, "blocking"),
                               async_save=False) as mb:
            t_block, blocked_sync = loop(lambda i, c: mb.save(
                f"model.iter{i}", c[0], c[1], c[2], meta={"iteration": i}))
            blob_bytes = mb.entries()[-1].size

        with CheckpointManager(os.path.join(root, "async")) as ma:
            t_async, blocked_async = loop(lambda i, c: ma.save(
                f"model.iter{i}", c[0], c[1], c[2], meta={"iteration": i}))
            t0 = time.perf_counter()
            ma.wait()
            drain_s = time.perf_counter() - t0

            template = {"params": params, "module_state": mstate,
                        "optim_state": ostate}
            t0 = time.perf_counter()
            restored = ma.restore_latest(template)
            restore_s = time.perf_counter() - t0
            assert restored is not None
            assert restored[1].step == n_saves * save_every  # last fired save
    finally:
        shutil.rmtree(root, ignore_errors=True)

    block_ms = blocked_sync / iters * 1e3
    async_ms = blocked_async / iters * 1e3
    result = {
        "metric": "checkpoint_async_step_overhead_ms",
        "value": round(async_ms, 4),
        "unit": "ms/step",
        "vs_baseline": None,
        "blocking_step_overhead_ms": round(block_ms, 4),
        "speedup_vs_blocking": round(block_ms / max(async_ms, 1e-6), 2),
        "plain_ms_per_step": round(t_plain / iters * 1e3, 3),
        "loop_delta_blocking_ms_per_step": round(
            (t_block - t_plain) / iters * 1e3, 4),
        "loop_delta_async_ms_per_step": round(
            (t_async - t_plain) / iters * 1e3, 4),
        "restore_ms": round(restore_s * 1e3, 2),
        "async_drain_ms": round(drain_s * 1e3, 2),
        "blob_mb": round(blob_bytes / 1e6, 2),
        "iters": iters,
        "save_every": save_every,
        "saves_per_mode": n_saves,
        "model_depth": depth,
        "batch": batch,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "timing": "headline = time save() blocks the step loop, amortized "
                  "per step (exact); loop_delta_* are whole-loop deltas vs "
                  "the no-save run (jitter-prone); async drain overlaps "
                  "training in real runs",
    }
    _write_metrics_out(args, {"bench": result})
    print(json.dumps(result))


def run_pipeline_bench(args):
    """Input-pipeline benchmark: per-stage img/s for the host feed path
    (produce / augment xN / stage / transfer) plus the overlapped
    end-to-end rate — the 0.97x methodology from ``perf/feeder_roofline.py``
    applied to the parallel transformer pool, now via the shared
    ``PipelineStats`` plumbing.

    The augment chain is the pad-4 random crop + horizontal flip on
    synthetic uint8 ImageNet images, fanned across ``--pipeline-workers``
    workers; batches stay uint8 (normalize-on-device, like the train
    bench). Two bounds are reported: ``min(stage rates)`` (perfect
    overlap — the acceptance bar on a multicore host) and the
    *achievable* bound ``min(min_stage, n_cores * harmonic_rate)``, which
    accounts for hosts with fewer cores than pipeline stages (a 1-core
    dev container cannot overlap anything; asserting min-stage there
    would test the rig, not the pipeline). ``--smoke`` shrinks the run
    and exits nonzero unless the JSON is complete and end-to-end >=
    0.8x the achievable bound."""
    import time as _time

    from bigdl_tpu.core.rng import RandomGenerator
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.image import HFlip, RandomCropper
    from bigdl_tpu.dataset.parallel_pipeline import PipelineStats
    from bigdl_tpu.dataset.prefetch import host_prefetch
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.dataset.transformer import FunctionTransformer

    platform = jax.devices()[0].platform
    n_cores = os.cpu_count() or 1
    smoke = args.smoke
    batch = args.batch or (16 if smoke else 64)
    max_workers = args.pipeline_workers
    sweep = sorted({w for w in (1, 2, 4, 8) if w <= max_workers} | {max_workers})
    if smoke:
        sweep = sorted({1, max_workers})
    chunk = 8

    rs = np.random.RandomState(0)
    n_src = 4 * batch
    elems = [(rs.randint(0, 255, (3, 224, 224)).astype(np.uint8), i)
             for i in range(n_src)]
    img_mb = elems[0][0].nbytes / 1e6

    def cycle():
        while True:
            yield from elems

    def to_sample(t):
        # keep uint8 end to end: 4x fewer bytes staged and transferred,
        # normalization happens on device (same as the train bench)
        return Sample(t[0], np.int32(t[1]))

    aug = (RandomCropper(224, 224, pad=4, rng=RandomGenerator(3))
           >> HFlip(rng=RandomGenerator(5))
           >> FunctionTransformer(to_sample))

    # pool buffers hold up to ~n_workers * 2 * depth * chunk elements;
    # every pooled measurement warms up past that and measures a window
    # several times larger, so rates are steady-state, not buffer drains
    buf_elems = max_workers * 2 * 2 * chunk

    def rate_of(it, n_items, per_item=1, warmup=4, windows=2):
        # best of `windows` consecutive windows on the warm stream: one
        # scheduler hiccup must not sink a rate (same min-of-reps
        # reasoning as the train bench's `timed`)
        for _ in range(warmup):
            next(it)
        best = 0.0
        for _ in range(windows):
            t0 = _time.perf_counter()
            for _ in range(n_items):
                next(it)
            best = max(best, n_items * per_item / (_time.perf_counter() - t0))
        return best

    # 1. produce: the raw source stream
    produce_rate = rate_of(cycle(), 8 * batch)

    # 2. augment xN scaling sweep (the tentpole measurement)
    n_aug = max(4 * buf_elems, (8 if smoke else 32) * batch)
    scaling = {}
    for w in sweep:
        pool = aug.parallel(w, chunk=chunk, base_seed=11)
        it = pool.apply(cycle())
        scaling[w] = rate_of(it, n_aug, warmup=buf_elems)
        it.close()
    aug_rate = scaling[max_workers]

    # 3. batch: SampleToMiniBatch stacking over pre-augmented samples
    ready_samples = list(aug.apply(iter(elems)))

    def cycle_samples():
        while True:
            yield from ready_samples

    n_batches = 16 if smoke else 64
    batch_rate = rate_of(
        SampleToMiniBatch(batch).apply(cycle_samples()),
        n_batches, per_item=batch)

    # 4. stage: host_prefetch passthrough on prebuilt minibatches
    ready = list(SampleToMiniBatch(batch).apply(iter(ready_samples)))

    def cycle_batches():
        while True:
            yield from ready

    staged = host_prefetch(cycle_batches(), depth=4)
    stage_rate = rate_of(staged, 8 * n_batches, per_item=batch)
    staged.close()

    def measure_volatile(aug_rate):
        """The measurements the bound/ratio hang on, grouped so a noisy
        window can be retried as one consistent pass. ``aug_rate``:
        reuse the sweep's max-worker rate on pass 1, remeasure on retry."""
        if aug_rate is None:
            it = aug.parallel(max_workers, chunk=chunk,
                              base_seed=11).apply(cycle())
            aug_rate = rate_of(it, n_aug, warmup=buf_elems)
            it.close()

        # transfer: device_put bandwidth at batch size (uint8 payload).
        # MEDIAN of the reps: the CPU backend sometimes aliases host
        # memory (zero-copy) and sometimes copies — one lucky zero-copy
        # rep would inflate a best-of rate ~25x and poison the bound
        probe = np.stack([e[0] for e in elems[:batch]])
        jax.block_until_ready(jax.device_put(probe))
        times = []
        for _ in range(5):
            t0 = _time.perf_counter()
            jax.block_until_ready(jax.device_put(probe))
            times.append(_time.perf_counter() - t0)
        xfer_rate = batch / float(np.median(times))

        # end to end: source -> pool(augment) -> batch -> staging thread
        # -> device transfer, all overlapped; PipelineStats carries the
        # per-stage occupancy/stall/starve counters. Worker count is
        # capped at 2x the cores: oversubscribing a small host buys only
        # scheduler churn (nobody runs 8 workers on 1 core in production)
        e2e_workers = min(max_workers, max(2, 2 * n_cores))
        stats = PipelineStats()
        pool = aug.parallel(e2e_workers, chunk=chunk, base_seed=11,
                            stats=stats)
        e2e_stream = host_prefetch(
            SampleToMiniBatch(batch).apply(pool.apply(cycle())),
            depth=4, stats=stats)

        def put_batches():
            for mb in e2e_stream:
                yield jax.block_until_ready(jax.device_put(mb.input))

        n_e2e = max(2 * buf_elems // batch, 12 if smoke else 64)
        e2e_rate = rate_of(put_batches(), n_e2e, per_item=batch,
                           warmup=max(4, buf_elems // batch), windows=3)
        e2e_stream.close()

        # the no-pool control: same chain run serially. The direct test
        # of "the pool adds no stalls" on ANY core count — a 1-core host
        # cannot overlap stages, so only this comparison (not the
        # min-stage bound) isolates pool overhead from rig limits.
        serial_stream = host_prefetch(
            SampleToMiniBatch(batch).apply(aug.apply(cycle())), depth=4)

        def put_serial():
            for mb in serial_stream:
                yield jax.block_until_ready(jax.device_put(mb.input))

        serial_rate = rate_of(put_serial(), n_e2e, per_item=batch,
                              warmup=4, windows=3)
        serial_stream.close()

        stage_rates = {"produce": produce_rate,
                       f"augment_x{max_workers}": aug_rate,
                       "batch": batch_rate, "stage": stage_rate,
                       "transfer": xfer_rate}
        min_stage = min(stage_rates.values())
        harmonic = 1.0 / sum(1.0 / r for r in stage_rates.values())
        achievable = min(min_stage, n_cores * harmonic)
        return {
            "metric": "pipeline_end_to_end_images_per_sec",
            "value": round(e2e_rate, 1),
            "unit": "images/sec",
            "vs_baseline": None,
            "stage_rates": {k: round(v, 1) for k, v in stage_rates.items()},
            "augment_scaling": {str(w): round(r, 1)
                                for w, r in scaling.items()},
            "augment_scaling_x": round(scaling[max_workers] / scaling[1], 2),
            "ratio_vs_min_stage": round(e2e_rate / min_stage, 3),
            "ratio_vs_achievable": round(e2e_rate / achievable, 3),
            "achievable_bound": round(achievable, 1),
            "e2e_serial_images_per_sec": round(serial_rate, 1),
            "pool_e2e_speedup": round(e2e_rate / serial_rate, 2),
            "n_cores": n_cores,
            "workers": max_workers,
            "e2e_workers": e2e_workers,
            "batch": batch,
            "chunk": chunk,
            "img_mb": round(img_mb, 3),
            "smoke": smoke,
            "platform": platform,
            "device_kind": jax.devices()[0].device_kind,
            "pipeline_stats": stats.snapshot(),
            "timing": "per-stage rates isolated; e2e overlapped; achievable "
                      "bound = min(min_stage, n_cores * harmonic) accounts "
                      "for hosts with fewer cores than stages",
        }

    def smoke_ok(res):
        # pool adds no stalls vs the serial control, always; on hosts
        # with real parallelism the overlapped rate must also track the
        # stage bound (on 1 core that bound measures the rig, not us).
        # 1-core allowance 0.7: N worker threads time-slicing one core
        # pay scheduler churn that exists neither serially nor on any
        # real host; genuine pool stalls (deadlock, broken backpressure)
        # collapse throughput far below that.
        if res["pool_e2e_speedup"] < (0.8 if n_cores >= 2 else 0.7):
            return False
        return n_cores < 2 or res["ratio_vs_achievable"] >= 0.8

    result = measure_volatile(aug_rate)
    if smoke and not smoke_ok(result):
        # the bound and e2e are measured in different sub-windows; on a
        # loaded shared host one noisy window can split them. One full
        # consistent re-pass before declaring the pipeline broken —
        # adopted if IT passes the gate (whichever check failed), else
        # the better-reading pass is reported.
        retry = measure_volatile(None)
        if (smoke_ok(retry)
                or retry["ratio_vs_achievable"]
                > result["ratio_vs_achievable"]):
            result = retry
        result["retried"] = True

    _write_metrics_out(args, {"bench": result})
    print(json.dumps(result))
    if smoke:
        required = ("value", "stage_rates", "augment_scaling",
                    "ratio_vs_achievable", "pool_e2e_speedup")
        missing = [k for k in required if result.get(k) in (None, {})]
        if missing:
            raise SystemExit(f"pipeline smoke: missing fields {missing}")
        if not smoke_ok(result):
            raise SystemExit(
                "pipeline smoke: end-to-end %.1f img/s (%.2fx the "
                "achievable bound %.1f, %.2fx the serial control): the "
                "pool is adding stalls"
                % (result["value"], result["ratio_vs_achievable"],
                   result["achievable_bound"], result["pool_e2e_speedup"]))


def run_chaos_bench(args):
    """Chaos soak (``--mode chaos``): a short train-with-checkpoints +
    serve-with-replicas workload under a FIXED-SEED randomized fault
    schedule, asserting the invariants the stack promises individually:

    - **train**: with worker crashes injected into the parallel input
      pipeline (supervised restarts) and transient OSErrors injected
      into the checkpoint blob/manifest writes (RetryPolicy healing),
      training completes and BOTH the live final params and the
      restored newest checkpoint are bit-identical to a fault-free run
      of the same seed;
    - **serve**: with one replica killed mid-soak (``engine.decode``
      site), transient submit faults (``replica.submit`` site), and
      deadline-bearing requests, the ReplicaSet front door raises only
      API-typed errors (Overloaded/ReplicaUnavailable at submit;
      DeadlineExceeded/StreamCancelled/the injected fault on streams),
      and after the schedule exhausts a clean final wave is served
      entirely by the surviving replica;
    - **watchdog**: a wedged decode step (armed latency) fails its
      streams with a StallError diagnostic instead of hanging;
    - **speculative**: a draft-step fault mid-speculation fails the
      in-flight streams with the INJECTED error through the stream API
      (the engine's step contract) and BOTH models' page lanes drain to
      zero per owner;
    - **disaggregation**: a fault mid page-handoff (adopt stage locally,
      export stage armed in a child prefill worker over the fault RPCs)
      fails only that stream with the injected error, BOTH role pools'
      per-owner gauges drain to zero, and the fabric keeps serving the
      monolithic engine's exact bits;
    - **drain**: KV pages return to zero on every engine, no
      /dev/shm segment leaks, and every bigdl-owned thread retires.

    All schedules derive from ``--chaos-seed`` via the splitmix64 plans
    in ``bigdl_tpu.faults`` — the soak replays exactly. ``--smoke``
    shrinks the run for the CI gate (<60 s on one core); the invariant
    checks run in every mode and exit nonzero on violation."""
    import glob
    import shutil
    import tempfile
    import threading

    import bigdl_tpu.nn as nn
    from bigdl_tpu import faults, optim
    from bigdl_tpu.core.rng import RandomGenerator
    from bigdl_tpu.dataset import DataSet, FunctionTransformer, \
        SampleToMiniBatch
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.faults import InjectedFault, RetryPolicy, StallError
    from bigdl_tpu.nn.layers.attention import Transformer
    from bigdl_tpu.serving import (
        DeadlineExceeded,
        DisaggregatedEngine,
        GenerationEngine,
        Overloaded,
        PagedDecodeKernels,
        RemoteReplica,
        ReplicaServer,
        ReplicaSet,
        ReplicaUnavailable,
        ServingMetrics,
        StreamCancelled,
        TransportError,
        start_replica_process,
    )

    from bigdl_tpu.obs import flight_recorder

    t_start = time.perf_counter()
    seed = args.chaos_seed
    smoke = args.smoke
    train_iters = args.chaos_iters or (12 if smoke else 24)
    n_requests = args.chaos_requests or (24 if smoke else 64)
    violations = []

    # flight-recorder reconciliation: every armed fault that fires must
    # leave a structured breadcrumb, so a failed soak is reconstructable
    # from the recorder instead of a bare traceback. `fired_expected`
    # accumulates FaultInjector.snapshot() totals across the legs (each
    # faults.reset() clears the injector history, never the recorder).
    recorder = flight_recorder()
    fired_before = recorder.count("fault.fired")
    fired_expected = 0

    def own_threads():
        prefixes = ("bigdl-", "ckpt-writer", "pipeline-")
        return sorted(t.name for t in threading.enumerate()
                      if t.name.startswith(prefixes) and t.is_alive())

    shm_dir = "/dev/shm"
    shm_before = set(glob.glob(os.path.join(shm_dir, "*"))) \
        if os.path.isdir(shm_dir) else None

    # ---------------------------------------------------------- train ----
    def train_once(workdir, data_seed=5):
        def to_sample(t):
            return Sample(t[0], np.int32(t[1]))

        rs = np.random.RandomState(3)
        xs = rs.randn(128, 8).astype(np.float32)
        w = rs.randn(1, 8).astype(np.float32)
        ys = (xs @ w.T > 0).astype(np.int32)[:, 0]
        elems = [(xs[i], ys[i]) for i in range(len(xs))]
        # explicit rng: the default RandomGenerator is process-global
        # and its epoch shuffles would diverge between the two runs
        ds = DataSet.array(elems, rng=RandomGenerator(data_seed)) \
            >> (FunctionTransformer(to_sample) >> SampleToMiniBatch(16))
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 2), nn.LogSoftMax())
        opt = optim.LocalOptimizer(model, ds, nn.ClassNLLCriterion(),
                                   batch_size=16)
        opt.set_optim_method(optim.SGD(learning_rate=0.5, momentum=0.9))
        opt.set_end_when(optim.Trigger.max_iteration(train_iters))
        opt.set_checkpoint(workdir, optim.Trigger.several_iteration(3),
                           keep_last_n=3)
        opt.set_data_pipeline(2, ordered=True, max_worker_restarts=16)
        opt.set_watchdog(120.0)  # only a genuine hang fires
        params, _ = opt.optimize()
        mgr = opt.checkpoint_manager
        mgr.wait()
        restored = mgr.restore_latest()
        mgr.close()
        host = jax.tree_util.tree_map(np.asarray, params)
        return host, restored

    root = tempfile.mkdtemp(prefix="bigdl_chaos_")
    try:
        ref_params, ref_restored = train_once(os.path.join(root, "ref"))

        faults.arm("pipeline.worker", rate=0.05, seed=seed, times=6)
        faults.arm("ckpt.blob_write", nth=1, exc=OSError)
        faults.arm("ckpt.manifest_write", rate=0.5, seed=seed + 1,
                   times=2, exc=OSError)
        chaos_params, chaos_restored = train_once(os.path.join(root, "chaos"))
        train_fired = {s: v["fired"] for s, v in faults.snapshot().items()}
        fired_expected += sum(train_fired.values())
        faults.reset()

        ref_leaves = jax.tree_util.tree_leaves(ref_params)
        chaos_leaves = jax.tree_util.tree_leaves(chaos_params)
        params_match = len(ref_leaves) == len(chaos_leaves) and all(
            np.array_equal(a, b) for a, b in zip(ref_leaves, chaos_leaves))
        restored_match = (
            ref_restored is not None and chaos_restored is not None
            and ref_restored[1].step == chaos_restored[1].step
            and all(np.array_equal(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(ref_restored[0]),
                jax.tree_util.tree_leaves(chaos_restored[0]))))
        if not params_match:
            violations.append("train: faulted final params diverge from "
                              "the fault-free run")
        if not restored_match:
            violations.append("train: restored checkpoint diverges from "
                              "the fault-free run")
        if train_fired.get("pipeline.worker", 0) < 1 \
                or train_fired.get("ckpt.blob_write", 0) < 1:
            violations.append(f"train: fault schedule never fired "
                              f"({train_fired}) — the soak proved nothing")
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # ---------------------------------------------------------- serve ----
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        filter_size=64, num_hidden_layers=1)
    params, _ = model.init(jax.random.key(0))
    max_len, max_prompt, slots = 48, 8, 4
    kernels = PagedDecodeKernels(model)  # ONE compiled triple, shared

    def build_engine(step_cost_ms=2.0, stall_timeout=None):
        kern = _FixedCostKernels(kernels, step_cost_ms / 1e3) \
            if step_cost_ms else kernels
        eng = GenerationEngine(
            model, params, max_slots=slots, max_len=max_len,
            max_prompt_len=max_prompt, max_queue=4 * n_requests,
            kernels=kern, page_size=8, seed=seed,
            metrics=ServingMetrics(), stall_timeout=stall_timeout)
        eng.warmup()
        return eng

    replicas = [build_engine(), build_engine()]
    rset = ReplicaSet(replicas, max_failures=2,
                      probe=lambda e: e.generate([1], max_new_tokens=1,
                                                 timeout=5),
                      probe_interval=0.05, name="chaos")
    # schedule: replica 0 dies on its 7th decode step; three transient
    # submit faults land anywhere (failover absorbs them)
    death = faults.arm("engine.decode", after=6, times=1,
                       only=lambda engine=None, **_: engine is replicas[0])
    flaky_submit = faults.arm("replica.submit", rate=0.25, seed=seed + 2,
                              times=3)

    rs = np.random.RandomState(seed)
    outcomes = {"ok": 0, "overloaded": 0, "unavailable": 0, "deadline": 0,
                "cancelled": 0, "injected": 0}
    bad_front_door = []
    bad_stream = []

    def run_wave(n, deadlines=True):
        streams = []
        for i in range(n):
            plen = int(rs.randint(1, max_prompt + 1))
            prompt = rs.randint(1, 60, (plen,)).tolist()
            kw = dict(max_new_tokens=int(rs.randint(2, 12)))
            if deadlines and i % 7 == 3:
                kw["deadline"] = 0.004  # tight: expiry is an API error
            try:
                streams.append(rset.submit(prompt, **kw))
            except Overloaded:
                outcomes["overloaded"] += 1
            except ReplicaUnavailable:
                outcomes["unavailable"] += 1
            except Exception as e:  # non-API escape = violation
                bad_front_door.append(repr(e))
        for s in streams:
            try:
                s.result(timeout=120)
                outcomes["ok"] += 1
            except DeadlineExceeded:
                outcomes["deadline"] += 1
            except StreamCancelled:
                outcomes["cancelled"] += 1
            except InjectedFault:
                outcomes["injected"] += 1  # the scheduled replica death
            except Exception as e:
                bad_stream.append(repr(e))

    run_wave(n_requests)
    healthy_after_soak = list(rset.healthy_replicas)
    faults.disarm("engine.decode")
    faults.disarm("replica.submit")
    # self-healing moment: the schedule is exhausted; transiently-evicted
    # replicas rejoin via the backoff-paced prober (the permanently dead
    # one keeps failing its probe and stays quarantined)
    _wait_until(lambda: rset.healthy_replicas, timeout=20)
    healthy_after_heal = list(rset.healthy_replicas)
    if not healthy_after_heal:
        violations.append("serve: no replica rejoined after the fault "
                          "schedule exhausted (prober never healed the set)")
    pre_final_ok = outcomes["ok"]
    run_wave(max(8, n_requests // 4), deadlines=False)
    final_ok = outcomes["ok"] - pre_final_ok

    if bad_front_door:
        violations.append(f"serve: non-API front-door errors: "
                          f"{bad_front_door[:3]}")
    if bad_stream:
        violations.append(f"serve: non-API stream errors: {bad_stream[:3]}")
    if death.fired < 1:
        violations.append("serve: the replica-death fault never fired")
    if final_ok < max(8, n_requests // 4):
        violations.append(
            f"serve: only {final_ok} of the post-fault wave succeeded — "
            "the set did not heal around the dead replica")
    if outcomes["ok"] == 0:
        violations.append("serve: nothing succeeded during the soak")

    rset.close()
    pages_leaked = {f"r{i}": e.pages_in_use for i, e in enumerate(replicas)
                    if e.pages_in_use}
    if pages_leaked:
        violations.append(f"serve: leaked KV pages after close: "
                          f"{pages_leaked}")

    # -------------------------------------------------------- watchdog ----
    wd_engine = build_engine(step_cost_ms=0.0, stall_timeout=0.2)
    faults.arm("engine.decode", latency=1.0, times=1,
               only=lambda engine=None, **_: engine is wd_engine)
    stalled = wd_engine.submit([1, 2, 3], max_new_tokens=8)
    try:
        stalled.result(timeout=60)
        violations.append("watchdog: a wedged step completed a stream "
                          "instead of stalling it")
    except StallError:
        pass
    except Exception as e:
        violations.append(f"watchdog: wrong stall error {e!r}")
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    faults.reset()
    wd_engine.close(timeout=30)
    if wd_engine.pages_in_use:
        violations.append("watchdog: stalled engine leaked KV pages")

    # ----------------------------------------------- speculative leg ----
    # PR 10: a draft-step fault mid-speculation honours the engine's
    # step contract — the in-flight streams fail with the INJECTED
    # error through the API (a consumed donated cache cannot be
    # retried; nothing hangs, nothing escapes untyped) and BOTH models'
    # page lanes drain to zero, per-owner, not just in aggregate.
    spec_engine = GenerationEngine(
        model, params, max_slots=slots, max_len=max_len,
        max_prompt_len=max_prompt, max_queue=4 * n_requests,
        page_size=8, seed=seed, metrics=ServingMetrics(),
        speculate=(model, params, 2))
    spec_engine.warmup()
    clean = spec_engine.generate([1, 2, 3], max_new_tokens=4, timeout=60)
    if len(clean) != 4:
        violations.append("speculative: clean pre-fault generation came "
                          "back short")
    faults.arm("engine.draft", after=1, times=1,
               only=lambda engine=None, **_: engine is spec_engine)
    sstreams = []
    for _ in range(3):
        plen = int(rs.randint(1, max_prompt + 1))
        try:
            sstreams.append(spec_engine.submit(
                rs.randint(1, 60, (plen,)).tolist(),
                max_new_tokens=int(rs.randint(6, 12))))
        except RuntimeError:
            # the injected draft fault already stopped the engine:
            # refusing new submits IS the step contract — the streams
            # submitted before the fault carry the invariant checks
            break
    spec_injected = 0
    for s in sstreams:
        try:
            s.result(timeout=60)
        except InjectedFault:
            spec_injected += 1
        except Exception as e:
            violations.append(f"speculative: non-API stream error {e!r}")
    faults.disarm("engine.draft")
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    if spec_injected < 1:
        violations.append("speculative: the mid-speculation draft fault "
                          "never failed a stream")
    spec_target_pages = spec_engine._pool.in_use_by("target")
    spec_draft_pages = spec_engine._pool.in_use_by("draft")
    spec_engine.close()
    if spec_engine.pages_in_use or spec_target_pages or spec_draft_pages:
        violations.append(
            f"speculative: KV pages leaked after the draft fault "
            f"(target={spec_target_pages}, draft={spec_draft_pages}, "
            f"total={spec_engine.pages_in_use})")

    # ---------------------------------------------- prefix-cache leg ----
    # PR 12: a fault injected between prefix attach (cache references
    # taken, fresh pages reserved) and the first decode step fails the
    # stream with the INJECTED error and releases every refcount — the
    # drain gate extends to shared_pages == 0 after the terminal
    # eviction, so a crashed prefix-caching engine can never strand
    # pages behind the index.
    faults.reset()  # the speculative leg's firings are already counted
    pfx_engine = GenerationEngine(
        model, params, max_slots=slots, max_len=max_len,
        max_prompt_len=2 * 8,   # one full shared page + divergent tail
        max_queue=4 * n_requests,
        kernels=kernels, page_size=8, seed=seed,
        metrics=ServingMetrics(), prefix_cache=True)
    pfx_engine.warmup()
    shared_prompt = rs.randint(1, 60, (8,)).tolist()   # one full page + tail
    pfx_engine.generate(shared_prompt + [3], max_new_tokens=3, timeout=60)
    pfx_clean = pfx_engine.generate(shared_prompt + [4], max_new_tokens=3,
                                    timeout=60)
    pfx_snap = pfx_engine.metrics.snapshot()
    if len(pfx_clean) != 3 or pfx_snap["prefix_hits"] < 1:
        violations.append(
            f"prefix: clean shared-prefix serving broke before the fault "
            f"(hits={pfx_snap['prefix_hits']}, out={len(pfx_clean)})")
    if pfx_engine.shared_pages < 1:
        violations.append("prefix: retirement published no shared pages")
    faults.arm("engine.prefix_attach", nth=1, times=1,
               only=lambda engine=None, **_: engine is pfx_engine)
    pfx_injected = 0
    try:
        pfx_engine.generate(shared_prompt + [5], max_new_tokens=3,
                            timeout=60)
        violations.append("prefix: the attach fault never failed a stream")
    except InjectedFault:
        pfx_injected = 1
    except Exception as e:
        violations.append(f"prefix: non-API stream error {e!r}")
    faults.disarm("engine.prefix_attach")
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    faults.reset()
    pfx_shared_after = pfx_engine.shared_pages
    pfx_engine.close()
    if pfx_shared_after or pfx_engine.pages_in_use \
            or pfx_engine.shared_pages:
        violations.append(
            f"prefix: pages leaked after the attach fault "
            f"(shared={pfx_shared_after}, in_use="
            f"{pfx_engine.pages_in_use}) — refcounts must release and "
            f"shared_pages drain to 0")

    # --------------------------------------------- KV-tier leg (PR 18) ----
    # PR 18: faults at the host-tier copy sites, per page-block copy. A
    # kv.offload fault drops ONLY the affected entry — the page evicts
    # plainly and the stream that triggered the eviction is untouched;
    # a kv.restore fault degrades the matched chain to a miss and the
    # request re-prefills the SAME bits; and after both schedules BOTH
    # tiers' gauges drain to zero — nothing strands on either side of
    # the tier boundary.
    kv_host_pages = args.host_pages or 16
    kv_ref = GenerationEngine(
        model, params, max_slots=2, max_len=max_len, max_prompt_len=20,
        max_queue=4 * n_requests, kernels=kernels, page_size=8,
        seed=seed, metrics=ServingMetrics())
    kv_ref.warmup()
    kv_engine = GenerationEngine(
        model, params, max_slots=2, max_len=max_len, max_prompt_len=20,
        max_queue=4 * n_requests, kernels=kernels, page_size=8,
        seed=seed, num_pages=4, metrics=ServingMetrics(),
        prefix_cache=True, host_pages=kv_host_pages)
    kv_engine.warmup()
    kv_rs = np.random.RandomState(seed + 9)
    # three 2-page prefix families against a 4-page pool: every later
    # admission evicts the previous family, so each pass offloads (or,
    # under the armed fault, drops) its predecessors' pages
    kv_families = [kv_rs.randint(1, 60, (16,)).tolist() for _ in range(3)]

    def kv_pass(tail):
        outs = []
        for f in kv_families:
            p = f + tail
            got = kv_engine.generate(p, max_new_tokens=3, timeout=60)
            if got != kv_ref.generate(p, max_new_tokens=3, timeout=60):
                violations.append(
                    f"kvtier: stream bits diverged from the no-host "
                    f"reference on tail {tail}")
            outs.append(got)
        return outs

    faults.arm("kv.offload",
               only=lambda engine=None, **_: engine is kv_engine)
    kv_pass([1, 2])
    kv_host = kv_engine.host_store
    if kv_host.offloaded_pages or kv_host.pages:
        violations.append(
            f"kvtier: pages reached the host tier through a faulted "
            f"offload copy (offloaded={kv_host.offloaded_pages}, "
            f"resident={kv_host.pages})")
    kv_offload_dropped = kv_host.dropped_pages
    if kv_offload_dropped < 1:
        violations.append("kvtier: the armed offload fault never "
                          "dropped an entry")
    faults.disarm("kv.offload")
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    faults.reset()
    kv_pass([3, 4])          # clean pass: re-publish, offload for real
    if kv_host.offloaded_pages < 1:
        violations.append("kvtier: no pages offloaded once the fault "
                          "was disarmed")
    faults.arm("kv.restore", nth=1, times=1,
               only=lambda engine=None, kind=None, **_:
               engine is kv_engine and kind == "prefix")
    kv_pass([5, 6])          # first revisit degrades to a miss, bits intact
    faults.disarm("kv.restore")
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    faults.reset()
    kv_restored = kv_host.restored_pages
    kv_degraded = kv_host.dropped_pages - kv_offload_dropped
    if kv_degraded < 1:
        violations.append("kvtier: the armed restore fault never "
                          "degraded a host entry to a miss")
    kv_ref.close()
    kv_engine.close()
    kv_host_after = kv_host.pages
    if kv_engine.pages_in_use or kv_engine.shared_pages or kv_host_after:
        violations.append(
            f"kvtier: pages stranded after the fault schedule "
            f"(device={kv_engine.pages_in_use}, "
            f"shared={kv_engine.shared_pages}, host={kv_host_after}) — "
            f"both tiers must drain to zero")

    # -------------------------------------------- disaggregation leg (PR 15) ----
    # A fault at the engine.page_handoff site (mid-handoff, after the
    # prefill finished but before the decode role owns the pages) fails
    # ONLY that stream with the injected error and drains BOTH role
    # pools' per-owner gauges to zero — proven on the local path (adopt
    # stage, parent injector) and the RPC path (export stage armed in
    # the CHILD over the fault RPCs), with the fabric serving the same
    # bits as a monolithic engine before and after each fault.
    dz_ref = build_engine(step_cost_ms=0.0)
    dz_prompt = rs.randint(1, 60, (6,)).tolist()
    dz_want = dz_ref.generate(dz_prompt, max_new_tokens=5, timeout=60)
    dz_ref.close()

    dz = DisaggregatedEngine(
        model, params, max_slots=slots, max_len=max_len,
        max_prompt_len=max_prompt, max_queue=4 * n_requests,
        kernels=kernels, page_size=8, seed=seed,
        metrics=ServingMetrics())
    dz.warmup()
    dz_injected = 0
    if dz.generate(dz_prompt, max_new_tokens=5, timeout=60) != dz_want:
        violations.append("disagg: local handoff diverged from the "
                          "monolithic bits")
    faults.arm("engine.page_handoff", nth=1, times=1,
               only=lambda key=None, **ctx: ctx.get("stage") == "adopt")
    try:
        dz.generate(dz_prompt, max_new_tokens=5, timeout=60)
        violations.append("disagg: the adopt fault never failed a stream")
    except InjectedFault:
        dz_injected += 1
    except Exception as e:
        violations.append(f"disagg: non-API stream error {e!r}")
    faults.disarm("engine.page_handoff")
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    faults.reset()
    if dz.generate(dz_prompt, max_new_tokens=5, timeout=60) != dz_want:
        violations.append("disagg: post-fault local serving diverged")
    dz_owner_gauges = (dz.prefill_engine._pool.snapshot()["by_owner"],
                       dz.decode_engine._pool.snapshot()["by_owner"])
    dz.close()
    if dz.prefill_engine.pages_in_use or dz.decode_engine.pages_in_use \
            or any(dz_owner_gauges):
        violations.append(
            f"disagg: pages leaked after the adopt fault (owner gauges "
            f"prefill/decode = {dz_owner_gauges}) — a failed handoff "
            f"must release both sides")

    dz_child_fired = dz_child_recorded = 0
    dz_remote_pages = None
    dz_worker = start_replica_process(
        "bigdl_tpu.serving.disagg:chaos_prefill_worker", name="dzprefill")
    rdz = DisaggregatedEngine(
        model, params, remote_prefill=dz_worker, max_slots=slots,
        max_len=max_len, max_prompt_len=16, max_queue=4 * n_requests,
        kernels=kernels, page_size=8, seed=seed,
        metrics=ServingMetrics())
    try:
        rdz.decode_engine.warmup()
        if rdz.generate(dz_prompt, max_new_tokens=5,
                        timeout=120) != dz_want:
            violations.append("disagg: RPC handoff diverged from the "
                              "monolithic bits")
        dz_worker.arm_fault("engine.page_handoff", nth=1, times=1)
        try:
            rdz.generate(dz_prompt, max_new_tokens=5, timeout=120)
            violations.append("disagg: the remote export fault never "
                              "failed a stream")
        except InjectedFault:
            dz_injected += 1
        except Exception as e:
            violations.append(f"disagg: non-API RPC stream error {e!r}")
        # child-side reconciliation: the CHILD's injector history must
        # match its own flight recorder (the fault fired over there)
        dz_child_fired = sum(v["fired"]
                             for v in dz_worker.fault_snapshot().values())
        dz_child_recorded = dz_worker.recorder_count("fault.fired")
        dz_worker.reset_faults()
        if dz_child_fired != 1 or dz_child_fired != dz_child_recorded:
            violations.append(
                f"disagg: child injector/recorder disagree "
                f"(fired={dz_child_fired}, recorded={dz_child_recorded})")
        if rdz.generate(dz_prompt, max_new_tokens=5,
                        timeout=120) != dz_want:
            violations.append("disagg: post-fault RPC serving diverged")
        dz_remote_pages = dz_worker.remote_snapshot().get("pages_in_use")
        if dz_remote_pages or rdz.decode_engine._pool.in_use:
            violations.append(
                f"disagg: pages leaked across the wire (remote_gauge="
                f"{dz_remote_pages}, decode="
                f"{rdz.decode_engine._pool.in_use})")
    finally:
        rdz.close()

    # ------------------------------------------------- network leg (PR 14) ----
    # The cross-process fabric under its own fault sites plus one REAL
    # SIGKILL. Part one: a hedged ReplicaSet mixing an in-process engine
    # with a RemoteReplica hosting the SAME engine build behind an
    # in-thread ReplicaServer serves a wave while rpc.connect /
    # rpc.send / rpc.recv_delay fire on schedule — the front door
    # stays taxonomy-only, responses over the wire are bit-identical
    # to in-process ones, and both engines' KV pages drain through the
    # wire's close. Part two: a child process is SIGKILLed mid-traffic
    # and rejoins via revive(), with the child's OWN injector history
    # reconciled against its flight recorder over the fault RPCs.
    net_engine = build_engine()
    net_server = ReplicaServer(net_engine, name="net")
    faults.arm("rpc.connect", nth=1, times=1, exc=ConnectionError)
    net_remote = RemoteReplica(
        (net_server.host, net_server.port), name="net",
        connect_policy=RetryPolicy(max_attempts=4, base_delay=0.02,
                                   jitter=0.0,
                                   transient=(OSError, ConnectionError)))
    local_engine = build_engine()
    nset = ReplicaSet([local_engine, net_remote], max_failures=8,
                      hedge=True, hedge_delay=0.05, name="net")
    # wire-vs-process bit-identity before any scheduled failure: the
    # same prompt through the remote proxy and the local twin engine
    # (this first call also dials the connection, through the armed
    # rpc.connect fault — the RetryPolicy must have healed it)
    ident_prompt = rs.randint(1, 60, (max_prompt,)).tolist()
    over_wire = list(net_remote.predict(ident_prompt, timeout=60,
                                        max_new_tokens=6))
    in_proc = list(local_engine.generate(ident_prompt, max_new_tokens=6,
                                         timeout=60))
    if net_remote._policy.snapshot()["retries"] < 1:
        violations.append("net: the injected connect fault never forced "
                          "a policy-paced reconnect")
    if over_wire != in_proc:
        violations.append(
            f"net: remote responses diverge from the single-process run "
            f"({over_wire} != {in_proc})")
    faults.arm("rpc.send", nth=2, times=2, exc=OSError)
    faults.arm("rpc.recv_delay", rate=0.25, seed=seed + 3, times=3,
               latency=0.02)
    net_outcomes = {"ok": 0, "deadline": 0, "transport": 0, "api": 0}
    net_bad = []
    for i in range(16):
        plen = int(rs.randint(1, max_prompt + 1))
        prompt = rs.randint(1, 60, (plen,)).tolist()
        kw = dict(max_new_tokens=int(rs.randint(2, 8)))
        if i % 5 == 3:
            kw["deadline"] = 0.004  # expiry is an API error over the wire
        try:
            nset.submit(prompt, **kw).result(timeout=60)
            net_outcomes["ok"] += 1
        except DeadlineExceeded:
            net_outcomes["deadline"] += 1
        except TransportError:
            # taxonomy: a response leg lost mid-flight indicts the
            # replica (eviction accrual), never the caller's API
            net_outcomes["transport"] += 1
        except (Overloaded, ReplicaUnavailable, StreamCancelled,
                InjectedFault):
            net_outcomes["api"] += 1
        except Exception as e:  # non-taxonomy escape = violation
            net_bad.append(repr(e))
    if net_bad:
        violations.append(f"net: non-API errors escaped the fabric: "
                          f"{net_bad[:3]}")
    if net_outcomes["ok"] < 8:
        violations.append(f"net: too few successes under rpc faults "
                          f"({net_outcomes})")
    net_transport = net_remote.snapshot()
    net_remote_pages = net_remote.remote_snapshot().get("pages_in_use")
    net_hedges = {"launched": nset.hedges_launched, "won": nset.hedges_won}
    fired_expected += sum(v["fired"] for v in faults.snapshot().values())
    faults.reset()
    nset.close()   # crosses the wire: the remote close drains the server
    net_server.wait_closed(timeout=10)
    net_engine.close()
    if net_engine.pages_in_use or local_engine.pages_in_use \
            or net_remote_pages:
        violations.append(
            f"net: KV pages leaked across the wire (remote_gauge="
            f"{net_remote_pages}, remote_after={net_engine.pages_in_use}, "
            f"local={local_engine.pages_in_use})")

    net_child_fired = net_child_recorded = 0
    sigkill_ok = revive_ok = False
    child = start_replica_process("bigdl_tpu.serving.remote:toy_backend",
                                  name="netchild")
    try:
        # child-side reconciliation over the fault RPCs: a latency-only
        # spec on the server's rpc.peer_kill site fires (sleeps) without
        # killing, and the child's injector history must match its own
        # flight recorder
        child.arm_fault("rpc.peer_kill", nth=1, times=1, latency=0.005)
        child.predict([1, 2], timeout=30)
        net_child_fired = sum(v["fired"]
                              for v in child.fault_snapshot().values())
        net_child_recorded = child.recorder_count("fault.fired")
        if net_child_fired < 1 or net_child_fired != net_child_recorded:
            violations.append(
                f"net: child injector/recorder disagree "
                f"(fired={net_child_fired}, "
                f"recorded={net_child_recorded})")
        child.kill()   # the REAL SIGKILL, mid-serving
        try:
            child.predict([3], timeout=10)
            violations.append("net: a SIGKILLed child answered a request")
        except TransportError:
            sigkill_ok = True
        except Exception as e:
            violations.append(
                f"net: SIGKILL surfaced a non-taxonomy error {e!r}")
        try:
            child.revive(timeout=20)
            revive_ok = list(child.predict([4, 5], timeout=30)) == [8, 10]
        except Exception as e:
            violations.append(f"net: killed child failed to rejoin: {e!r}")
        if not revive_ok:
            violations.append("net: revived child served wrong bits")
    finally:
        child.close(drain=False, timeout=5)

    # ----------------------------------------------------------- drain ----
    _join_threads(("bigdl-", "ckpt-writer", "pipeline-"), timeout=15)
    leftover = own_threads()
    if leftover:
        violations.append(f"drain: bigdl threads still alive: {leftover}")
    shm_leaked = []
    if shm_before is not None:
        shm_leaked = sorted(set(glob.glob(os.path.join(shm_dir, "*")))
                            - shm_before)
        if shm_leaked:
            violations.append(f"drain: leaked shm segments: {shm_leaked}")

    # ------------------------------------------------ flight recorder ----
    # every fault the injector fired must have landed one structured
    # "fault.fired" event — the reconstructability invariant
    fired_recorded = recorder.count("fault.fired") - fired_before
    if fired_recorded != fired_expected:
        violations.append(
            f"recorder: {fired_recorded} fault.fired events recorded but "
            f"the injector fired {fired_expected} — chaos runs must be "
            f"reconstructable from the flight recorder")
    if recorder.count("watchdog.stall") < 1:
        violations.append("recorder: the watchdog stall left no "
                          "flight-recorder event")

    result = {
        "metric": "chaos_soak_pass",
        "value": 0.0 if violations else 1.0,
        "unit": "bool",
        "vs_baseline": None,
        "train_iters": train_iters,
        "train_params_bitwise_match": params_match,
        "train_restored_bitwise_match": restored_match,
        "train_faults_fired": train_fired,
        "serve_requests": n_requests,
        "serve_outcomes": outcomes,
        "serve_healthy_after_soak": healthy_after_soak,
        "serve_healthy_after_heal": healthy_after_heal,
        "serve_final_wave_ok": final_ok,
        "replica_death_fired": death.fired,
        "submit_faults_fired": flaky_submit.fired,
        "speculative_streams_failed": spec_injected,
        "prefix_attach_fault_failed_streams": pfx_injected,
        "prefix_hits": pfx_snap["prefix_hits"],
        "prefix_shared_pages_after_fault": pfx_shared_after,
        "kv_offload_fault_dropped_pages": kv_offload_dropped,
        "kv_restore_fault_degraded_pages": kv_degraded,
        "kv_offloaded_pages": kv_host.offloaded_pages,
        "kv_restored_pages": kv_restored,
        "kv_host_pages_after_close": kv_host_after,
        "disagg_handoff_faults_failed_streams": dz_injected,
        "disagg_child_faults_fired": dz_child_fired,
        "disagg_child_faults_recorded": dz_child_recorded,
        "disagg_remote_pages_gauge": dz_remote_pages,
        "net_outcomes": net_outcomes,
        "net_transport": net_transport,
        "net_hedges": net_hedges,
        "net_remote_pages_gauge": net_remote_pages,
        "net_child_faults_fired": net_child_fired,
        "net_child_faults_recorded": net_child_recorded,
        "net_sigkill_transport_error": sigkill_ok,
        "net_sigkill_rejoined": revive_ok,
        "recorder_fault_events": fired_recorded,
        "recorder_fault_expected": fired_expected,
        "threads_leftover": leftover,
        "shm_leaked": shm_leaked,
        "violations": violations,
        "seed": seed,
        "smoke": smoke,
        "duration_s": round(time.perf_counter() - t_start, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "timing": "invariant soak, not a throughput measurement; all "
                  "fault schedules are pure functions of --chaos-seed",
    }
    _write_metrics_out(args, {"serving": replicas[0].metrics,
                              "speculative": spec_engine.metrics,
                              "prefix": pfx_engine._prefix,
                              "kv_host": kv_host,
                              "disagg": dz.metrics,
                              "bench": result})
    print(json.dumps(result))
    if violations:
        # the flight recorder's whole point: a failed soak prints what
        # recently happened, not just which invariant broke
        print("flight recorder (last 40 events):\n"
              + recorder.format_events(last=40), file=sys.stderr)
        raise SystemExit("chaos soak FAILED:\n  - " + "\n  - ".join(violations))


def run_fleet_bench(args):
    """Elastic-fleet benchmark (``--mode fleet``): an OPEN-LOOP load
    harness over the PR-16 autoscaler — Poisson arrivals on an absolute
    schedule (a diurnal ramp, a 3x burst storm, a cool-down), offered
    to a static 1-prefill/1-decode :class:`DisaggregatedFleet` and then
    to the SAME minimum-size fleet with an :class:`AutoscaleController`
    steering per-role :class:`EnginePool` knobs. Open-loop means the
    dispatcher never waits for completions: when the fleet falls
    behind, requests keep landing — queues grow, TTFT blows the budget,
    the bounded queue sheds ``Overloaded`` — exactly the regime a
    closed-loop (concurrency-limited) client can never produce, and the
    regime autoscaling exists for.

    SLO attainment is the fraction of OFFERED requests that complete
    with TTFT <= ``--fleet-ttft-slo-ms`` AND mean ITL <=
    ``--fleet-itl-slo-ms``; a shed or failed request is a miss by
    definition. Kernel costs are modeled (``_FixedCostKernels``: the
    prompt chunk costs on prefill members, the decode step costs on
    decode members — the PR-15 disagg column's pricing), so member
    capacity is arithmetic: a decode member sustains ~slots /
    (new_tokens * step_cost) rps, a prefill member ~1 / (chunks *
    prompt_cost) rps, and the burst is sized to exceed the static
    fleet's capacity while staying inside the autoscaled maxima.

    Mid-burst, the harness SIGKILLs a decode member in effigy (an
    armed ``engine.decode`` fault — the in-process equivalent of a
    dead child) and the controller's heal pass must replace it with
    the front door only ever raising ``Overloaded`` /
    ``ReplicaUnavailable``.

    ``--smoke`` shrinks the phases and gates (the CI step): autoscaled
    burst attainment strictly above static, zero pages stranded on
    either fleet, zero non-taxonomy front-door errors, >= 1 heal,
    asymmetric per-role scaling visible in the captured size history,
    and every bigdl thread / child process retired."""
    import multiprocessing
    import threading

    from bigdl_tpu import faults
    from bigdl_tpu.nn.layers.attention import Transformer
    from bigdl_tpu.serving import (
        AutoscaleController,
        DisaggregatedFleet,
        EnginePool,
        GenerationEngine,
        Overloaded,
        PagedDecodeKernels,
        ReplicaUnavailable,
        ScalingPolicy,
        ServingMetrics,
    )
    from bigdl_tpu.serving.autoscale import above, all_of, any_of, below

    t_start = time.perf_counter()
    smoke = args.smoke
    seed = args.fleet_seed

    # ---- modeled costs and workload shape (capacity is arithmetic) ----
    step_ms = args.step_cost_ms if args.step_cost_ms else 4.0
    prompt_ms = 2.5 * step_ms              # per prompt chunk
    page = 8
    slots = 4
    chunks = 3
    prompt_len = chunks * page             # 24 tokens, 3 chunks
    new_tokens = 24
    max_len = prompt_len + new_tokens
    # per-member capacity: decode ~ slots/(new*step) ~ 41 rps,
    # prefill ~ 1/(chunks*prompt) ~ 33 rps at the defaults
    decode_cap = slots / (new_tokens * step_ms / 1e3)
    prefill_cap = 1.0 / (chunks * prompt_ms / 1e3)

    base_rps = args.fleet_base_rps or 16.0
    burst_x = args.fleet_burst_x
    if smoke:
        ramp_s, burst_s, cool_s = 5.0, 8.0, 6.0
    else:
        ramp_s, burst_s, cool_s = 10.0, 16.0, 10.0
    total_s = ramp_s + burst_s + cool_s
    ttft_slo_ms = args.fleet_ttft_slo_ms
    itl_slo_ms = args.fleet_itl_slo_ms

    model = Transformer(vocab_size=64, hidden_size=32, num_heads=2,
                        filter_size=64, num_hidden_layers=1)
    params, _ = model.init(jax.random.key(0))
    kernels = PagedDecodeKernels(model)   # ONE compiled triple: every
    # member (and every mid-burst scale-up / heal) shares it, so a
    # dynamic spawn compiles nothing
    prefill_k = _FixedCostKernels(kernels, 0.0, prompt_ms / 1e3)
    decode_k = _FixedCostKernels(kernels, step_ms / 1e3, 0.0)
    eng_kw = dict(max_slots=slots, max_len=max_len,
                  max_prompt_len=prompt_len, page_size=page,
                  prefill_chunk=page, max_queue=32)

    def make_role(role):
        k = prefill_k if role == "prefill" else decode_k
        def make():
            return GenerationEngine(
                model, params, role=role, kernels=k,
                metrics=ServingMetrics(recent_window_s=3.0), **eng_kw)
        return make

    rs = np.random.RandomState(seed)
    prompts = [rs.randint(1, 64, (prompt_len,)).tolist()
               for _ in range(32)]

    def rate_at(t):
        if t < ramp_s:                      # diurnal ramp into the day
            return base_rps * (0.3 + 0.7 * t / ramp_s)
        if t < ramp_s + burst_s:            # the 3x storm
            return base_rps * burst_x
        return base_rps                     # evening steady state

    def phase_of(t):
        if t < ramp_s:
            return "ramp"
        if t < ramp_s + burst_s:
            return "burst"
        return "cool"

    def build_schedule():
        # same seed for both legs: bit-identical offered traces
        srs = np.random.RandomState(seed + 1)
        t, out = 0.0, []
        while True:
            t += srs.exponential(1.0 / rate_at(t))
            if t >= total_s:
                return out
            out.append(t)

    schedule = build_schedule()
    allowed_drops = (Overloaded, ReplicaUnavailable)

    def run_leg(fleet, events=()):
        """Dispatch the schedule open-loop, then harvest. ``events``
        is [(t_offset, fn)] fired by the dispatcher as the clock passes
        each offset (the chaos kill rides here)."""
        evq, ei = sorted(events, key=lambda e: e[0]), 0
        pending = []
        t0 = time.perf_counter()
        for i, at in enumerate(schedule):
            while ei < len(evq) and evq[ei][0] <= at:
                evq[ei][1]()
                ei += 1
            delay = t0 + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            rec = {"phase": phase_of(at)}
            try:
                s = fleet.submit(prompts[i % len(prompts)],
                                 max_new_tokens=new_tokens)
            except allowed_drops as e:
                rec["outcome"] = ("overloaded" if isinstance(e, Overloaded)
                                  else "unavailable")
                pending.append((rec, None))
                continue
            except Exception as e:         # taxonomy violation — gated
                rec["outcome"] = f"BAD:{type(e).__name__}"
                pending.append((rec, None))
                continue
            pending.append((rec, s))
        records = []
        for rec, s in pending:
            if s is not None:
                try:
                    s.result(timeout=120)
                except allowed_drops as e:
                    rec["outcome"] = ("overloaded"
                                      if isinstance(e, Overloaded)
                                      else "unavailable")
                except Exception as e:
                    rec["outcome"] = f"BAD:{type(e).__name__}"
                else:
                    rec["outcome"] = "ok"
                    rec["ttft_ms"] = (s.t_first - s.t_submit) * 1e3
                    n = len(s.tokens)
                    rec["itl_ms"] = ((s.t_done - s.t_first) / (n - 1) * 1e3
                                     if n > 1 else 0.0)
            records.append(rec)
        # retirement runs between decode steps; give the loops a beat
        # to hand every page back before the stranding check
        _wait_until(lambda: not fleet.pages_in_use(), timeout=10)
        return records, fleet.pages_in_use()

    def met(rec):
        return (rec["outcome"] == "ok"
                and rec.get("ttft_ms", 1e9) <= ttft_slo_ms
                and rec.get("itl_ms", 1e9) <= itl_slo_ms)

    def attainment(records, phase=None):
        rel = [r for r in records
               if phase is None or r["phase"] == phase]
        if not rel:
            return None
        return round(sum(1 for r in rel if met(r)) / len(rel), 4)

    def pct(vals, q):
        return round(float(np.percentile(vals, q)), 2) if vals else None

    def leg_fields(tag, records):
        ttfts = [r["ttft_ms"] for r in records
                 if r["phase"] == "burst" and "ttft_ms" in r]
        outcomes = {}
        for r in records:
            outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
        return {
            f"{tag}_attainment": attainment(records),
            f"{tag}_attainment_ramp": attainment(records, "ramp"),
            f"{tag}_attainment_burst": attainment(records, "burst"),
            f"{tag}_attainment_cool": attainment(records, "cool"),
            f"{tag}_burst_ttft_p50_ms": pct(ttfts, 50),
            f"{tag}_burst_ttft_p99_ms": pct(ttfts, 99),
            f"{tag}_outcomes": outcomes,
        }

    def own_threads():
        return sorted(t.name for t in threading.enumerate()
                      if t.name.startswith("bigdl-") and t.is_alive())

    # ------------------------------------------------------ static leg ----
    # the same-resource baseline: the autoscaled fleet's MINIMUM sizes,
    # pinned — what you provision when you pay for the valley
    faults.default().reset()
    static_fleet = DisaggregatedFleet(
        make_role("prefill"), make_role("decode"),
        n_prefill=1, n_decode=1, name="fleet_static", warm=True)
    static_records, static_pages = run_leg(static_fleet)
    static_fleet.close()

    # -------------------------------------------------- autoscaled leg ----
    faults.default().reset()
    from bigdl_tpu.obs import MetricsRegistry

    fleet = DisaggregatedFleet(
        make_role("prefill"), make_role("decode"),
        n_prefill=1, n_decode=1, name="fleet", warm=True)
    reg = MetricsRegistry()
    reg.register("fleet", fleet)
    ctrl = AutoscaleController({
        "fleet.prefill": (
            EnginePool(fleet, "prefill", drain_timeout=10.0),
            ScalingPolicy(
                min_replicas=1, max_replicas=2,
                up_when=above("fleet.prefill.queue_depth", 3),
                down_when=below("fleet.prefill.queue_depth", 1),
                breach_up=2, breach_down=8,
                cooldown_up_s=1.0, cooldown_down_s=5.0)),
        "fleet.decode": (
            EnginePool(fleet, "decode", drain_timeout=10.0),
            ScalingPolicy(
                min_replicas=1, max_replicas=3,
                up_when=any_of(
                    above("fleet.decode.queue_depth", 2),
                    above("fleet.decode.page_occupancy", 0.85),
                    above("fleet.decode.itl_recent_p99_ms", itl_slo_ms)),
                down_when=all_of(
                    below("fleet.decode.queue_depth", 1),
                    below("fleet.decode.page_occupancy", 0.5)),
                breach_up=2, breach_down=8,
                cooldown_up_s=1.0, cooldown_down_s=5.0)),
    }, registry=reg, interval_s=0.25)

    heal_spec = {"spec": None}

    def kill_one_decode():
        # the chaos leg: a decode member dies mid-storm; the heal pass
        # must replace it while the front door stays inside the taxonomy
        with fleet._cond:
            serving = [m for m in fleet._members["decode"]
                       if m.healthy and not m.draining and not m.warming]
        if serving:
            victim = serving[0].engine
            heal_spec["spec"] = faults.default().arm(
                "engine.decode", times=1,
                only=lambda engine=None, **kw: engine is victim)

    t0_mono = time.monotonic()
    ctrl.start()
    auto_records, auto_pages = run_leg(
        fleet, events=[(ramp_s + 0.3 * burst_s, kill_one_decode)])
    ctrl.stop()
    ctrl_snap = ctrl.snapshot()
    fleet.close()
    faults.default().reset()

    # ------------------------------------------------------- evidence ----
    sizes = [(round(t - t0_mono, 2), s["fleet.prefill"], s["fleet.decode"])
             for t, s in ctrl.size_history]
    peak_prefill = max((p for _, p, _ in sizes), default=1)
    peak_decode = max((d for _, _, d in sizes), default=1)
    asymmetric = any(p != d for _, p, d in sizes)
    pool_snaps = ctrl_snap["pools"]
    heals = pool_snaps["fleet.decode"]["heals"] \
        + pool_snaps["fleet.prefill"]["heals"]
    scale_ups = pool_snaps["fleet.decode"]["scale_ups"] \
        + pool_snaps["fleet.prefill"]["scale_ups"]
    scale_downs = pool_snaps["fleet.decode"]["scale_downs"] \
        + pool_snaps["fleet.prefill"]["scale_downs"]
    bad_errors = [r["outcome"] for r in static_records + auto_records
                  if r["outcome"].startswith("BAD:")]

    _join_threads("bigdl-", timeout=15)
    leftover = own_threads()
    children = [p.name for p in multiprocessing.active_children()]

    static_att = leg_fields("static", static_records)
    auto_att = leg_fields("autoscaled", auto_records)
    s_burst = static_att["static_attainment_burst"]
    a_burst = auto_att["autoscaled_attainment_burst"]

    violations = []
    if smoke:
        if s_burst is None or a_burst is None or a_burst <= s_burst:
            violations.append(
                f"autoscaled burst attainment {a_burst} must be strictly "
                f"above static {s_burst} — elasticity bought nothing")
        if static_pages or auto_pages:
            violations.append(
                f"stranded KV pages: static={static_pages} "
                f"autoscaled={auto_pages}")
        if bad_errors:
            violations.append(
                f"front door leaked non-taxonomy errors: {bad_errors[:5]}")
        if heals < 1 or not heal_spec["spec"] \
                or heal_spec["spec"].fired < 1:
            violations.append(
                "chaos leg: the killed decode member was never healed "
                f"(heals={heals}, fault_fired="
                f"{heal_spec['spec'].fired if heal_spec['spec'] else 0})")
        if scale_ups < 1 or not asymmetric \
                or (peak_prefill <= 1 and peak_decode <= 1):
            violations.append(
                f"asymmetric scaling not observed (ups={scale_ups}, "
                f"peak prefill={peak_prefill}, decode={peak_decode})")
        if leftover:
            violations.append(f"leaked bigdl threads: {leftover}")
        if children:
            violations.append(f"leaked child processes: {children}")

    result = {
        "metric": "fleet_burst_slo_attainment",
        "value": a_burst,
        "unit": "fraction",
        "vs_baseline": None,
        "static_burst_slo_attainment": s_burst,
        **static_att,
        **auto_att,
        "offered_requests": len(schedule),
        "base_rps": base_rps,
        "burst_x": burst_x,
        "phase_seconds": [ramp_s, burst_s, cool_s],
        "ttft_slo_ms": ttft_slo_ms,
        "itl_slo_ms": itl_slo_ms,
        "step_cost_ms": step_ms,
        "prompt_cost_ms": prompt_ms,
        "prefill_member_capacity_rps": round(prefill_cap, 1),
        "decode_member_capacity_rps": round(decode_cap, 1),
        "scale_ups": scale_ups,
        "scale_downs": scale_downs,
        "bounced_downs": pool_snaps["fleet.decode"]["bounced_downs"]
        + pool_snaps["fleet.prefill"]["bounced_downs"],
        "heals": heals,
        "heal_fault_fired": (heal_spec["spec"].fired
                             if heal_spec["spec"] else 0),
        "peak_prefill_members": peak_prefill,
        "peak_decode_members": peak_decode,
        "asymmetric_scaling_observed": asymmetric,
        "pool_size_history": sizes,
        "pages_stranded_static": static_pages,
        "pages_stranded_autoscaled": auto_pages,
        "non_taxonomy_errors": len(bad_errors),
        "violations": violations,
        "seed": seed,
        "smoke": smoke,
        "duration_s": round(time.perf_counter() - t_start, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "timing": "open-loop Poisson offered load on an absolute "
                  "schedule; attainment counts every offered request",
    }
    _write_metrics_out(args, {"fleet": fleet,
                              "fleet_static": static_fleet,
                              "autoscale": ctrl,
                              "bench": result})
    print(json.dumps(result))
    if violations:
        raise SystemExit("fleet smoke FAILED:\n  - "
                         + "\n  - ".join(violations))


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("train", "serving", "checkpoint",
                                       "pipeline", "chaos", "lm", "fleet"),
                    default="train",
                    help="train = ResNet-50 throughput on the TPU (default; "
                         "fails without one); "
                         "serving = dynamic-batching requests/sec + latency "
                         "percentiles at fixed concurrency; checkpoint = blocking vs async "
                         "save overhead per step + restore latency; "
                         "pipeline = per-stage host input-pipeline img/s "
                         "(produce / augment xN / stage / transfer) + "
                         "overlapped end-to-end ratio vs min stage rate; "
                         "chaos = deterministic fault-injection soak over "
                         "train-with-checkpoints + serve-with-replicas "
                         "(bit-identical recovery, API-only front-door "
                         "errors, zero resource leaks); "
                         "lm = transformer forward/decode tokens/sec + "
                         "empirical MFU (the MXU-heavy workload the MFU "
                         "north star describes), with a --quantize int8 "
                         "A/B leg; "
                         "fleet = open-loop Poisson load (diurnal ramp + "
                         "3x burst storm) against an SLO-driven autoscaled "
                         "DisaggregatedFleet vs the same-resource static "
                         "fleet — reports SLO attainment vs offered load, "
                         "with a mid-burst chaos kill + heal (runs "
                         "directly, no supervisor)")
    ap.add_argument("--concurrency", type=int, default=32,
                    help="serving: concurrent client threads")
    ap.add_argument("--requests", type=int, default=0,
                    help="serving: total requests (0 = auto)")
    ap.add_argument("--serve-max-batch", type=int, default=8,
                    help="serving: DynamicBatcher max_batch_size")
    ap.add_argument("--serve-max-wait-ms", type=float, default=2.0,
                    help="serving: DynamicBatcher batch window")
    ap.add_argument("--generate", action="store_true",
                    help="serving: generation sub-mode — continuous-"
                         "batching GenerationEngine tokens/sec + TTFT "
                         "p50/p99 vs static run-to-completion batching "
                         "on a mixed-length workload")
    ap.add_argument("--serve-slots", type=int, default=8,
                    help="serving --generate: engine slot-table size")
    ap.add_argument("--page-size", type=int, default=16,
                    help="serving --generate: KV-cache page size (tokens "
                         "per page in the paged block-table pool)")
    ap.add_argument("--tp", type=int, default=1,
                    help="serving --generate: tensor-parallel degree — the "
                         "engine runs sharded over a tp-device mesh "
                         "(Megatron pspecs, KV pools sharded on heads); "
                         "the static baseline stays single-device, so the "
                         "mismatch gate checks sharded bit-identity")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving --generate: replica count — R engines on "
                         "disjoint device groups behind a ReplicaSet vs one "
                         "engine at the same per-step cost; --smoke gates "
                         "replicated tokens/sec >= 1.5x single-replica")
    ap.add_argument("--step-cost-ms", type=float, default=None,
                    help="serving --generate --replicas: fixed per-kernel-"
                         "call cost standing in for a chip's step time "
                         "(default: 8 ms under --smoke with replicas > 1, "
                         "else 0 — raw wall clock)")
    ap.add_argument("--sample", action="store_true",
                    help="serving --generate: sample (temperature 0.8, "
                         "top-k 40, top-p 0.95) instead of greedy — runs "
                         "inside the jitted step; seeded per request, so "
                         "the continuous-vs-static mismatch gate still "
                         "applies")
    ap.add_argument("--speculate", type=int, default=0,
                    help="serving --generate: add the speculative-decoding "
                         "column — a draft-verified engine proposing K "
                         "tokens per round vs the plain paged engine on "
                         "the same workload at fixed per-model step costs "
                         "(--step-cost-ms for the target, --draft-cost-ms "
                         "for the draft); --smoke gates >= 1.5x tokens/sec "
                         "at the modeled cost ratio with zero greedy "
                         "mismatches and compile-once intact")
    ap.add_argument("--draft-cost-ms", type=float, default=2.0,
                    help="serving --generate --speculate: fixed per-call "
                         "cost of one draft decode step (the modeled "
                         "cheap-draft cost — default 2 ms vs the 24 ms "
                         "default target step, a ~12x-smaller distilled "
                         "draft; the target verify runs at "
                         "--step-cost-ms)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="serving --generate: add the shared-prefix replay "
                         "column — ONE 3-page system prompt x N requests "
                         "through a prefix-caching engine vs cache-off at "
                         "a fixed modeled prompt-kernel cost; --smoke "
                         "gates hit-rate >= 0.9, >= 2x fewer chunk/"
                         "prefill invocations, TTFT p50 <= 0.8x off, and "
                         "zero output mismatches (cache on/off must be "
                         "bit-identical)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="serving --generate: add the prefill/decode "
                         "disaggregation column — the same prompt-heavy "
                         "1:1 short:long mix through a monolithic engine "
                         "vs a DisaggregatedEngine (dedicated prefill and "
                         "decode roles, finished KV pages handed off "
                         "between pools) at equal modeled step/prompt "
                         "costs; --smoke gates decode ITL p99 <= 0.7x "
                         "monolithic, zero output mismatches (the handoff "
                         "must be bit-exact), and drained role pools")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="serving --generate: add the KV-tier column — a "
                         "prefix working set ~10x the device pool replayed "
                         "twice through a host-tier engine (HostPageStore "
                         "of this many pages beneath a 4-page device pool) "
                         "vs the same engine with no host tier; --smoke "
                         "gates effective hit-rate > 0, restored-prefix "
                         "TTFT p50 < full re-prefill TTFT p50, zero "
                         "output mismatches (offload->restore must be "
                         "bit-identical), and both tiers drained at close; "
                         "--mode chaos: arm kv.offload/kv.restore over the "
                         "same replay and gate both tiers draining to zero "
                         "under injected copy faults")
    ap.add_argument("--async-sched", action="store_true",
                    help="serving --generate: add the async-scheduling "
                         "column (PR 19) — the same workload slice through "
                         "a sync engine vs an async_scheduling=True engine "
                         "over a modeled device whose step cost is paid at "
                         "MATERIALIZATION (dispatch returns immediately, "
                         "like real async dispatch) plus a fixed per-step "
                         "host cost on the loop thread; --smoke gates zero "
                         "output mismatches (async must be byte-exact), "
                         "step_overlap_frac > 0.5, and async >= 1.2x sync "
                         "tokens/sec at the default 8 ms step / 3 ms host")
    ap.add_argument("--host-cost-ms", type=float, default=3.0,
                    help="--async-sched: modeled per-step HOST cost "
                         "(scheduling, delivery, stream pushes), slept on "
                         "the engine loop thread — the share async "
                         "scheduling folds into the in-flight step's "
                         "window and sync pays serially")
    ap.add_argument("--grammar", choices=("json", "regex"), default=None,
                    help="serving --generate: add the structured-"
                         "generation column (PR 20) — the same prompts "
                         "constrained by a token-level grammar automaton "
                         "(json: an enum+boolean tool-call schema; regex: "
                         "a fixed-length id pattern) through the same "
                         "kernels, plus a speculative constrained-vs-"
                         "unconstrained acceptance-rate A/B; --smoke "
                         "gates parse rate 1.0 on both constrained legs, "
                         "zero engine-vs-static and speculative-vs-plain "
                         "mismatches, and compile-once (the mask is data "
                         "riding the per-slot bias argument)")
    ap.add_argument("--kv-dtype", choices=("fp32", "bf16", "int8"),
                    default="fp32",
                    help="serving --generate: KV page-pool storage dtype. "
                         "int8 stores pages with per-token fp32 scale "
                         "pools and adds the capacity-at-fixed-bytes "
                         "column vs bf16 (--smoke gates it >= 1.8x, scale "
                         "pools priced into the budget)")
    ap.add_argument("--quantize", choices=("none", "int8"), default="none",
                    help="serving --generate / lm: int8 post-training "
                         "quantization of the GEMM weights "
                         "(per-output-channel scales, s8 x s8 -> s32 "
                         "dot_general — the MXU's ~1.9x-over-bf16 path); "
                         "both schedulers quantize identically, so the "
                         "mismatch gate covers the quantized tier")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="chaos: root seed of every fault schedule (the "
                         "soak replays exactly for a given seed)")
    ap.add_argument("--chaos-iters", type=int, default=0,
                    help="chaos: training iterations per leg (0 = auto)")
    ap.add_argument("--chaos-requests", type=int, default=0,
                    help="chaos: serving requests in the fault wave "
                         "(0 = auto)")
    ap.add_argument("--fleet-base-rps", type=float, default=0.0,
                    help="fleet: steady offered arrival rate in req/s "
                         "(0 = auto: 16 — the burst is --fleet-burst-x "
                         "times this, sized past one member's modeled "
                         "capacity)")
    ap.add_argument("--fleet-burst-x", type=float, default=3.0,
                    help="fleet: burst-storm multiplier over the base "
                         "rate")
    ap.add_argument("--fleet-ttft-slo-ms", type=float, default=750.0,
                    help="fleet: per-request TTFT budget for SLO "
                         "attainment")
    ap.add_argument("--fleet-itl-slo-ms", type=float, default=50.0,
                    help="fleet: per-request mean inter-token-latency "
                         "budget for SLO attainment")
    ap.add_argument("--fleet-seed", type=int, default=7,
                    help="fleet: arrival-schedule seed (both legs replay "
                         "the identical offered trace)")
    ap.add_argument("--ckpt-iters", type=int, default=20,
                    help="checkpoint: timed steps per loop")
    ap.add_argument("--ckpt-save-every", type=int, default=5,
                    help="checkpoint: save interval in steps")
    ap.add_argument("--ckpt-depth", type=int, default=8,
                    help="checkpoint: resnet depth on non-TPU backends "
                         "(TPU always runs the bench ResNet-50)")
    ap.add_argument("--pipeline-workers", type=int, default=8,
                    help="pipeline: max worker count for the augment pool "
                         "(the sweep measures 1/2/4/8 up to this)")
    ap.add_argument("--smoke", action="store_true",
                    help="pipeline: small CPU run that exits nonzero "
                         "unless the JSON parses and end-to-end >= 0.8x "
                         "the achievable stage bound; serving --generate: "
                         "exits nonzero unless continuous batching >= 1.5x "
                         "static tokens/sec AND paged KV admits >= 2x the "
                         "dense concurrent sequences at a fixed KV budget "
                         "(the CI gates)")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="all modes: dump an obs.MetricsRegistry JSON "
                         "collect() over everything the run touched "
                         "(serving/pages/timeline/faults/flight recorder "
                         "+ the result line) to PATH at end of run — the "
                         "machine-readable artifact CI uploads from the "
                         "smoke steps")
    ap.add_argument("--batch", type=int, default=0, help="0 = auto")
    ap.add_argument("--long", type=int, default=20,
                    help="train: steps per timed window")
    ap.add_argument("--no-host-pipeline", dest="host_pipeline",
                    action="store_false", default=True,
                    help="skip the data->device fed-throughput measurement "
                         "(on by default — the reference's canonical metric "
                         "is pipeline-fed, DistriOptimizer.scala:410-417)")
    return ap.parse_args(argv)


def run_bench(args):
    from bigdl_tpu.core.engine import enable_compile_cache
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.optim_method import SGD

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py --mode train measures a TPU; JAX found "
            f"{device.platform!r}")
    peak = spec_peak(device.device_kind)["bf16_flops"]
    enable_compile_cache()
    batch = args.batch or 128
    class_num = 1000
    compute_dtype = jnp.bfloat16

    # HWIO kernel storage: bit-identical math, saves the per-step OIHW
    # layout staging around the fused conv+SGD kernels. BIGDL_STEM=s2d
    # swaps the stem for the space-to-depth fold (mathematically
    # identical; A/B knob)
    model = resnet.build_imagenet(50, class_num, kernel_format="HWIO",
                                  stem_s2d=os.environ.get("BIGDL_STEM") == "s2d")
    criterion = CrossEntropyCriterion()
    method = SGD(learning_rate=0.1, momentum=0.9)

    carry = model.init(jax.random.key(0))
    carry = (*carry, method.init_state(carry[0]))
    x = jnp.asarray(np.random.rand(batch, 3, 224, 224), compute_dtype)
    y = jnp.asarray(np.random.randint(0, class_num, (batch,)), jnp.int32)

    # same resident batch each step, like DistriOptimizerPerf's dummy data;
    # the carried (donated) params make the steps dependency-chained
    step = jax.jit(build_step(model, criterion, method), donate_argnums=0)
    # experimentation hook: JSON dict of TPU compiler options, passed via
    # lower().compile(compiler_options=...)
    copts = json.loads(os.environ.get("BIGDL_BENCH_COMPILER_OPTS") or "null")
    if copts:
        step = step.lower(carry, (x, y)).compile(compiler_options=copts)

    # warm-up: compile, and the sanity check — an untrained 1000-way
    # classifier's CE must be ~ln(1000)
    carry, loss = step(carry, (x, y))
    first_loss = float(loss)
    expect = math.log(class_num)
    assert abs(first_loss - expect) < 1.0, (
        f"first-step loss {first_loss:.3f} is not ~ln({class_num})={expect:.3f}: "
        "the benchmark model is not computing a real cross-entropy"
    )
    for _ in range(3):
        carry, loss = step(carry, (x, y))
    jax.block_until_ready(loss)

    n_steps, windows = args.long, []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n_steps):
            carry, loss = step(carry, (x, y))
        jax.block_until_ready(loss)
        windows.append((time.perf_counter() - t0) / n_steps)
    dt_step = float(np.median(windows))
    imgs_per_sec = batch / dt_step  # single chip: per-chip == total

    step_flops_per_img = 3 * 4.089e9  # fwd 4.089 GFLOP/img @224; train ~3x
    mfu = imgs_per_sec * step_flops_per_img / peak
    assert 0.0 < mfu <= 1.0, (
        f"MFU {mfu:.3f} outside (0, 1]: the timing is broken "
        f"({imgs_per_sec * step_flops_per_img / 1e12:.1f} TFLOP/s against a "
        f"published peak of {peak / 1e12:.0f})")

    host = {}
    if args.host_pipeline:
        host_rate = run_host_pipeline(
            model, criterion, method, batch, 2 * n_steps, compute_dtype)
        # measured host->device bandwidth (the wall for any host-fed mode)
        probe = (np.random.rand(batch, 3, 224, 224) * 255).astype(np.uint8)
        fetch = jax.jit(lambda a: jnp.float32(a).sum())
        fetch(jax.device_put(probe)).block_until_ready()  # compiles cast+sum
        xfers = []
        for _ in range(5):
            t0 = time.perf_counter()
            fetch(jax.device_put(probe)).block_until_ready()
            xfers.append(time.perf_counter() - t0)
        host = {"host_pipeline_images_per_sec": round(host_rate, 2),
                "host_to_device_MBps": round(
                    probe.nbytes / float(np.median(xfers)) / 1e6, 1)}

    result = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(imgs_per_sec, 2),
        **host,
        "unit": "images/sec/chip",
        "vs_baseline": round(imgs_per_sec / 3000.0, 4),
        "batch": batch,
        "iters": n_steps,
        "ms_per_step": round(dt_step * 1e3, 2),
        "ms_per_step_windows": [round(w * 1e3, 2) for w in windows],
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "peak_tflops_spec": peak / 1e12,
        "mfu_spec_table": round(mfu, 4),
        "first_step_loss": round(first_loss, 4),
        "timing": "median of 5 windows, host clock around block_until_ready",
    }
    _write_metrics_out(args, {"bench": result})
    print(json.dumps(result))


def main(argv=None):
    args = _parse_args(argv)
    if args.mode == "serving":
        if args.generate:
            run_generation_bench(args)
        else:
            run_serving_bench(args)
    elif args.mode == "checkpoint":
        run_checkpoint_bench(args)
    elif args.mode == "pipeline":
        run_pipeline_bench(args)
    elif args.mode == "chaos":
        # invariant soak (pass/fail), not a measurement
        run_chaos_bench(args)
    elif args.mode == "lm":
        run_lm_bench(args)
    elif args.mode == "fleet":
        run_fleet_bench(args)
    else:
        run_bench(args)


if __name__ == "__main__":
    main()
