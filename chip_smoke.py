"""Does the system still start on the chip? The quickest proof there is.

Drives the two paths users of this framework depend on, once each, through
their normal entry points, on one TPU chip, in ONE process:

- **train**: ResNet-50 at ImageNet shape, batch 128, bf16 compute, through
  ``optim.optimizer(...).optimize()`` fed by the real host pipeline;
- **serve**: a default ``GenerationEngine`` (paged KV, kernel auto-selected)
  over ``nn.Transformer`` at the widest width the repo builds for the chip,
  float and int8 tiers, checked token-for-token against ``static_generate``
  and for the Pallas call in its compiled decode step; plus one
  ``InferenceService`` ResNet-50 ``predict``.

``--chips 4`` runs instead — and only — the data-parallel phase: a
``DistriOptimizer`` over a dp=4 mesh against ``LocalOptimizer`` on one chip.

Every phase prints one JSON line; any failed check raises, so the exit code
is non-zero and the last line is never printed. Without a TPU the script
fails before anything else. The phases are functions of their sizes so they
can be rehearsed tiny on the CPU from a scratch script; run as a command
nothing shrinks. The last line of a good run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import math
import os
import tempfile
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]   # XLA compiles, or reads of the compile cache; all threads


def _on_compile_event(event, duration_secs, **_):
    if event == _COMPILE_EVENT:
        _compile_s[0] += duration_secs


class _Phase:
    """Times a phase and prints its JSON line: seconds split into compile
    (JAX's backend-compile events: an XLA compile or a cache read) and run
    (everything else — tracing, lowering, transfers, execution, checks)."""

    def __init__(self, name):
        self.name, self.checked = name, {}

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _compile_s[0]
        return self.checked

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            total = time.perf_counter() - self.t0
            comp = _compile_s[0] - self.c0
            print(json.dumps({
                "phase": self.name, "compile_s": round(comp, 2),
                "run_s": round(total - comp, 2), **self.checked}), flush=True)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


# --------------------------------------------------------------- kernels --

def phase_paged_kernel(*, slots, heads, head_dim, page_size, max_len,
                       interpret=False):
    """``paged_flash_attention`` against ``paged_attention_reference`` on
    seeded random pages, float and int8. The reference runs at HIGHEST
    matmul precision, so it is the exact side; the XLA path at default
    precision is printed beside the kernel. On the v5e the kernel's f32
    dots run on the MXU and multiply in bf16 (about 7e-3 from exact on
    unit-variance pages) while XLA computes a one-row query exactly; a
    wrong page, mask or scale is an error of order 1."""
    from bigdl_tpu.nn.int8 import quantize_kv_rows
    from bigdl_tpu.ops.flash_attention import (
        paged_attention_reference,
        paged_flash_attention,
    )

    with _Phase("paged_kernel") as out:
        rs = np.random.RandomState(SEED)
        ppn = max_len // page_size
        n_pages = slots * ppn + 1
        shape = (n_pages, heads, page_size, head_dim)
        kp = jnp.asarray(rs.randn(*shape), jnp.float32)
        vp = jnp.asarray(rs.randn(*shape), jnp.float32)
        q = jnp.asarray(rs.randn(slots, heads, head_dim), jnp.float32)
        page_map = jnp.asarray(
            rs.permutation(n_pages - 1)[:slots * ppn].reshape(slots, ppn),
            jnp.int32)
        positions = jnp.asarray(rs.randint(0, max_len, slots), jnp.int32)
        positions = positions.at[0].set(0).at[1].set(max_len - 1)

        def pair(kp, vp, **scales):
            args = (q, kp, vp, page_map, positions)
            got = jax.jit(lambda *a: paged_flash_attention(
                *a, interpret=interpret, **scales))(*args)
            ref = lambda *a: paged_attention_reference(*a, **scales)
            xla = jax.jit(ref)(*args)
            with jax.default_matmul_precision("highest"):
                exact = jax.jit(ref)(*args)
            err = float(jnp.max(jnp.abs(got - exact)))
            assert got.shape == (slots, heads, head_dim), got.shape
            assert np.isfinite(err) and err < 3e-2, err
            return {"kernel": err,
                    "xla_path": float(jnp.max(jnp.abs(xla - exact)))}

        out["float32_max_abs_err"] = pair(kp, vp)
        # int8 pools as the engine builds them: (num_pages, H, ps, D) int8
        # pages with one fp32 scale per token row, shared across heads
        rows = lambda p: p.transpose(0, 2, 1, 3).reshape(
            n_pages * page_size, heads, head_dim)
        pages = lambda r: r.reshape(
            n_pages, page_size, heads, head_dim).transpose(0, 2, 1, 3)
        (k8, ks), (v8, vs) = quantize_kv_rows(rows(kp)), quantize_kv_rows(rows(vp))
        out["int8_max_abs_err"] = pair(
            pages(k8), pages(v8),
            k_scales=ks.reshape(n_pages, page_size),
            v_scales=vs.reshape(n_pages, page_size))
        out["shape"] = [slots, heads, head_dim, page_size, max_len]


# ----------------------------------------------------------------- train --

def phase_train(*, depth, classes, image, batch, steps):
    """ResNet at ImageNet shape through the public trainer. Returns the
    trained ``(model, params, module_state)`` for the predict phase."""
    from bigdl_tpu.core.config import DtypePolicy, EngineConfig
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import SGD, Trigger, optimizer
    from bigdl_tpu.visualization.summary import TrainSummary

    with _Phase("train") as out:
        rs = np.random.RandomState(SEED)
        # ONE batch, so every step repeats it and the loss has to fall
        x = rs.standard_normal((batch, 3, image, image)).astype(np.float32)
        y = rs.randint(0, classes, batch).astype(np.int32)
        model = resnet.build_imagenet(depth, classes)
        config = EngineConfig(seed=SEED, dtypes=DtypePolicy.mixed())
        params, mstate = model.init(jax.random.key(SEED))
        # host copies of device COPIES: a host view of the donated buffer
        # itself would pin it and defeat the donation checked below
        before = jax.device_get([jnp.copy(l) for l in _leaves(params)[:4]])
        donated = _leaves(params)
        summary = TrainSummary(tempfile.mkdtemp(prefix="chip_smoke_"), "train")
        opt = optimizer(model, DataSet.tensors(x, y), CrossEntropyCriterion(),
                        batch_size=batch, config=config)
        opt.set_model_and_state(params, mstate)
        opt.set_optim_method(SGD(learning_rate=0.02, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(steps))
        opt.set_train_summary(summary)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            new_params, new_mstate = opt.optimize()
        summary.close()
        losses = [v for _, v in summary.read_scalar("Loss")]

        assert type(opt).__name__ == "LocalOptimizer", type(opt)
        assert len(losses) == steps, losses
        assert all(math.isfinite(l) for l in losses), losses
        assert abs(losses[0] - math.log(classes)) < 1.0, (
            f"first-step loss {losses[0]:.3f} is not ~ln({classes})")
        assert losses[-1] < losses[0], losses
        assert any(not np.array_equal(b, np.asarray(a))
                   for b, a in zip(before, _leaves(new_params)[:4]))
        # the step donates params/state: no "donated buffers were not
        # usable" warning, and the buffers that went in are gone (no copy)
        donation = [str(w.message) for w in caught
                    if "donat" in str(w.message).lower()]
        assert not donation, donation
        assert all(l.is_deleted() for l in donated), "params were copied"
        out.update(losses=[round(l, 4) for l in losses], batch=batch,
                   image=image, compute_dtype="bfloat16",
                   optimizer=type(opt).__name__, donated=True)
    return model, new_params, new_mstate


def phase_predict(model, params, mstate, *, image, n):
    """``InferenceService.predict`` of ``n`` images against one direct
    forward of the same batch."""
    from bigdl_tpu.serving import InferenceService

    with _Phase("inference_service") as out:
        rs = np.random.RandomState(SEED + 1)
        xs = rs.standard_normal((n, 3, image, image)).astype(np.float32)
        want = np.asarray(jax.jit(
            lambda p, s, x: model.apply(p, x, state=s, training=False)[0])(
                params, mstate, xs))
        with InferenceService(model, params, mstate,
                              max_batch_size=n) as svc:
            futures = [svc.submit(x) for x in xs]
            got = np.stack([np.asarray(f.result(timeout=600))
                            for f in futures])
        assert got.shape == want.shape and np.isfinite(got).all(), got.shape
        err = float(np.max(np.abs(got - want)))
        assert err < 5e-2 * max(1.0, float(np.max(np.abs(want)))), err
        out.update(images=n, out_shape=list(got.shape),
                   max_abs_err_vs_direct=err)


# ----------------------------------------------------------------- serve --

def phase_serve(*, vocab, hidden, heads, filter_size, layers, max_len,
                slots, max_prompt, int8, kernel_in_step=True):
    """Default ``GenerationEngine`` against ``static_generate`` (the repo's
    own contract: streams are schedule-invariant, token for token), and —
    float tier — against the same baseline over the XLA gather path."""
    from bigdl_tpu.nn.layers.attention import Transformer
    from bigdl_tpu.serving import (
        GenerationEngine,
        PagedDecodeKernels,
        static_generate,
    )

    tier = dict(cache_dtype="int8", quantize="int8") if int8 else {}
    with _Phase("serve_int8" if int8 else "serve") as out:
        model = Transformer(vocab_size=vocab, hidden_size=hidden,
                            num_heads=heads, filter_size=filter_size,
                            num_hidden_layers=layers)
        params, _ = model.init(jax.random.key(SEED))
        engine = GenerationEngine(model, params, max_slots=slots,
                                  max_len=max_len, max_prompt_len=max_prompt,
                                  seed=SEED, **tier)
        assert engine.paged
        engine.warmup()
        rs = np.random.RandomState(SEED)
        lengths = [3, 9, 16, engine.prefill_chunk + 17, 5, 31,
                   engine.prefill_chunk - 1, 12]
        assert max(lengths) <= max_prompt and max(lengths) > engine.prefill_chunk
        requests = [(rs.randint(1, vocab, n).tolist(), new)
                    for n, new in zip(lengths, [8, 24, 12, 16, 32, 8, 20, 16])]
        # first half greedy, second half seeded sampling
        sampling = [{} if i < len(requests) // 2 else
                    dict(temperature=0.8, top_k=40, top_p=0.95, seed=100 + i)
                    for i in range(len(requests))]
        streams = [engine.submit(p, max_new_tokens=m, **spec)
                   for (p, m), spec in zip(requests, sampling)]
        outs = [s.result(timeout=600) for s in streams]
        engine.close()

        assert [len(o) for o in outs] == [m for _, m in requests]
        assert all(0 <= t < vocab for o in outs for t in o)
        assert engine.decode_compilations == 1, engine.decode_compilations
        assert (engine.free_pages, engine.pages_in_use) == (
            engine.num_pages, 0), (engine.free_pages, engine.pages_in_use)
        snap = engine.metrics.snapshot()
        assert snap.get("prefill_chunks", 0) >= 1, "no prompt was chunked"

        # the compiled decode step holds the Pallas call: a silent route to
        # paged_attention_reference fails here
        kernels = engine.kernels
        shape_of = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        s_i32 = jax.ShapeDtypeStruct((slots,), jnp.int32)
        s_f32 = jax.ShapeDtypeStruct((slots,), jnp.float32)
        text = kernels._decode.lower(
            shape_of(engine._params), shape_of(engine._cache), s_i32, s_i32,
            shape_of(engine._page_map), s_f32, s_i32, s_f32,
            shape_of(engine._keys), shape_of(engine._bias),
        ).compile().as_text()
        if kernel_in_step:
            assert "tpu_custom_call" in text, "decode step has no Pallas call"

        static = dict(max_slots=slots, max_len=max_len,
                      prompt_buckets=engine.prompt_buckets,
                      page_size=engine.page_size,
                      prefill_chunk=engine.prefill_chunk, seed=SEED,
                      sampling=sampling, **tier)
        souts, _ = static_generate(model, params, requests, kernels=kernels,
                                   **static)
        assert souts == outs, "engine streams differ from static_generate"
        out.update(requests=len(requests), tokens=sum(map(len, outs)),
                   chunked_prompts=int(snap["prefill_chunks"]),
                   decode_compilations=engine.decode_compilations,
                   pool=[engine.free_pages, engine.pages_in_use],
                   pallas_in_decode_step="tpu_custom_call" in text,
                   identical_to_static_generate=True)
        if not int8:
            # same requests with decode attention on the XLA gather path:
            # rounding differs (so a near-tie may flip a token and the
            # stream then diverges), but a wrong kernel agrees nowhere
            # past the prefill token
            xouts, _ = static_generate(
                model, params, requests,
                kernels=PagedDecodeKernels(model, use_kernel=False), **static)
            half = len(requests) // 2          # greedy streams only
            agree = [next((i for i, (a, b) in enumerate(zip(o, x)) if a != b),
                          len(o)) / len(o)
                     for o, x in zip(outs[:half], xouts[:half])]
            assert np.mean(agree) >= 0.5, agree
            out["greedy_prefix_agreement_with_xla_path"] = [
                round(a, 3) for a in agree]


# -------------------------------------------------------------- four chips --

def phase_dp(*, n_chips, depth, classes, image, per_chip_batch, steps,
             loss_tol):
    """``DistriOptimizer`` over a dp mesh (ZeRO-1) against ``LocalOptimizer``
    on one chip: same seed, same global batch."""
    from bigdl_tpu.core.config import DtypePolicy, EngineConfig
    from bigdl_tpu.core.engine import Engine
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.dataset.prefetch import device_put_batch
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim import SGD, Trigger
    from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
    from bigdl_tpu.optim.optimizer import LocalOptimizer
    from bigdl_tpu.visualization.summary import TrainSummary

    batch = n_chips * per_chip_batch
    rs = np.random.RandomState(SEED)
    x = rs.standard_normal((batch, 3, image, image)).astype(np.float32)
    y = rs.randint(0, classes, batch).astype(np.int32)

    def train(cls, config):
        model = resnet.build_imagenet(depth, classes)
        summary = TrainSummary(tempfile.mkdtemp(prefix="chip_smoke_"), "dp")
        opt = cls(model, DataSet.tensors(x, y), CrossEntropyCriterion(),
                  batch, config)
        opt.set_optim_method(SGD(learning_rate=0.02, momentum=0.9))
        opt.set_end_when(Trigger.max_iteration(steps))
        opt.set_train_summary(summary)
        opt.optimize()
        summary.close()
        losses = [v for _, v in summary.read_scalar("Loss")]
        assert len(losses) == steps and all(map(math.isfinite, losses)), losses
        return opt, losses

    def spread(leaf):
        """(devices holding a shard, shard size / leaf size)."""
        shards = leaf.addressable_shards
        return (len({s.device for s in shards}),
                max(s.data.size for s in shards) / leaf.size)

    with _Phase(f"train_dp{n_chips}") as out:
        config = EngineConfig(seed=SEED, dtypes=DtypePolicy.mixed(),
                              mesh_shape=(("dp", n_chips),))
        Engine.init(config)
        opt, losses = train(DistriOptimizer, config)
        assert opt.zero1 and dict(opt.mesh.shape) == {"dp": n_chips}
        xb, _ = device_put_batch(MiniBatch(x, y), opt._data_sharding)
        assert spread(xb) == (n_chips, 1 / n_chips), spread(xb)
        state_leaf = max(_leaves(opt._optim_state), key=lambda l: l.size)
        assert spread(state_leaf) == (n_chips, 1 / n_chips), spread(state_leaf)
        step, _ = opt._build_step()
        text = step.lower(
            opt._params, opt._module_state, opt._optim_state, xb,
            jax.device_put(y, opt._data_sharding), jax.random.key(0),
            jnp.asarray(1, jnp.int32)).compile().as_text()
        collectives = {c: text.count(c + "(") + text.count(c + "-start(")
                       for c in ("all-reduce", "reduce-scatter", "all-gather")}
        assert collectives["all-reduce"] + collectives["reduce-scatter"] > 0
        out.update(losses=[round(l, 4) for l in losses], global_batch=batch,
                   batch_shards=list(spread(xb)),
                   optim_state_leaf=list(state_leaf.shape),
                   optim_state_shards=list(spread(state_leaf)),
                   collectives=collectives)

    with _Phase("train_one_chip_same_batch") as out:
        Engine.reset()
        _, ref = train(LocalOptimizer,
                       EngineConfig(seed=SEED, dtypes=DtypePolicy.mixed()))
        diff = [abs(a - b) for a, b in zip(losses, ref)]
        assert max(diff) < loss_tol, (losses, ref)
        out.update(losses=[round(l, 4) for l in ref],
                   max_abs_loss_diff_vs_dp=round(max(diff), 5))


# ------------------------------------------------------------------ main --

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the data-parallel phase and its "
                         "one-chip comparison, on four chips")
    args = ap.parse_args(argv)

    if not __debug__:
        raise SystemExit("chip_smoke's checks are assert statements: no -O")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(
            f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    from bigdl_tpu import native
    from bigdl_tpu.core.engine import enable_compile_cache
    from bigdl_tpu.ops import attention

    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)
    cache_dir = enable_compile_cache()
    print(json.dumps({"phase": "start", "jax": jax.__version__,
                      "device_kind": devices[0].device_kind,
                      "devices": len(devices), "chips": args.chips,
                      "native_available": native.native_available(),
                      "compile_cache_dir": cache_dir}), flush=True)

    if args.chips == 4:
        phase_dp(n_chips=4, depth=50, classes=1000, image=224,
                 per_chip_batch=64, steps=4, loss_tol=0.05)
    else:
        phase_paged_kernel(slots=16, heads=8, head_dim=64, page_size=16,
                           max_len=256)
        trained = phase_train(depth=50, classes=1000, image=224, batch=128,
                              steps=6)
        phase_predict(*trained, image=224, n=4)
        del trained
        for int8 in (False, True):
            phase_serve(vocab=8192, hidden=512, heads=8, filter_size=2048,
                        layers=4, max_len=256, slots=16, max_prompt=128,
                        int8=int8)

    # this run's compile seconds beside the previous run's (same cache
    # directory): a warm cache shows as the smaller number
    record = os.path.join(cache_dir, f"chip_smoke_compile_s.{args.chips}")
    previous = None
    if os.path.exists(record):
        with open(record) as f:
            previous = float(f.read())
    os.makedirs(cache_dir, exist_ok=True)
    with open(record, "w") as f:
        f.write(repr(_compile_s[0]))
    print(json.dumps({"phase": "compile_cache", "dir": cache_dir,
                      "compile_s": round(_compile_s[0], 2),
                      "previous_run_compile_s": (
                          None if previous is None else round(previous, 2)),
                      "attention_kernel_fallbacks": dict(
                          attention.kernel_fallbacks)}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
