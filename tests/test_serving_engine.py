"""Generation-serving tier (bigdl_tpu/serving/engine.py + router.py):
continuous batching correctness, slot lifecycle, compile bounds,
scheduling determinism, and multi-model routing.

The load-bearing properties, per the subsystem contract:

- engine tokens == full-forward greedy decode (the KV slot table is an
  exact cache, not an approximation);
- the decode step compiles ONCE at warmup and never again, whatever the
  admission/retirement pattern (fixed slot-table shapes, donated cache);
- requests admit into free slots mid-flight and retire mid-flight (EOS,
  max-tokens, deadline, cancel) without disturbing neighbours — outputs
  are bit-identical across admission orderings;
- continuous batching beats run-to-completion static batching on mixed
  lengths even on one core (the win is scheduling, not parallelism);
- router quotas reject per-model while other models keep serving.
"""

import threading
import time

import jax
import numpy as np
import pytest

from bigdl_tpu.nn import Linear, ReLU, Sequential
from bigdl_tpu.nn.layers.attention import Transformer
from bigdl_tpu.serving import (
    DeadlineExceeded,
    DecodeKernels,
    GenerationEngine,
    InferenceService,
    ModelRouter,
    Overloaded,
    StreamCancelled,
    UnknownModel,
    static_generate,
)

SLOTS, MAXLEN, MAXPROMPT = 4, 48, 8


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=4,
                        filter_size=64, num_hidden_layers=2)
    params, _ = model.init(jax.random.key(0))
    # one kernels pair for the whole module: the jit cache persists across
    # engines, so each test pays bookkeeping, not recompilation
    kernels = DecodeKernels(model)
    return model, params, kernels


# fixed per-call cost: stands in for a real chip's step time so
# timing-sensitive tests (deadlines, cancel, mid-flight admission,
# scheduling throughput) are deterministic instead of racing a
# microsecond-fast CPU step
from _serving_shims import SlowKernels as _SlowKernels  # noqa: E402


def make_engine(lm, **kw):
    model, params, kernels = lm
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("max_len", MAXLEN)
    kw.setdefault("max_prompt_len", MAXPROMPT)
    kw.setdefault("kernels", kernels)
    return GenerationEngine(model, params, **kw)


def ref_greedy(model, params, prompt, n, eos_id=None):
    """Reference: full causal forward per step, argmax of the last
    position — the engine's slot-table decode must match this exactly."""
    import jax.numpy as jnp

    ids = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        # jitted per length (see test_paged_generation.ref_greedy)
        logits, _ = jax.jit(model.apply)(params, jnp.asarray([ids]))
        tok = int(np.asarray(logits)[0, -1].argmax())
        ids.append(tok)
        out.append(tok)
        if eos_id is not None and tok == eos_id:
            break
    return out


# --------------------------------------------------------- correctness ----


def test_generate_matches_full_forward_greedy(lm):
    model, params, _ = lm
    eng = make_engine(lm)
    prompts = [[1, 5, 9], [2, 4], [7, 3, 11, 13, 2]]
    streams = [eng.submit(p, max_new_tokens=6) for p in prompts]
    outs = [s.result(timeout=30) for s in streams]
    eng.close()
    for p, o in zip(prompts, outs):
        assert o == ref_greedy(model, params, p, 6)


def test_slot_lifecycle_admit_decode_retire_reuse(lm):
    """6 requests through 2 slots: every request admits into a freed
    slot, decodes, retires, and the table ends empty — slot reuse is
    forced because requests outnumber slots 3:1."""
    eng = make_engine(lm, max_slots=2)
    streams = [eng.submit([1 + i, 3], max_new_tokens=4 + i) for i in range(6)]
    outs = [s.result(timeout=30) for s in streams]
    assert [len(o) for o in outs] == [4 + i for i in range(6)]
    snap = eng.metrics.snapshot()
    assert snap["served"] == 6 and snap["prefills"] == 6
    assert snap["decode_steps"] > 0
    assert eng.active_slots == 0 and eng.free_slots == [0, 1]
    eng.close()
    # closing again is a no-op; submitting after close rejects
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1, 2])


def test_midflight_admission_does_not_disturb_running_request(lm):
    """A request admitted WHILE another is mid-decode produces exactly
    the tokens it produces solo, and the running request's stream is
    unaffected — the slot rows are independent."""
    model, params, _ = lm
    solo = make_engine(lm)
    want_a = solo.generate([2, 9, 4], max_new_tokens=30, timeout=30)
    want_b = solo.generate([5, 1], max_new_tokens=5, timeout=30)
    solo.close()

    model, params, kernels = lm
    eng = make_engine(lm, kernels=_SlowKernels(kernels))
    a = eng.submit([2, 9, 4], max_new_tokens=30)
    # wait until A is demonstrably mid-flight (has streamed tokens)
    deadline = time.monotonic() + 10
    while len(a.tokens) < 3 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert len(a.tokens) >= 3, "request never started decoding"
    assert not a.done
    b = eng.submit([5, 1], max_new_tokens=5)
    assert b.result(timeout=30) == want_b
    assert a.result(timeout=30) == want_a
    eng.close()


def test_determinism_across_admission_orderings(lm):
    """Greedy decode + independent slot rows: per-prompt outputs are
    bit-identical whatever order requests arrive in, however they get
    packed into slots, and whenever they are admitted."""
    prompts = [[i + 1, 2 * i + 1, 5] for i in range(6)]
    lengths = [4, 11, 6, 9, 3, 13]

    def run(order, stagger):
        eng = make_engine(lm, max_slots=2)
        streams = {}
        for j, i in enumerate(order):
            streams[i] = eng.submit(prompts[i], max_new_tokens=lengths[i])
            if stagger and j % 2:
                time.sleep(0.005)
        outs = {i: s.result(timeout=30) for i, s in streams.items()}
        eng.close()
        return outs

    a = run(list(range(6)), stagger=False)
    b = run(list(reversed(range(6))), stagger=True)
    assert a == b


def test_eos_retirement_frees_slot_early(lm):
    """With eos_id set to a token the model actually emits, the stream
    stops at (and includes) EOS instead of running to max_new_tokens."""
    model, params, _ = lm
    free_run = ref_greedy(model, params, [1, 5, 9], 10)
    eos = free_run[2]  # a token the model is known to emit
    want = ref_greedy(model, params, [1, 5, 9], 10, eos_id=eos)
    assert want[-1] == eos and len(want) < 10

    eng = make_engine(lm, eos_id=eos)
    out = eng.generate([1, 5, 9], max_new_tokens=10, timeout=30)
    assert out == want
    assert eng.metrics.snapshot()["served"] == 1
    eng.close()


class _EchoPosition:
    """Decode-capable stub whose argmax token IS the cache position:
    generation from a length-n prompt yields [n, n, n+1, n+2, ...] —
    fully scripted, so decode-time retirement paths can be pinned
    exactly (the untrained transformer collapses to a constant token,
    which only ever exercises prefill-time EOS)."""

    VOCAB = 64

    def init_cache(self, max_slots, max_len, dtype):
        import jax.numpy as jnp

        return {"kv": jnp.zeros((max_slots, 1, max_len, 1), dtype)}

    def prefill(self, params, cache, slot, tokens, length):
        import jax.numpy as jnp

        return jax.nn.one_hot(length, self.VOCAB), cache

    def decode_step(self, params, cache, tokens, positions):
        return jax.nn.one_hot(positions, self.VOCAB), cache


def test_eos_retirement_mid_decode_scripted():
    """Decode-time EOS: the scripted model emits n, n, n+1, n+2, ... for
    a length-n prompt, so eos_id = n + 2 must stop the stream exactly at
    its fourth token while a no-EOS neighbour runs to its max."""
    stub = _EchoPosition()
    eng = GenerationEngine(stub, {}, max_slots=2, max_len=32,
                           max_prompt_len=8, eos_id=5 + 2)
    with_eos = eng.submit([1, 2, 3, 4, 5], max_new_tokens=20)   # n = 5
    without = eng.submit([1, 2, 3], max_new_tokens=6)           # n = 3
    assert with_eos.result(timeout=30) == [5, 5, 6, 7]
    assert without.result(timeout=30) == [3, 3, 4, 5, 6, 7][:6]
    assert eng.metrics.snapshot()["served"] == 2
    assert eng.free_slots == [0, 1]
    eng.close()


def test_deadline_expires_midflight_other_streams_unaffected(lm):
    """A deadline that expires mid-generation retires the slot: the
    stream fails with DeadlineExceeded but keeps its partial tokens;
    a concurrent no-deadline request completes untouched."""
    model, params, kernels = lm
    eng = make_engine(lm, kernels=_SlowKernels(kernels))  # ~2ms/step
    doomed = eng.submit([1, 2, 3], max_new_tokens=40, deadline=0.03)
    live = eng.submit([4, 5], max_new_tokens=40)
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=30)
    assert doomed.tokens, "expiry should keep the partial stream"
    assert len(doomed.tokens) < 40
    assert len(live.result(timeout=30)) == 40
    snap = eng.metrics.snapshot()
    assert snap["expired"] == 1 and snap["served"] == 1
    eng.close()


def test_deadline_expired_while_pending_never_takes_a_slot(lm):
    """With one slot busy on a long generation, a queued request whose
    deadline lapses is dropped at admission — no prefill is spent on it."""
    model, params, kernels = lm
    eng = make_engine(lm, max_slots=1, kernels=_SlowKernels(kernels))
    long_run = eng.submit([1, 2], max_new_tokens=40)  # >= 80ms of steps
    doomed = eng.submit([3, 4], max_new_tokens=5, deadline=0.005)
    with pytest.raises(DeadlineExceeded):
        doomed.result(timeout=30)
    assert doomed.tokens == []  # dropped before any token
    assert len(long_run.result(timeout=30)) == 40
    snap = eng.metrics.snapshot()
    assert snap["expired"] == 1 and snap["prefills"] == 1
    eng.close()


def test_cancel_retires_at_next_boundary(lm):
    model, params, kernels = lm
    eng = make_engine(lm, kernels=_SlowKernels(kernels))
    s = eng.submit([1, 2], max_new_tokens=46)
    deadline = time.monotonic() + 10
    while len(s.tokens) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    s.cancel()
    with pytest.raises(StreamCancelled):
        s.result(timeout=30)
    assert 2 <= len(s.tokens) < 46
    eng.close()


# ------------------------------------------------- compile/shape bounds ----


def test_decode_compiles_once_across_admissions_and_retirements(lm):
    """The acceptance assertion: warmup compiles the decode step exactly
    once and the prefill path once per prompt bucket; admissions and
    retirements of varying-length requests afterwards trigger ZERO
    recompilation — the slot-table shapes are fixed and the KV cache is
    donated, so the steady-state loop is allocation- and compile-free."""
    model, params, _ = lm
    kernels = DecodeKernels(model)  # private pair: counters start at zero
    eng = make_engine(lm, kernels=kernels, max_queue=64)
    eng.warmup()
    assert kernels.decode_traces == 1
    assert kernels.prefill_traces == len(eng.prompt_buckets)

    streams = []
    for i in range(10):  # every prompt bucket, varied targets, staggering
        plen = 1 + (i * 3) % MAXPROMPT
        streams.append(eng.submit([1 + j for j in range(plen)],
                                  max_new_tokens=2 + (i * 5) % 17))
        if i % 3 == 0:
            time.sleep(0.002)
    for s in streams:
        s.result(timeout=30)
    eng.close()

    assert kernels.decode_traces == 1, "decode step recompiled under traffic"
    assert kernels.prefill_traces == len(eng.prompt_buckets)
    # the pjit caches agree with the trace counters
    assert kernels._decode._cache_size() == 1
    assert kernels._prefill._cache_size() == len(eng.prompt_buckets)


def test_overloaded_at_pending_bound_and_bad_prompts(lm):
    model, params, kernels = lm
    eng = make_engine(lm, max_slots=1, max_queue=2,
                      kernels=_SlowKernels(kernels))
    first = eng.submit([1], max_new_tokens=40)  # occupies the single slot
    deadline = time.monotonic() + 10
    while eng.active_slots < 1 and time.monotonic() < deadline:
        time.sleep(0.001)  # wait for admission so the queue bound is clean
    accepted = [eng.submit([2], max_new_tokens=2) for _ in range(2)]
    with pytest.raises(Overloaded):
        for _ in range(50):  # the slot may drain the queue between submits
            eng.submit([3], max_new_tokens=2)
    assert eng.metrics.snapshot()["rejected"] >= 1
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    with pytest.raises(ValueError, match="max_prompt_len"):
        eng.submit(list(range(MAXPROMPT + 1)))
    first.result(timeout=30)
    for s in accepted:
        s.result(timeout=30)
    eng.close()


# --------------------------------------------------------- streams/close ----


def test_stream_iterates_incrementally_with_ttft(lm):
    model, params, _ = lm
    eng = make_engine(lm)
    s = eng.submit([3, 1, 4], max_new_tokens=8)
    seen = list(s)  # single-pass iterator ends at stream completion
    assert seen == s.result(timeout=5) == ref_greedy(model, params, [3, 1, 4], 8)
    assert s.ttft_s is not None and s.ttft_s >= 0
    snap = eng.metrics.snapshot()
    assert snap["tokens_out"] == 8 and snap["ttft_ms"] is not None
    eng.close()


def test_close_drains_inflight_streams(lm):
    eng = make_engine(lm, max_slots=2)
    streams = [eng.submit([1 + i], max_new_tokens=12) for i in range(5)]
    eng.close()  # default drain: every stream must complete, none fail
    for s in streams:
        assert len(s.result(timeout=5)) == 12


def test_close_timeout_never_fails_still_draining_streams(lm):
    """A drain close whose join times out must LEAVE the in-flight
    streams alone (the loop is still legitimately serving them); a
    follow-up unbounded close completes the drain."""
    model, params, kernels = lm
    eng = make_engine(lm, kernels=_SlowKernels(kernels))  # ~2ms/step
    streams = [eng.submit([1 + i], max_new_tokens=40) for i in range(3)]
    eng.close(drain=True, timeout=0.01)  # expires mid-drain
    assert eng._thread.is_alive()  # still draining
    assert not any(s.done and s.error is not None for s in streams)
    eng.close(drain=True)  # unbounded: finishes the drain
    for s in streams:
        assert len(s.result(timeout=5)) == 40


def test_close_nodrain_fails_queued_streams(lm):
    eng = make_engine(lm, max_slots=1)
    streams = [eng.submit([1 + i], max_new_tokens=30) for i in range(4)]
    eng.close(drain=False)
    failed = 0
    for s in streams:
        try:
            s.result(timeout=5)
        except RuntimeError:
            failed += 1
    assert failed >= 1  # queued requests must fail, not strand


def test_engine_reload_swaps_params_between_steps(lm):
    model, params, kernels = lm
    params2, _ = model.init(jax.random.key(7))
    eng = make_engine(lm)
    before = eng.generate([1, 5, 9], max_new_tokens=6, timeout=30)
    eng.reload(jax.tree_util.tree_map(lambda a: a.copy(), params2))
    after = eng.generate([1, 5, 9], max_new_tokens=6, timeout=30)
    assert after == ref_greedy(model, params2, [1, 5, 9], 6)
    assert eng.metrics.snapshot()["reloads"] == 1
    # a different model's tree cannot be hot-swapped in
    tiny = Transformer(vocab_size=64, hidden_size=16, num_heads=2,
                       filter_size=32, num_hidden_layers=1)
    tparams, _ = tiny.init(jax.random.key(0))
    with pytest.raises(ValueError, match="signature"):
        eng.reload(tparams)
    # the rejected reload left the good weights serving
    assert eng.generate([1, 5, 9], max_new_tokens=6, timeout=30) == after
    assert before == ref_greedy(model, params, [1, 5, 9], 6)
    eng.close()


def test_unclosed_engine_is_garbage_collectable(lm):
    """Same discipline as the batcher worker: the loop thread holds only
    a weak engine ref while idle, so an engine whose owner forgot
    close() is collected (params + KV cache freed) and its loop exits."""
    import gc
    import weakref

    eng = make_engine(lm)
    eng.generate([1, 2], max_new_tokens=3, timeout=30)
    thread = eng._thread
    ref = weakref.ref(eng)
    del eng
    deadline = time.monotonic() + 10
    while ref() is not None and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.02)
    assert ref() is None, "unclosed GenerationEngine leaked"
    thread.join(timeout=10)
    assert not thread.is_alive()


# ----------------------------------------------- continuous vs static ----


def test_continuous_beats_static_on_mixed_lengths(lm):
    """The scheduling acceptance bar: on an alternating short/long
    workload, continuous batching sustains >= 1.5x the run-to-completion
    static tokens/sec — on ONE core, because the win is slot occupancy
    (short sequences retire and readmit instead of idling until the
    longest batch-mate finishes), not parallelism. A fixed per-call cost
    stands in for the chip's step time (the fixture model decodes in
    microseconds, where Python bookkeeping would drown the signal —
    ``bench.py --mode serving --generate --smoke`` gates the same 1.5x
    on real wall-clock with a realistically-sized model)."""
    model, params, kernels = lm
    slow = _SlowKernels(kernels, step_sleep=0.002)
    requests = [([1 + i, 3, 7], 2 if i % 2 == 0 else 40) for i in range(16)]

    # warm the jit caches before timing (shared inner kernels); both
    # schedulers use the ENGINE's prompt buckets so neither pays a
    # compile inside its timed region
    eng = make_engine(lm)
    eng.warmup()
    buckets = eng.prompt_buckets
    eng.close()
    static_generate(model, params, requests[:2], max_slots=SLOTS,
                    max_len=MAXLEN, kernels=kernels, prompt_buckets=buckets)

    eng = make_engine(lm, max_queue=64, kernels=slow)
    t0 = time.perf_counter()
    streams = [eng.submit(p, max_new_tokens=m) for p, m in requests]
    outs = [s.result(timeout=60) for s in streams]
    cont_wall = time.perf_counter() - t0
    cont_steps = eng.metrics.snapshot()["decode_steps"]
    eng.close()

    t0 = time.perf_counter()
    souts, static_steps = static_generate(
        model, params, requests, max_slots=SLOTS, max_len=MAXLEN,
        kernels=slow, prompt_buckets=buckets)
    static_wall = time.perf_counter() - t0

    assert outs == souts  # greedy decode is schedule-invariant
    tokens = sum(len(o) for o in outs)
    ratio = (tokens / cont_wall) / (tokens / static_wall)
    n = len(requests)
    # the forward-count gap is deterministic: assert it strictly, and the
    # wall-clock ratio (same fixed cost per forward on both sides) at the
    # 1.5x acceptance bar
    assert (static_steps + n) / (cont_steps + n) > 1.5, (
        static_steps, cont_steps)
    assert ratio >= 1.5, (
        f"continuous {ratio:.2f}x static (steps {cont_steps} vs "
        f"{static_steps}) — scheduling win lost in overhead")


# ----------------------------------------------------------- router ----


def _mlp_service(seed=0, **kw):
    model = Sequential().add(Linear(8, 16)).add(ReLU()).add(Linear(16, 4))
    params, state = model.init(jax.random.key(seed))
    return InferenceService(model, params, state, **kw), model, params, state


def test_router_dispatches_by_name_and_rejects_unknown(lm):
    svc, model, params, state = _mlp_service()
    router = ModelRouter()
    router.register("mlp", svc).register("lm", make_engine(lm))
    assert router.names() == ["lm", "mlp"]

    x = np.arange(8, dtype="float32")
    y = router.predict("mlp", x, timeout=30)
    full, _ = model.apply(params, x[None], state=state)
    np.testing.assert_allclose(np.asarray(y), np.asarray(full)[0],
                               rtol=1e-5, atol=1e-6)

    toks = router.predict("lm", [1, 5, 9], timeout=30, max_new_tokens=4)
    assert len(toks) == 4

    with pytest.raises(UnknownModel, match="resnet"):
        router.submit("resnet", x)
    with pytest.raises(ValueError, match="already registered"):
        router.register("mlp", svc)
    router.close()
    with pytest.raises(RuntimeError, match="closed"):
        router.submit("mlp", x)


def test_router_quota_rejects_per_model_while_others_serve(lm):
    """Saturating model A's in-flight quota raises Overloaded naming A;
    model B keeps serving throughout — per-model isolation."""
    gate = threading.Event()
    model = Sequential().add(Linear(8, 16)).add(ReLU()).add(Linear(16, 4))
    params, state = model.init(jax.random.key(0))

    def gated_forward(p, s, xb):
        gate.wait(timeout=30)
        out, _ = model.apply(p, xb, state=s, training=False)
        return out

    slow = InferenceService(model, params, state, max_wait_ms=1.0,
                            forward_fn=gated_forward)
    fast, fmodel, fparams, fstate = _mlp_service(seed=1)
    router = ModelRouter()
    router.register("slow", slow, max_inflight=3)
    router.register("fast", fast)

    x = np.arange(8, dtype="float32")
    held = [router.submit("slow", x) for _ in range(3)]
    with pytest.raises(Overloaded, match="slow"):
        router.submit("slow", x)
    assert router.inflight("slow") == 3
    # a quota-shed request counts as rejected in the model's metrics even
    # though the backend never saw it
    assert router.snapshot()["slow"]["rejected"] == 1
    # the sibling model is untouched by A's saturation
    assert np.asarray(router.predict("fast", x, timeout=30)).shape == (4,)

    gate.set()
    for f in held:
        f.result(timeout=30)
    deadline = time.monotonic() + 10
    while router.inflight("slow") and time.monotonic() < deadline:
        time.sleep(0.005)
    assert router.inflight("slow") == 0  # quota released on completion
    router.predict("slow", x, timeout=30)  # and admits again
    router.close()


def test_router_quota_applies_to_generation_streams(lm):
    router = ModelRouter()
    router.register("lm", make_engine(lm), max_inflight=2)
    a = router.submit("lm", [1, 2], max_new_tokens=30)
    b = router.submit("lm", [3, 4], max_new_tokens=30)
    with pytest.raises(Overloaded, match="lm"):
        router.submit("lm", [5, 6], max_new_tokens=2)
    a.result(timeout=30)
    b.result(timeout=30)
    deadline = time.monotonic() + 10
    while router.inflight("lm") and time.monotonic() < deadline:
        time.sleep(0.005)
    assert len(router.predict("lm", [5, 6], timeout=30,
                              max_new_tokens=2)) == 2
    router.close()


class _ManualHandle:
    """Duck-typed future whose done callbacks the TEST fires — including
    twice, which a real backend can do when ``close(drain=False)`` races
    a completion during replica eviction."""

    def __init__(self, break_add=False):
        self._cbs = []
        self.error = None
        self.break_add = break_add

    def add_done_callback(self, fn):
        if self.break_add:
            raise RuntimeError("injected broken handle")
        self._cbs.append(fn)

    def fire(self, times=1):
        for _ in range(times):
            for fn in list(self._cbs):
                fn(self)

    def result(self, timeout=None):
        return None


class _ManualBackend:
    def __init__(self, break_add=False):
        from bigdl_tpu.serving import ServingMetrics

        self.metrics = ServingMetrics()
        self.break_add = break_add
        self.handles = []

    def submit(self, x, **kw):
        h = _ManualHandle(self.break_add)
        self.handles.append(h)
        return h

    def close(self, drain=True, timeout=None):
        pass


def test_router_quota_release_idempotent_and_exception_safe():
    """Regression (replica-eviction race): a backend future failed by
    ``close(drain=False)`` WHILE the worker completes it can run its done
    callbacks twice — the quota slot must release exactly once (never
    leak, never double-release); and a handle whose ``add_done_callback``
    raises must not leak the slot either."""
    router = ModelRouter()
    good = _ManualBackend()
    router.register("m", good, max_inflight=1)
    h = router.submit("m", 1)
    assert router.inflight("m") == 1
    h.fire(times=2)  # double-fired completion: released ONCE, not twice
    assert router.inflight("m") == 0
    h2 = router.submit("m", 1)  # a double-release would have gone to -1
    with pytest.raises(Overloaded):
        router.submit("m", 1)   # quota still bounds at exactly 1
    h2.fire()
    assert router.inflight("m") == 0

    bad = _ManualBackend(break_add=True)
    router.register("b", bad, max_inflight=1)
    for _ in range(2):  # a leak would jam the quota shut on try 2
        with pytest.raises(RuntimeError, match="broken handle"):
            router.submit("b", 1)
        assert router.inflight("b") == 0
    router.close()


def test_router_snapshot_and_table(lm):
    svc, *_ = _mlp_service()
    router = ModelRouter()
    router.register("mlp", svc, max_inflight=8)
    router.register("lm", make_engine(lm))
    router.predict("mlp", np.arange(8, dtype="float32"), timeout=30)
    router.predict("lm", [1, 2, 3], timeout=30, max_new_tokens=3)
    snap = router.snapshot()
    assert snap["mlp"]["served"] == 1 and snap["mlp"]["max_inflight"] == 8
    assert snap["lm"]["served"] == 1 and snap["lm"]["tokens_out"] == 3
    table = router.format_table()
    assert "mlp" in table and "lm" in table and "tokens_out" in table
    # unregister leaves the other model running
    router.unregister("mlp", close=True)
    assert router.names() == ["lm"]
    assert len(router.predict("lm", [9], timeout=30, max_new_tokens=2)) == 2
    router.close()


# ----------------------------------------- fault sites + stall watchdog ----

from bigdl_tpu import faults  # noqa: E402
from bigdl_tpu.faults import StallError  # noqa: E402
from _serving_shims import arm_step_failure  # noqa: E402


def test_step_failure_via_site_fails_streams_and_stops_engine(lm):
    """The engine's own ``engine.decode`` fault site is the one
    injection mechanism for step failures: streams fail with the
    injected error (original exception preserved), the loop stops, and
    new submits are refused."""
    eng = make_engine(lm, kernels=_SlowKernels(lm[2]))
    spec = arm_step_failure(eng, after=2, message="injected step death")
    s = eng.submit([1, 5, 9], max_new_tokens=20)
    with pytest.raises(RuntimeError, match="injected step death"):
        s.result(timeout=30)
    assert spec.fired >= 1
    with pytest.raises(RuntimeError, match="step failure"):
        eng.submit([2])
    assert len(s.tokens) >= 1  # tokens produced before the death remain
    eng.close()


def test_engine_watchdog_fails_streams_on_stalled_step():
    """A wedged decode step (armed latency far past ``stall_timeout``)
    must not hang consumers: the watchdog fails every pending/active
    stream with a StallError diagnostic, submits are refused, and once
    the stuck step finally returns the loop reconciles the slot table
    and exits."""
    stub = _EchoPosition()
    eng = GenerationEngine(stub, {}, max_slots=2, max_len=32,
                           max_prompt_len=8, stall_timeout=0.15)
    faults.arm("engine.decode", latency=1.2, times=1)
    a = eng.submit([1, 2, 3], max_new_tokens=10)
    b = eng.submit([4, 5], max_new_tokens=10)
    with pytest.raises(StallError, match="no progress"):
        a.result(timeout=30)
    with pytest.raises(StallError, match="failing pending work"):
        b.result(timeout=30)
    with pytest.raises(RuntimeError, match="step failure"):
        eng.submit([6])
    # the wedged step returns ~1 s later; the loop thread reconciles the
    # slots/pages and exits instead of stepping a failed engine
    deadline = time.monotonic() + 15
    while (eng.active_slots or eng._thread.is_alive()) \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.active_slots == 0
    assert not eng._thread.is_alive()
    # the exiting loop owns watchdog retirement (close() may have been
    # skipped while the step was wedged): its thread and strong engine
    # ref must be gone without any close() call
    deadline = time.monotonic() + 10
    while eng._watchdog._thread.is_alive() \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert not eng._watchdog._thread.is_alive()
    eng.close()


def test_engine_watchdog_quiet_on_healthy_traffic(lm):
    """A generous watchdog never fires on normal decoding, and close()
    retires its thread."""
    model, params, _ = lm
    eng = make_engine(lm, stall_timeout=10.0)
    out = eng.generate([1, 5, 9], max_new_tokens=6, timeout=30)
    assert out == ref_greedy(model, params, [1, 5, 9], 6)
    assert eng._watchdog.stalls == 0
    eng.close()
    deadline = time.monotonic() + 5
    while eng._watchdog._thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not eng._watchdog._thread.is_alive()
