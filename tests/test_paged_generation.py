"""Paged-KV generation tier (PR 6): block-table cache bit-identity,
in-step sampling, chunked prefill, page-pool lifecycle, compile bounds.

The load-bearing properties, per the subsystem contract:

- the paged gather path is BIT-identical to dense slot-table attention
  on the same backend — at the op level, the model level (any page size,
  fragmented and recycled page maps), and the engine level (same greedy
  tokens as the dense PR-5 engine, any admission order);
- sampling runs inside the jitted step, matches a pure-numpy per-step
  oracle at fixed seed, and is deterministic across runs, admission
  orderings, and schedulers (a request's stream is a function of its
  seed alone);
- chunked prefill bounds a decode-only neighbour's inter-token gap
  while a max-length prompt prefills, and lifts the
  ``max_prompt_len < max_len`` admission wall;
- the paged prefill/chunk/decode kernels each compile exactly once
  across a mixed greedy+sampled, short+chunked workload;
- bf16 KV storage stays within a bounded greedy-token divergence of
  fp32.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.core.rng import threefry_key_data
from bigdl_tpu.nn.layers.attention import Transformer
from bigdl_tpu.ops.flash_attention import (
    _xla_attention,
    gather_kv_lanes,
    paged_attention_reference,
    paged_flash_attention,
)
from bigdl_tpu.ops.sampling import (
    numpy_reference_sample,
    sample_tokens,
    split_key_data,
)
from bigdl_tpu.serving import (
    DecodeKernels,
    GenerationEngine,
    PagePool,
    PagedDecodeKernels,
    static_generate,
)

SLOTS, MAXLEN = 4, 48  # divisible by every page size under test


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=4,
                        filter_size=64, num_hidden_layers=2)
    params, _ = model.init(jax.random.key(0))
    # one kernel triple for the whole module: the jit cache persists
    # across engines, so each test pays bookkeeping, not recompilation
    kernels = PagedDecodeKernels(model)
    dense_kernels = DecodeKernels(model)
    return model, params, kernels, dense_kernels


def make_engine(lm, **kw):
    model, params, kernels, _ = lm
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("max_len", MAXLEN)
    kw.setdefault("kernels", kernels)
    return GenerationEngine(model, params, **kw)


def ref_greedy(model, params, prompt, n):
    ids = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        # jitted per length: op-by-op dispatch compiles every primitive
        # anew at every new length and was the slowest thing in tier-1
        logits, _ = jax.jit(model.apply)(params, jnp.asarray([ids]))
        tok = int(np.asarray(logits)[0, -1].argmax())
        ids.append(tok)
        out.append(tok)
    return out


# ------------------------------------------------------------ op level ----


class TestPagedOps:
    def _pools(self, rng, n_pages, heads=2, ps=4, d=8):
        return (jnp.asarray(rng.randn(n_pages, heads, ps, d)
                            .astype(np.float32)),
                jnp.asarray(rng.randn(n_pages, heads, ps, d)
                            .astype(np.float32)))

    def test_reference_bit_identical_to_dense_lanes(self):
        """The acceptance anchor: gathering a fragmented page map into
        logical lanes and attending == dense lane attention, to the BIT
        (gather is data movement; the math after it is the same ops)."""
        rng = np.random.RandomState(0)
        kp, vp = self._pools(rng, 16)
        page_map = jnp.asarray(np.stack(
            [rng.choice(16, 4, replace=False) for _ in range(3)])
            .astype(np.int32))
        positions = jnp.asarray([3, 9, 14], jnp.int32)
        q = jnp.asarray(rng.randn(3, 2, 8).astype(np.float32))

        out = paged_attention_reference(q, kp, vp, page_map, positions)

        lanes_k = gather_kv_lanes(kp, page_map)
        lanes_v = gather_kv_lanes(vp, page_map)
        length = lanes_k.shape[2]
        rows = positions[:, None] + jnp.arange(1)[None, :]
        cols = jnp.arange(length)
        validity = jnp.where(cols[None, None, :] <= rows[:, :, None],
                             0.0, -1e9)[:, None, :, :]
        dense = _xla_attention(q[:, :, None, :], lanes_k, lanes_v, validity,
                               8 ** -0.5, False)[:, :, 0, :]
        assert np.array_equal(np.asarray(out), np.asarray(dense))

    def test_pallas_kernel_matches_reference(self):
        """The TPU kernel (interpret mode here) agrees with the jnp
        gather reference — page-map indirection, per-slot position
        masking, and skipped out-of-range pages included."""
        rng = np.random.RandomState(1)
        kp, vp = self._pools(rng, 12, heads=2, ps=4, d=8)
        page_map = jnp.asarray(np.stack(
            [rng.choice(12, 3, replace=False) for _ in range(4)])
            .astype(np.int32))
        positions = jnp.asarray([0, 5, 11, 7], jnp.int32)
        q = jnp.asarray(rng.randn(4, 2, 8).astype(np.float32))
        ref = paged_attention_reference(q, kp, vp, page_map, positions)
        out = paged_flash_attention(q, kp, vp, page_map, positions,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_gather_kv_lanes_is_exact_data_movement(self):
        rng = np.random.RandomState(2)
        kp, _ = self._pools(rng, 8, heads=1, ps=4, d=2)
        pm = jnp.asarray([[5, 0, 3]], jnp.int32)
        lanes = np.asarray(gather_kv_lanes(kp, pm))
        pool = np.asarray(kp)
        want = np.concatenate([pool[5], pool[0], pool[3]], axis=1)
        assert np.array_equal(lanes[0], want)


# --------------------------------------------------------- model level ----


class TestPagedModel:
    @pytest.mark.parametrize("page_size", [4, 8, 16])
    def test_prefill_and_decode_bit_identical_to_dense(self, lm, page_size):
        """Across page sizes and a FRAGMENTED page assignment, paged
        prefill + decode logits equal the dense slot-table decode
        bitwise."""
        model, params, _, _ = lm
        ppn = MAXLEN // page_size
        ids = np.array([5, 11, 2, 29, 7, 3], np.int32)
        padded = np.zeros(8, np.int32)
        padded[:6] = ids

        cache = model.init_cache(3, MAXLEN)
        dl, cache = model.prefill(params, cache, 1, jnp.asarray(padded), 6)

        rng = np.random.RandomState(page_size)
        n_pages = 3 * ppn
        pool = model.init_paged_cache(n_pages + 1, page_size)
        trash = n_pages
        pages = rng.choice(n_pages, ppn, replace=False).astype(np.int32)
        page_map = np.full((3, ppn), trash, np.int32)
        page_map[1] = pages
        pl_, pool = model.prefill_paged(
            params, pool, jnp.asarray(pages), jnp.asarray(padded), 0, 6,
            trash)
        assert np.array_equal(np.asarray(dl), np.asarray(pl_))

        toks = np.zeros(3, np.int32)
        pos = np.zeros(3, np.int32)
        for t, nxt in ((6, 17), (7, 23)):
            toks[1], pos[1] = nxt, t
            d_log, cache = model.decode_step(
                params, cache, jnp.asarray(toks), jnp.asarray(pos))
            p_log, pool = model.decode_step_paged(
                params, pool, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(page_map))
            assert np.array_equal(np.asarray(d_log), np.asarray(p_log))

    def test_recycled_pages_stay_exact(self, lm):
        """Retire-then-admit reuse: pages that held another sequence are
        handed to a new one WITHOUT clearing; the stale keys must be
        invisible — logits equal a fresh-pool run bitwise."""
        model, params, _, _ = lm
        ps, ppn = 4, MAXLEN // 4
        n_pages = ppn
        pages = jnp.arange(ppn, dtype=jnp.int32)
        trash = n_pages
        old = np.asarray([9, 9, 9, 9, 9, 9, 9], np.int32)
        new = np.asarray([4, 17, 2, 33], np.int32)
        pad_new = np.zeros(4, np.int32)
        pad_new[:4] = new

        dirty = model.init_paged_cache(n_pages + 1, ps)
        dirty = model.prefill_paged(params, dirty, pages, jnp.asarray(old),
                                    0, 7, trash, need_logits=False)
        d_log, _ = model.prefill_paged(params, dirty, pages,
                                       jnp.asarray(pad_new), 0, 4, trash)

        fresh = model.init_paged_cache(n_pages + 1, ps)
        f_log, _ = model.prefill_paged(params, fresh, pages,
                                       jnp.asarray(pad_new), 0, 4, trash)
        assert np.array_equal(np.asarray(d_log), np.asarray(f_log))

    def test_chunked_prefill_bitwise_equals_whole(self, lm):
        model, params, _, _ = lm
        ps, ppn = 4, MAXLEN // 4
        pages = jnp.arange(ppn, dtype=jnp.int32)
        trash = int(ppn)
        ids = np.array([5, 11, 2, 29, 7, 3], np.int32)

        whole = model.init_paged_cache(ppn + 1, ps)
        w_log, _ = model.prefill_paged(params, whole, pages,
                                       jnp.asarray(ids), 0, 6, trash)

        chunked = model.init_paged_cache(ppn + 1, ps)
        chunked = model.prefill_paged(params, chunked, pages,
                                      jnp.asarray(ids[:2]), 0, 2, trash,
                                      need_logits=False)
        chunked = model.prefill_paged(params, chunked, pages,
                                      jnp.asarray(ids[2:4]), 2, 2, trash,
                                      need_logits=False)
        c_log, _ = model.prefill_paged(params, chunked, pages,
                                       jnp.asarray(ids[4:]), 4, 2, trash)
        assert np.array_equal(np.asarray(w_log), np.asarray(c_log))


# ------------------------------------------------------------- sampling ----


class TestSampling:
    def test_matches_numpy_reference_per_step(self):
        """Fixed seed, 20 steps x 4 slots of random logits under mixed
        temperature / top-k / top-p: the jitted sampler must pick the
        SAME token id as the numpy oracle at every step, and its key
        evolution must replay exactly."""
        rng = np.random.RandomState(0)
        temps = np.asarray([0.0, 0.7, 1.0, 1.6], np.float32)
        top_ks = np.asarray([0, 5, 0, 12], np.int32)
        top_ps = np.asarray([1.0, 1.0, 0.9, 0.8], np.float32)
        keys = np.stack([threefry_key_data(100 + s) for s in range(4)])
        fn = jax.jit(sample_tokens)
        for _ in range(20):
            logits = rng.randn(4, 50).astype(np.float32) * 2.0
            toks, new_keys = fn(jnp.asarray(logits), jnp.asarray(temps),
                                jnp.asarray(top_ks), jnp.asarray(top_ps),
                                jnp.asarray(keys))
            toks = np.asarray(toks)
            new_keys = np.asarray(new_keys)
            for s in range(4):
                nkd, u = split_key_data(keys[s])
                want = numpy_reference_sample(
                    logits[s], float(temps[s]), int(top_ks[s]),
                    float(top_ps[s]), u)
                assert int(toks[s]) == want
                assert np.array_equal(new_keys[s], nkd)
            keys = new_keys

    def test_greedy_rows_bitwise_argmax(self):
        rng = np.random.RandomState(1)
        logits = rng.randn(3, 40).astype(np.float32)
        toks, _ = sample_tokens(
            jnp.asarray(logits), jnp.zeros(3, jnp.float32),
            jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.float32),
            jnp.zeros((3, 2), jnp.uint32))
        assert np.array_equal(np.asarray(toks), logits.argmax(-1))

    def test_top_k_one_is_argmax_at_any_temperature(self):
        rng = np.random.RandomState(2)
        logits = rng.randn(2, 40).astype(np.float32)
        toks, _ = sample_tokens(
            jnp.asarray(logits), jnp.full(2, 3.0, jnp.float32),
            jnp.ones(2, jnp.int32), jnp.ones(2, jnp.float32),
            jnp.asarray(np.stack([threefry_key_data(s) for s in range(2)])))
        assert np.array_equal(np.asarray(toks), logits.argmax(-1))


# -------------------------------------------------------- engine level ----


class TestPagedEngine:
    @pytest.mark.parametrize("page_size", [4, 16])
    def test_bit_identical_to_dense_engine_any_order(self, lm, page_size):
        """THE acceptance assertion: same prompts through the paged and
        the dense PR-5 engine produce identical greedy token streams,
        under both submission orders, and both match the full-forward
        reference."""
        model, params, _, dense_kernels = lm
        prompts = [[1, 5, 9], [2, 4], [7, 3, 11, 13, 2], [6, 2, 2, 8]]
        lengths = [6, 9, 4, 11]

        deng = GenerationEngine(model, params, max_slots=2, max_len=MAXLEN,
                                max_prompt_len=8, kernels=dense_kernels)
        want = {i: deng.submit(prompts[i], max_new_tokens=lengths[i])
                for i in range(4)}
        want = {i: s.result(timeout=30) for i, s in want.items()}
        deng.close()

        for order in (range(4), reversed(range(4))):
            # private kernels when the page size differs from the module
            # fixture's default pool shape
            eng = make_engine(lm, max_slots=2, page_size=page_size,
                              kernels=None)
            streams = {i: eng.submit(prompts[i], max_new_tokens=lengths[i])
                       for i in order}
            outs = {i: s.result(timeout=30) for i, s in streams.items()}
            eng.close()
            assert outs == want
        assert want[0] == ref_greedy(model, params, prompts[0], 6)

    def test_slot_and_page_reuse_under_pressure(self, lm):
        """8 requests through 2 slots and a pool sized for ~2 typical
        requests: every admission reuses recycled pages, outputs stay
        exact, and the pool drains back to fully free."""
        model, params, _, _ = lm
        eng = make_engine(lm, max_slots=2, page_size=4, num_pages=10,
                          kernels=None)
        streams = [eng.submit([1 + i, 3], max_new_tokens=4 + i)
                   for i in range(8)]
        outs = [s.result(timeout=30) for s in streams]
        for i, o in enumerate(outs):
            assert o == ref_greedy(model, params, [1 + i, 3], 4 + i)
        assert eng.pages_in_use == 0 and eng.free_pages == 10
        snap = eng.metrics.snapshot()
        assert snap["pages_total"] == 10 and snap["pages_peak"] >= 2
        assert snap["page_occupancy"] == 0.0
        eng.close()

    def test_head_of_line_waits_for_pages_no_deadlock(self, lm):
        """A request whose reservation exceeds the free pages waits at
        the queue head (FIFO — page pressure delays, never reorders or
        rejects) and runs once the incumbent retires."""
        model, params, _, _ = lm
        eng = make_engine(lm, max_slots=2, page_size=4, num_pages=8,
                          kernels=None)
        big1 = eng.submit([1, 2], max_new_tokens=30)    # needs 8 pages
        big2 = eng.submit([3, 4], max_new_tokens=30)    # must wait
        assert big1.result(timeout=30) == ref_greedy(model, params,
                                                     [1, 2], 30)
        assert big2.result(timeout=30) == ref_greedy(model, params,
                                                     [3, 4], 30)
        assert eng.pages_in_use == 0
        eng.close()

    def test_long_prompt_admitted_and_chunked(self, lm):
        """The lifted admission wall: prompts up to max_len - 1 are
        accepted and chunked (the dense engine rejects at submit), and
        still decode exactly."""
        model, params, _, dense_kernels = lm
        long_prompt = list(np.random.RandomState(0).randint(1, 60, MAXLEN - 8))
        eng = make_engine(lm, max_slots=2, page_size=4, prefill_chunk=8,
                          kernels=None)
        assert eng.max_prompt_len == MAXLEN - 1
        out = eng.generate(long_prompt, max_new_tokens=4, timeout=30)
        assert out == ref_greedy(model, params, long_prompt, 4)
        snap = eng.metrics.snapshot()
        assert snap["prefill_chunks"] == (MAXLEN - 8 - 1) // 8
        with pytest.raises(ValueError, match="max_prompt_len"):
            eng.submit(list(range(1, MAXLEN + 1)))
        eng.close()

        deng = GenerationEngine(model, params, max_slots=2, max_len=MAXLEN,
                                kernels=dense_kernels)
        with pytest.raises(ValueError, match="max_prompt_len"):
            deng.submit(long_prompt)
        deng.close()

    def test_chunked_prefill_bounds_neighbor_token_gap(self, lm):
        """The TTFT-protection acceptance: while a near-max-length prompt
        prefills chunk by chunk, a decode-only neighbour keeps receiving
        ~one token per engine iteration — with whole-prompt prefill it
        would receive ZERO until the prefill finished. Structural, not
        timed: we count the neighbour's tokens between the long submit
        and the long prompt's first token."""
        model, params, _, _ = lm
        eng = make_engine(lm, max_slots=2, page_size=4, prefill_chunk=4,
                          kernels=None)
        neighbour = eng.submit([5, 1], max_new_tokens=44)
        deadline = time.monotonic() + 10
        while len(neighbour.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(neighbour.tokens) >= 2, "neighbour never started"

        long_prompt = list(np.random.RandomState(1).randint(1, 60, 40))
        n_chunks = -(-40 // 4)  # 10 engine iterations of prefill work
        before = len(neighbour.tokens)
        long_stream = eng.submit(long_prompt, max_new_tokens=2)
        deadline = time.monotonic() + 20
        while not long_stream.tokens and time.monotonic() < deadline:
            time.sleep(0.001)
        gained = len(neighbour.tokens) - before
        assert long_stream.tokens, "long prompt never produced a token"
        assert gained >= n_chunks - 2, (
            f"neighbour gained only {gained} tokens across {n_chunks} "
            "prefill iterations — chunked prefill is not interleaving")
        assert neighbour.result(timeout=30) == ref_greedy(
            model, params, [5, 1], 44)
        assert long_stream.result(timeout=30) == ref_greedy(
            model, params, long_prompt, 2)
        eng.close()

    def test_chunked_prefill_immune_to_neighbour_decode_traffic(self, lm):
        """Regression (review findings 1+2): while a prompt prefills in
        chunks, interleaved decode steps scatter a pad K/V row and split
        a PRNG key for EVERY slot in the batch — so the prefilling slot's
        page-map row must stay parked on trash and its request key must
        arm only at the final chunk. Pre-fix, a decoding neighbour
        corrupted the prompt's first page (greedy) and advanced its
        sampling stream by one split per interleaved step (sampled):
        output depended on neighbour traffic. The contract: a chunked
        request's stream — greedy AND sampled — is identical with and
        without a busy neighbour."""
        model, params, _, _ = lm
        long_prompt = list(np.random.RandomState(2).randint(1, 60, 30))

        def run(with_neighbour, **sample_kw):
            eng = make_engine(lm, max_slots=2, page_size=4, prefill_chunk=4,
                              seed=11, kernels=None)
            nb = None
            if with_neighbour:
                nb = eng.submit([5, 1], max_new_tokens=40)
                deadline = time.monotonic() + 10
                while len(nb.tokens) < 2 and time.monotonic() < deadline:
                    time.sleep(0.001)
                assert len(nb.tokens) >= 2
            out = eng.generate(long_prompt, max_new_tokens=6, timeout=30,
                               **sample_kw)
            if nb is not None:
                nb.result(timeout=30)
            eng.close()
            return out

        assert run(False) == run(True)  # greedy: page integrity
        spec = dict(temperature=0.9, top_k=20, top_p=0.95)
        assert run(False, **spec) == run(True, **spec)  # sampled: key arm

    def test_submit_rejects_unreservable_page_budget(self, lm):
        """Regression (review finding 3): a request whose reservation
        exceeds the WHOLE pool can never be admitted — it must fail at
        submit instead of deadlocking the FIFO head and busy-spinning
        the loop."""
        eng = make_engine(lm, max_slots=2, page_size=16, num_pages=2,
                          kernels=None)
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit([1, 2], max_new_tokens=40)  # needs 3 of 2 pages
        # a fitting request still serves normally afterwards
        assert len(eng.generate([1, 2], max_new_tokens=8, timeout=30)) == 8
        eng.close()

    def test_close_nodrain_releases_reserved_pages(self, lm):
        """Regression (review): failing in-flight streams (close with
        drain=False) must return their reserved pages — a shared
        ServingMetrics would otherwise report a phantom pages_in_use
        forever."""
        eng = make_engine(lm, max_slots=1, page_size=4, kernels=None)
        streams = [eng.submit([1 + i], max_new_tokens=30) for i in range(3)]
        eng.close(drain=False)
        failed = 0
        for s in streams:
            try:
                s.result(timeout=5)
            except RuntimeError:
                failed += 1
        assert failed >= 1
        assert eng.pages_in_use == 0 and eng.free_pages == eng.num_pages
        assert eng.metrics.snapshot()["pages_in_use"] == 0

    def test_sampling_deterministic_across_runs_and_orderings(self, lm):
        """Fixed engine seed => identical sampled streams across fresh
        engines AND reversed admission order; distinct explicit seeds
        diverge."""
        prompts = [[3, 1, 4], [1, 5], [9, 2, 6, 5]]

        def run(order):
            eng = make_engine(lm, max_slots=2, page_size=4, seed=42)
            streams = {i: eng.submit(prompts[i], max_new_tokens=8,
                                     temperature=0.9, top_k=20, top_p=0.95)
                       for i in order}
            outs = {i: s.result(timeout=30) for i, s in streams.items()}
            eng.close()
            return outs

        a = run(range(3))
        b = run(reversed(range(3)))
        assert a == b

        eng = make_engine(lm, max_slots=2, page_size=4, seed=42)
        s1 = eng.generate(prompts[0], max_new_tokens=8, temperature=0.9,
                          top_k=20, top_p=0.95, seed=1, timeout=30)
        s2 = eng.generate(prompts[0], max_new_tokens=8, temperature=0.9,
                          top_k=20, top_p=0.95, seed=2, timeout=30)
        assert s1 != s2  # vanishingly unlikely to collide over 8 draws
        snap = eng.metrics.snapshot()
        assert snap["sampled_tokens"] == 16
        eng.close()

    def test_sampling_rejected_on_dense_engine(self, lm):
        model, params, _, dense_kernels = lm
        deng = GenerationEngine(model, params, max_slots=2, max_len=MAXLEN,
                                kernels=dense_kernels)
        with pytest.raises(ValueError, match="paged"):
            deng.submit([1, 2], temperature=0.8)
        deng.close()

    def test_compile_once_across_mixed_paged_workload(self, lm):
        """The compile-bound acceptance, paged edition: warmup traces
        decode once, prefill once per prompt bucket, the chunk kernel
        once; a mixed workload (greedy + sampled, short + chunked-long,
        staggered admissions, page reuse) traces NOTHING further."""
        model, params, _, _ = lm
        kernels = PagedDecodeKernels(model)  # private: counters from zero
        eng = GenerationEngine(model, params, max_slots=SLOTS,
                               max_len=MAXLEN, kernels=kernels,
                               page_size=4, prefill_chunk=8, max_queue=64)
        eng.warmup()
        assert kernels.decode_traces == 1
        assert kernels.chunk_traces == 1
        assert kernels.prefill_traces == len(eng.prompt_buckets)

        streams = []
        rng = np.random.RandomState(0)
        for i in range(12):
            plen = 1 + (i * 7) % (MAXLEN - 9)
            prompt = [int(t) for t in rng.randint(1, 60, plen)]
            kw = {}
            if i % 3 == 0:
                kw = dict(temperature=0.8, top_k=10, top_p=0.9)
            streams.append(eng.submit(prompt,
                                      max_new_tokens=2 + (i * 5) % 9, **kw))
            if i % 4 == 0:
                time.sleep(0.002)
        for s in streams:
            s.result(timeout=60)
        eng.close()

        assert kernels.decode_traces == 1, "paged decode recompiled"
        assert kernels.chunk_traces == 1, "chunk kernel recompiled"
        assert kernels.prefill_traces == len(eng.prompt_buckets)
        assert kernels._decode._cache_size() == 1
        assert kernels._chunk._cache_size() == 1
        assert kernels._prefill._cache_size() == len(eng.prompt_buckets)

    def test_static_generate_paged_matches_engine(self, lm):
        """Apples-to-apples satellite: static_generate over the SAME
        paged + sampling kernels produces the engine's exact streams —
        greedy and sampled (per-request seeds make sampling
        schedule-invariant)."""
        model, params, kernels, _ = lm
        requests = [([1 + i, 3, 7], 3 if i % 2 else 9) for i in range(6)]

        eng = make_engine(lm)
        greedy_eng = [eng.submit(p, max_new_tokens=m).result(timeout=30)
                      for p, m in requests]
        eng.close()
        greedy_static, steps = static_generate(
            model, params, requests, max_slots=SLOTS, max_len=MAXLEN,
            kernels=kernels)
        assert greedy_static == greedy_eng and steps > 0

        spec = dict(temperature=1.1, top_k=16, top_p=0.9)
        eng = make_engine(lm, seed=7)
        sampled_eng = [eng.submit(p, max_new_tokens=m, **spec)
                       .result(timeout=30) for p, m in requests]
        eng.close()
        sampled_static, _ = static_generate(
            model, params, requests, max_slots=SLOTS, max_len=MAXLEN,
            kernels=kernels, seed=7, sampling=[spec] * len(requests))
        assert sampled_static == sampled_eng
        assert sampled_eng != greedy_eng

    def test_bf16_kv_cache_parity(self, lm):
        """cache_dtype=bf16 on the paged pool end to end: greedy tokens
        stay within a bounded divergence of fp32 (the matmuls run fp32;
        only KV storage rounds), and the first token — produced before
        any rounded KV is re-read with long history — matches."""
        model, params, _, _ = lm
        prompts = [[1, 5, 9], [2, 4], [7, 3, 11, 13, 2], [9, 9, 1, 4]]

        def run(dtype):
            eng = make_engine(lm, page_size=8, cache_dtype=dtype,
                              kernels=None)
            outs = [eng.submit(p, max_new_tokens=12).result(timeout=30)
                    for p in prompts]
            eng.close()
            return outs

        f32 = run(jnp.float32)
        bf16 = run(jnp.bfloat16)
        agree = [sum(a == b for a, b in zip(x, y)) / len(x)
                 for x, y in zip(f32, bf16)]
        assert all(x[0] == y[0] for x, y in zip(f32, bf16))
        assert sum(agree) / len(agree) >= 0.75, agree

    def test_capacity_paged_beats_dense_at_fixed_budget(self, lm):
        """The capacity lever, measured through the real allocator: at
        the KV-byte budget of SLOTS dense lanes, the page pool admits
        >= 2x as many concurrent sequences of a 4:1 short:long mix."""
        model, _, _, _ = lm
        page_size = 8
        lane_pages = -(-MAXLEN // page_size)       # pages per dense lane
        pool = PagePool(SLOTS * lane_pages, page_size, MAXLEN)
        admitted = 0
        while True:
            # 4:1 mix: four short (prompt 6 + 4 new), one long (max_len)
            total = MAXLEN if admitted % 5 == 4 else 6 + 4
            need = pool.pages_for(min(total - 1, MAXLEN))
            if not pool.can_reserve(need):
                break
            pool.alloc(need)
            admitted += 1
        assert admitted >= 2 * SLOTS, (admitted, SLOTS)


# -------------------------------------------------------------- metrics ----


def test_paged_metrics_rows_append_after_golden_order():
    """PR-6 golden contract: paged/sampling/chunk rows render strictly
    AFTER the PR-5 generation rows, which render strictly after the PR-1
    base rows — append-only, never reordered."""
    from bigdl_tpu.serving import ServingMetrics

    m = ServingMetrics()
    m.record_batch(3, 4)
    m.record_served(0.010, 0.004)
    m.record_prefill(5, 8, 0.002)
    m.record_decode_step(3, 4)
    m.record_stream(12, 0.1)
    gen_lines = m.format_table().splitlines()

    m.record_chunk(8, 8)
    m.record_sampled(3)
    m.set_pages(5, 32)
    m.record_reload()
    full_lines = m.format_table().splitlines()
    # row ORDER is the contract (values legitimately move — chunk tokens
    # fold into the prompt-padding ratio): the PR-5 labels stay a strict
    # prefix, new labels append after them
    assert ([ln.split()[0] for ln in full_lines[:len(gen_lines)]]
            == [ln.split()[0] for ln in gen_lines])
    extra = [ln.split()[0] for ln in full_lines[len(gen_lines):]]
    assert extra == ["pages_in_use", "pages_total", "pages_peak",
                     "page_occupancy", "prefill_chunks", "sampled_tokens",
                     "reloads"]
    snap = m.snapshot()
    assert snap["pages_in_use"] == 5 and snap["pages_total"] == 32
    assert snap["pages_peak"] == 5 and snap["prefill_chunks"] == 1
    assert snap["sampled_tokens"] == 3
    assert snap["page_occupancy"] == pytest.approx(5 / 32)
    # chunk tokens fold into the prompt totals
    assert snap["prefills"] == 1
