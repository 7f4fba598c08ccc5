"""Speculative decoding tier (PR 10): draft-verified multi-token
generation over the paged engine.

The load-bearing properties, per the subsystem contract:

- **lossless greedy**: speculative greedy output is token-identical to
  plain greedy decode — float and int8, tp=1 and tp=2, any k, any
  admission order, whatever the draft model proposes;
- the rejection sampler (``ops.sampling.speculative_sample``) exact-
  matches its pure-numpy oracle per step, over accept, reject-residual,
  and full-acceptance-bonus branches;
- sampled speculative streams are deterministic across runs, admission
  orderings, and schedulers (draws are keyed by (request, output
  position), never by step — acceptance-length variance cannot desync a
  stream), and ``static_generate(speculate=...)`` emits the engine's
  exact streams;
- the draft/verify/prefill/chunk kernels each compile exactly once
  across a mixed workload (acceptance lengths are data, not shapes);
- the draft and target lanes live side by side in ONE ``PagePool`` with
  owner-tagged reservations, and both drain to zero on every path —
  retirement, cancel mid-flight, close(drain=False), injected faults.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu import faults
from bigdl_tpu.core.rng import threefry_key_data
from bigdl_tpu.faults import InjectedFault
from bigdl_tpu.nn.layers.attention import Transformer
from bigdl_tpu.ops.sampling import (
    draft_sample,
    filtered_probs,
    numpy_reference_draft,
    numpy_reference_filtered,
    numpy_reference_speculative,
    speculative_sample,
)
from bigdl_tpu.serving import (
    GenerationEngine,
    PagePool,
    SpeculativeKernels,
    StreamCancelled,
    static_generate,
)

SLOTS, MAXLEN = 4, 48


@pytest.fixture(scope="module")
def lm():
    model = Transformer(vocab_size=64, hidden_size=32, num_heads=4,
                        filter_size=64, num_hidden_layers=2)
    params, _ = model.init(jax.random.key(0))
    draft = Transformer(vocab_size=64, hidden_size=16, num_heads=2,
                        filter_size=32, num_hidden_layers=1)
    dparams, _ = draft.init(jax.random.key(1))
    # one kernel set for the whole module: the jit cache persists across
    # engines (each distinct k retraces the verify width once)
    kernels = SpeculativeKernels(model, draft)
    return model, params, draft, dparams, kernels


def make_engine(lm, k=2, shared=True, **kw):
    model, params, draft, dparams, kernels = lm
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("max_len", MAXLEN)
    kw.setdefault("page_size", 4)
    if shared:
        kw.setdefault("kernels", kernels)
    return GenerationEngine(model, params,
                            speculate=(draft, dparams, k), **kw)


def plain_engine(lm, **kw):
    model, params, _, _, _ = lm
    kw.setdefault("max_slots", SLOTS)
    kw.setdefault("max_len", MAXLEN)
    kw.setdefault("page_size", 4)
    return GenerationEngine(model, params, **kw)


def ref_greedy(model, params, prompt, n):
    ids = [int(t) for t in prompt]
    out = []
    for _ in range(n):
        # jitted per length (see test_paged_generation.ref_greedy)
        logits, _ = jax.jit(model.apply)(params, jnp.asarray([ids]))
        tok = int(np.asarray(logits)[0, -1].argmax())
        ids.append(tok)
        out.append(tok)
    return out


PROMPTS = [[1, 5, 9], [2, 4], [7, 3, 11, 13, 2], [6, 2, 2, 8]]
LENS = [6, 9, 4, 11]


# ------------------------------------------------------------- sampler ----


class TestSpeculativeSampler:
    def test_filtered_probs_matches_oracle(self):
        """Vocab-order filtered distributions: sampled rows match the
        numpy mirror within float tolerance; greedy rows are EXACT
        one-hot argmax deltas (the lossless-greedy foundation)."""
        rng = np.random.RandomState(0)
        temps = np.asarray([0.0, 0.7, 1.3, 0.0], np.float32)
        tks = np.asarray([0, 5, 0, 3], np.int32)
        tps = np.asarray([1.0, 1.0, 0.85, 0.9], np.float32)
        logits = (rng.randn(4, 40) * 2).astype(np.float32)
        got = np.asarray(filtered_probs(jnp.asarray(logits),
                                        jnp.asarray(temps),
                                        jnp.asarray(tks),
                                        jnp.asarray(tps)))
        for s in range(4):
            want = numpy_reference_filtered(logits[s], float(temps[s]),
                                            int(tks[s]), float(tps[s]))
            if temps[s] <= 0:
                assert np.array_equal(got[s], want)   # exact delta
            else:
                np.testing.assert_allclose(got[s], want, atol=1e-6)
                np.testing.assert_allclose(got[s].sum(), 1.0, atol=1e-5)

    def test_speculative_sample_matches_numpy_oracle_per_step(self):
        """The acceptance anchor: 15 steps x 4 slots (greedy + sampled
        rows mixed) of drafts proposed by ``draft_sample`` on random
        draft logits, verified against random target logits — the
        jitted sampler must pick the SAME accepted count and the SAME
        emitted tokens as the oracle at every step, across accept,
        reject-residual, and full-acceptance branches."""
        rng = np.random.RandomState(0)
        s_, k, vocab = 4, 3, 50
        temps = np.asarray([0.0, 0.8, 1.3, 0.0], np.float32)
        tks = np.asarray([0, 6, 0, 0], np.int32)
        tps = np.asarray([1.0, 0.9, 0.85, 1.0], np.float32)
        keys = np.stack([threefry_key_data(100 + s) for s in range(s_)])
        fspec = jax.jit(speculative_sample)
        fdraft = jax.jit(draft_sample)
        branch_seen = set()
        for step in range(15):
            out_base = rng.randint(0, 40, (s_,)).astype(np.int32)
            d_toks, d_dists = [], []
            # bias the target toward the draft every other step so the
            # accept branch is exercised, not just immediate rejection
            tlog = (rng.randn(s_, k + 1, vocab) * 2).astype(np.float32)
            for i in range(k):
                if step % 2:
                    dlog = tlog[:, i] + rng.randn(
                        s_, vocab).astype(np.float32) * 0.05
                else:
                    dlog = (rng.randn(s_, vocab) * 2).astype(np.float32)
                t_, di_ = fdraft(jnp.asarray(dlog), jnp.asarray(temps),
                                 jnp.asarray(tks), jnp.asarray(tps),
                                 jnp.asarray(keys),
                                 jnp.asarray(out_base + i))
                t_, di_ = np.asarray(t_), np.asarray(di_)
                for s in range(s_):
                    wt, wd = numpy_reference_draft(
                        dlog[s], float(temps[s]), int(tks[s]),
                        float(tps[s]), keys[s], int(out_base[s]) + i)
                    assert int(t_[s]) == wt
                    np.testing.assert_allclose(di_[s], wd, atol=1e-6)
                d_toks.append(t_)
                d_dists.append(di_)
            d_toks = np.stack(d_toks, 1)
            d_dists = np.stack(d_dists, 1)
            n_, toks_ = fspec(jnp.asarray(tlog), jnp.asarray(d_toks),
                              jnp.asarray(d_dists), jnp.asarray(temps),
                              jnp.asarray(tks), jnp.asarray(tps),
                              jnp.asarray(keys), jnp.asarray(out_base))
            n_, toks_ = np.asarray(n_), np.asarray(toks_)
            for s in range(s_):
                wn, wtoks = numpy_reference_speculative(
                    tlog[s], d_toks[s], d_dists[s], float(temps[s]),
                    int(tks[s]), float(tps[s]), keys[s],
                    int(out_base[s]))
                assert int(n_[s]) == wn
                assert [int(t) for t in toks_[s, :wn + 1]] == wtoks
                branch_seen.add("full" if wn == k
                                else "reject" if wn < k else "?")
        assert branch_seen >= {"full", "reject"}, branch_seen

    def test_all_greedy_batch_is_exact_prefix_match(self):
        """The greedy fast path: accepted = longest prefix where the
        draft equals the target argmax; every emitted token is a target
        argmax."""
        rng = np.random.RandomState(1)
        s_, k, vocab = 3, 3, 30
        tlog = (rng.randn(s_, k + 1, vocab)).astype(np.float32)
        am = tlog.argmax(-1)
        d_toks = am[:, :k].copy().astype(np.int32)
        d_toks[0, 1] = (d_toks[0, 1] + 1) % vocab    # mismatch at i=1
        d_toks[2, 0] = (d_toks[2, 0] + 1) % vocab    # mismatch at i=0
        dd = np.zeros((s_, k, vocab), np.float32)
        n_, toks_ = speculative_sample(
            jnp.asarray(tlog), jnp.asarray(d_toks), jnp.asarray(dd),
            jnp.zeros(s_, jnp.float32), jnp.zeros(s_, jnp.int32),
            jnp.ones(s_, jnp.float32), jnp.zeros((s_, 2), jnp.uint32),
            jnp.zeros(s_, jnp.int32))
        n_, toks_ = np.asarray(n_), np.asarray(toks_)
        assert list(n_) == [1, 3, 0]
        for s in range(s_):
            n = int(n_[s])
            assert np.array_equal(toks_[s, :n], am[s, :n])
            assert toks_[s, n] == am[s, n]

    def test_identical_distributions_accept_everything(self):
        """When the draft IS the target (same filtered distribution and
        it proposed a kept token), the accept ratio is 1 and u < 1
        always — full acceptance, the E[speedup] upper bound."""
        rng = np.random.RandomState(2)
        s_, k, vocab = 2, 4, 40
        temps = np.asarray([0.9, 0.0], np.float32)
        tks = np.zeros(2, np.int32)
        tps = np.ones(2, np.float32)
        keys = np.stack([threefry_key_data(s) for s in range(2)])
        row = (rng.randn(s_, vocab)).astype(np.float32)
        tlog = np.repeat(row[:, None], k + 1, axis=1)
        fp = np.asarray(filtered_probs(jnp.asarray(row),
                                       jnp.asarray(temps),
                                       jnp.asarray(tks),
                                       jnp.asarray(tps)))
        d_dists = np.repeat(fp[:, None], k, axis=1)
        d_toks = fp.argmax(-1)[:, None].repeat(k, 1).astype(np.int32)
        n_, _ = speculative_sample(
            jnp.asarray(tlog), jnp.asarray(d_toks), jnp.asarray(d_dists),
            jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
            jnp.asarray(keys), jnp.zeros(s_, jnp.int32))
        assert list(np.asarray(n_)) == [k, k]


# --------------------------------------------------------- model level ----


def test_verify_step_scores_like_sequential_decode(lm):
    """``decode_verify_paged`` row i == the logits a sequential
    ``decode_step_paged`` chain produces at the same position: argmax
    chains identical, logits within float tolerance (multi-row vs
    single-row reassociation only)."""
    model, params, _, _, _ = lm
    ps = 4
    ppn = MAXLEN // ps
    trash = 2 * ppn
    prompt = np.array([5, 11, 2, 29, 7], np.int32)
    rng = np.random.RandomState(3)
    pages = rng.choice(2 * ppn, ppn, replace=False).astype(np.int32)
    pm = np.full((2, ppn), trash, np.int32)
    pm[1] = pages

    def prefilled():
        cache = model.init_paged_cache(2 * ppn + 1, ps)
        logits, cache = model.prefill_paged(
            params, cache, jnp.asarray(pages), jnp.asarray(prompt), 0, 5,
            trash)
        return int(np.asarray(logits).argmax()), cache

    t0, cache = prefilled()
    seq_logits = []
    feed, pos = t0, 5
    for _ in range(4):
        tok = np.zeros(2, np.int32)
        posv = np.zeros(2, np.int32)
        tok[1], posv[1] = feed, pos
        lg, cache = model.decode_step_paged(
            params, cache, jnp.asarray(tok), jnp.asarray(posv),
            jnp.asarray(pm))
        seq_logits.append(np.asarray(lg)[1])
        feed = int(seq_logits[-1].argmax())
        pos += 1
    chain = [int(l.argmax()) for l in seq_logits]

    _, cache2 = prefilled()
    vt = np.zeros((2, 4), np.int32)
    vt[1] = [t0] + chain[:3]
    vp = np.zeros(2, np.int32)
    vp[1] = 5
    vlog, _ = model.decode_verify_paged(
        params, cache2, jnp.asarray(vt), jnp.asarray(vp),
        jnp.asarray(pm), trash)
    vlog = np.asarray(vlog)[1]
    assert [int(vlog[i].argmax()) for i in range(4)] == chain
    np.testing.assert_allclose(vlog, np.stack(seq_logits), atol=1e-5)


# -------------------------------------------------------- engine level ----


class TestSpeculativeEngine:
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_greedy_identity_any_k_any_order(self, lm, k):
        """THE acceptance assertion: speculative greedy == plain greedy
        token for token, for any k and either admission order, and both
        match the full-forward reference."""
        model, params, _, _, _ = lm
        peng = plain_engine(lm, max_slots=2)
        want = [peng.submit(PROMPTS[i], max_new_tokens=LENS[i])
                .result(timeout=60) for i in range(4)]
        peng.close()
        for order in (range(4), reversed(range(4))):
            eng = make_engine(lm, k=k, max_slots=2)
            streams = {i: eng.submit(PROMPTS[i], max_new_tokens=LENS[i])
                       for i in order}
            outs = {i: s.result(timeout=120) for i, s in streams.items()}
            eng.close()
            assert [outs[i] for i in range(4)] == want
        assert want[0] == ref_greedy(model, params, PROMPTS[0], LENS[0])

    def test_self_draft_accepts_most_tokens(self, lm):
        """Draft == target is the acceptance upper bound: greedy
        proposals match the verify argmax almost always (only budget
        truncation at stream ends loses a few), and output stays
        identical — speculation is lossless even at 100% acceptance."""
        model, params, _, _, _ = lm
        eng = GenerationEngine(model, params, max_slots=2, max_len=MAXLEN,
                               page_size=4, speculate=(model, params, 3))
        outs = [eng.submit(p, max_new_tokens=m).result(timeout=120)
                for p, m in zip(PROMPTS, LENS)]
        snap = eng.metrics.snapshot()
        eng.close()
        peng = plain_engine(lm, max_slots=2)
        want = [peng.submit(p, max_new_tokens=m).result(timeout=60)
                for p, m in zip(PROMPTS, LENS)]
        peng.close()
        assert outs == want
        assert snap["acceptance_rate"] >= 0.5, snap["acceptance_rate"]
        assert snap["verify_steps"] > 0
        # amortization: far fewer verify forwards than emitted tokens
        assert snap["verify_steps"] < snap["tokens_out"]

    def test_chunked_prompt_and_max_len_wall_identity(self, lm):
        """A chunked long prompt and a generation that runs into the
        max_len wall both stay token-identical to the plain engine."""
        model, params, _, _, _ = lm
        long_prompt = list(np.random.RandomState(0).randint(
            1, 60, MAXLEN - 8))
        peng = plain_engine(lm, max_slots=2, prefill_chunk=8)
        want_long = peng.generate(long_prompt, max_new_tokens=4,
                                  timeout=60)
        want_wall = peng.generate([1, 2, 3], max_new_tokens=200,
                                  timeout=120)
        peng.close()
        eng = make_engine(lm, k=4, max_slots=2, prefill_chunk=8,
                          shared=False, kernels=None)
        assert eng.generate(long_prompt, max_new_tokens=4,
                            timeout=120) == want_long
        got_wall = eng.generate([1, 2, 3], max_new_tokens=200,
                                timeout=120)
        eng.close()
        assert got_wall == want_wall and len(got_wall) == MAXLEN - 3

    def test_eos_truncation_identity(self, lm):
        """An EOS inside an accepted run truncates the stream exactly
        where plain decode stops — tokens past it are never emitted."""
        model, params, _, _, _ = lm
        ref = ref_greedy(model, params, [6, 2, 2, 8], 12)
        eos = ref[min(2, len(ref) - 1)]
        peng = plain_engine(lm, max_slots=2, eos_id=eos)
        want = peng.generate([6, 2, 2, 8], max_new_tokens=12, timeout=60)
        peng.close()
        for k in (1, 3):
            eng = make_engine(lm, k=k, max_slots=2, eos_id=eos)
            got = eng.generate([6, 2, 2, 8], max_new_tokens=12,
                               timeout=120)
            eng.close()
            assert got == want, (k, got, want)

    def test_sampled_deterministic_across_runs_and_orderings(self, lm):
        """Per-(request, output-position) keys: fixed engine seed =>
        identical sampled streams across fresh engines AND reversed
        admission order (acceptance-length variance cannot desync);
        distinct explicit seeds diverge."""
        prompts = [[3, 1, 4], [1, 5], [9, 2, 6, 5]]
        spec = dict(temperature=0.9, top_k=20, top_p=0.95)

        def run(order):
            eng = make_engine(lm, k=2, max_slots=2, seed=42)
            streams = {i: eng.submit(prompts[i], max_new_tokens=8, **spec)
                       for i in order}
            outs = {i: s.result(timeout=120) for i, s in streams.items()}
            eng.close()
            return outs

        a = run(range(3))
        b = run(reversed(range(3)))
        assert a == b
        eng = make_engine(lm, k=2, max_slots=2, seed=42)
        s1 = eng.generate(prompts[0], max_new_tokens=8, seed=1,
                          timeout=120, **spec)
        s2 = eng.generate(prompts[0], max_new_tokens=8, seed=2,
                          timeout=120, **spec)
        snap = eng.metrics.snapshot()
        eng.close()
        assert s1 != s2
        assert snap["sampled_tokens"] == 16

    def test_static_generate_speculative_matches_engine(self, lm):
        """``static_generate(speculate=...)`` over the SAME kernels
        emits the engine's exact streams — greedy and sampled (the
        schedule-invariance gate the speculative bench runs)."""
        model, params, draft, dparams, kernels = lm
        requests = [([1 + i, 3, 7], 3 if i % 2 else 9) for i in range(6)]

        eng = make_engine(lm, k=2)
        greedy_eng = [eng.submit(p, max_new_tokens=m).result(timeout=120)
                      for p, m in requests]
        eng.close()
        greedy_static, rounds = static_generate(
            model, params, requests, max_slots=SLOTS, max_len=MAXLEN,
            page_size=4, kernels=kernels,
            speculate=(draft, dparams, 2))
        assert greedy_static == greedy_eng and rounds > 0

        spec = dict(temperature=1.1, top_k=16, top_p=0.9)
        eng = make_engine(lm, k=2, seed=7)
        sampled_eng = [eng.submit(p, max_new_tokens=m, **spec)
                       .result(timeout=120) for p, m in requests]
        eng.close()
        sampled_static, _ = static_generate(
            model, params, requests, max_slots=SLOTS, max_len=MAXLEN,
            page_size=4, kernels=kernels, seed=7,
            speculate=(draft, dparams, 2),
            sampling=[spec] * len(requests))
        assert sampled_static == sampled_eng
        assert sampled_eng != greedy_eng

    def test_compile_once_across_mixed_speculative_workload(self, lm):
        """Warmup traces draft once, verify once, chunk once, prefill /
        draft_write once per bucket; a mixed workload (greedy + sampled,
        short + chunked-long, staggered admissions, every acceptance
        length) traces NOTHING further — acceptance is data, not
        shape."""
        model, params, draft, dparams, _ = lm
        kernels = SpeculativeKernels(model, draft)  # private counters
        eng = GenerationEngine(model, params, max_slots=SLOTS,
                               max_len=MAXLEN, kernels=kernels,
                               page_size=4, prefill_chunk=8,
                               max_queue=64,
                               speculate=(draft, dparams, 2))
        eng.warmup()
        n_buckets = len(eng.prompt_buckets)
        # draft_write serves chunk AND final-bucket shapes through one
        # jit: a prefill_chunk equal to a bucket width shares its trace
        n_dw = len(set(eng.prompt_buckets) | {eng.prefill_chunk})
        assert kernels.draft_traces == 1
        assert kernels.verify_traces == 1
        assert kernels.chunk_traces == 1
        assert kernels.prefill_traces == n_buckets
        assert kernels.draft_write_traces == n_dw

        streams = []
        rng = np.random.RandomState(0)
        for i in range(10):
            plen = 1 + (i * 7) % (MAXLEN - 9)
            prompt = [int(t) for t in rng.randint(1, 60, plen)]
            kw = {}
            if i % 3 == 0:
                kw = dict(temperature=0.8, top_k=10, top_p=0.9)
            streams.append(eng.submit(prompt,
                                      max_new_tokens=2 + (i * 5) % 9,
                                      **kw))
            if i % 4 == 0:
                time.sleep(0.002)
        for s in streams:
            s.result(timeout=240)
        eng.close()

        assert kernels.draft_traces == 1, "draft step recompiled"
        assert kernels.verify_traces == 1, "verify step recompiled"
        assert kernels.chunk_traces == 1
        assert kernels.prefill_traces == n_buckets
        assert kernels.draft_write_traces == n_dw
        assert kernels._draft._cache_size() == 1
        assert kernels._verify._cache_size() == 1
        assert kernels._prefill._cache_size() == n_buckets

    def test_int8_speculative_identity(self, lm):
        """The quantized tier composes: int8 GEMMs + int8 KV pages on
        BOTH models, speculative output == plain int8 output."""
        model, params, draft, dparams, _ = lm
        e1 = plain_engine(lm, max_slots=2, cache_dtype="int8",
                          quantize="int8", kernels=None)
        want = [e1.submit(p, max_new_tokens=m).result(timeout=120)
                for p, m in zip(PROMPTS[:3], LENS[:3])]
        e1.close()
        e2 = GenerationEngine(model, params, max_slots=2, max_len=MAXLEN,
                              page_size=4, cache_dtype="int8",
                              quantize="int8",
                              speculate=(draft, dparams, 2))
        got = [e2.submit(p, max_new_tokens=m).result(timeout=120)
               for p, m in zip(PROMPTS[:3], LENS[:3])]
        e2.close()
        assert got == want

    @pytest.mark.slow  # tp2 mesh leg (~27 s) — same tier as the other
    # sharded identity legs (async/int8 tp2 are slow-marked too)
    def test_tp2_token_identity(self, lm):
        """tp=2 over the speculative tier: both models shard on the
        serving mesh, greedy decode equals the single-device engine
        token for token, and the verify step compiles once."""
        from bigdl_tpu.parallel import serving_meshes

        model, params, draft, dparams, _ = lm
        peng = plain_engine(lm, max_slots=2)
        want = [peng.submit(p, max_new_tokens=m).result(timeout=60)
                for p, m in zip(PROMPTS[:3], LENS[:3])]
        peng.close()
        mesh = serving_meshes(1, 2)[0]
        eng = GenerationEngine(model, params, max_slots=2, max_len=MAXLEN,
                               page_size=4, mesh=mesh,
                               speculate=(draft, dparams, 2))
        eng.warmup()
        outs = [eng.submit(p, max_new_tokens=m).result(timeout=240)
                for p, m in zip(PROMPTS[:3], LENS[:3])]
        assert eng.kernels.verify_traces == 1
        eng.close()
        assert outs == want

    def test_submit_rejects_unreservable_double_lane_budget(self, lm):
        """The two-lane reservation doubles the page budget: a request
        whose TARGET lane alone would fit must still be rejected at
        submit when target + draft cannot ever fit the pool."""
        eng = make_engine(lm, k=2, max_slots=2, page_size=16,
                          num_pages=3, shared=False, kernels=None)
        with pytest.raises(ValueError, match="KV pages"):
            eng.submit([1, 2], max_new_tokens=30)   # 2 x 2 = 4 of 3
        assert len(eng.generate([1, 2], max_new_tokens=8,
                                timeout=120)) == 8
        eng.close()

    def test_pool_owner_tags_drain_on_cancel_and_failure(self, lm):
        """Both lanes of every slot return to the pool when a stream is
        cancelled mid-flight and when close(drain=False) fails the
        rest — per-owner gauges drain to zero, not just the total."""
        eng = make_engine(lm, k=2, max_slots=1)
        s1 = eng.submit([1, 2], max_new_tokens=40)
        deadline = time.monotonic() + 10
        while len(s1.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(s1.tokens) >= 2
        assert eng._pool.in_use_by("target") > 0
        assert eng._pool.in_use_by("draft") > 0
        s1.cancel()
        with pytest.raises(StreamCancelled):
            s1.result(timeout=30)
        assert eng._pool.in_use_by("target") == 0
        assert eng._pool.in_use_by("draft") == 0
        streams = [eng.submit([3 + i], max_new_tokens=30)
                   for i in range(3)]
        eng.close(drain=False)
        failed = 0
        for s in streams:
            try:
                s.result(timeout=10)
            except RuntimeError:
                failed += 1
        assert failed >= 1
        assert eng.pages_in_use == 0
        assert eng._pool.in_use_by("target") == 0
        assert eng._pool.in_use_by("draft") == 0
        assert eng.metrics.snapshot()["pages_in_use"] == 0

    @pytest.mark.parametrize("site", ["engine.draft", "engine.verify"])
    def test_fault_site_fails_streams_and_releases_both_lanes(self, lm,
                                                             site):
        """The new fault sites: an armed draft/verify fault fails the
        in-flight streams with the injected error (the engine's step
        contract — a consumed donated cache cannot be retried) and BOTH
        models' pages return to the pool."""
        eng = make_engine(lm, k=2, max_slots=2)
        with faults.armed(site, nth=2, only=lambda engine=None, **_:
                          engine is eng):
            streams = [eng.submit([1 + i, 4], max_new_tokens=20)
                       for i in range(2)]
            errors = 0
            for s in streams:
                try:
                    s.result(timeout=60)
                except InjectedFault:
                    errors += 1
            assert errors == 2
        assert eng.pages_in_use == 0
        assert eng._pool.in_use_by("target") == 0
        assert eng._pool.in_use_by("draft") == 0
        with pytest.raises(RuntimeError, match="step failure"):
            eng.submit([1])
        eng.close()

    def test_speculate_knob_validation(self, lm):
        model, params, draft, dparams, kernels = lm
        with pytest.raises(ValueError, match="triple"):
            GenerationEngine(model, params, speculate=(dparams, 2))
        with pytest.raises(ValueError, match="k must be"):
            GenerationEngine(model, params,
                             speculate=(draft, dparams, 0))
        with pytest.raises(ValueError, match="go together"):
            GenerationEngine(model, params, kernels=kernels,
                             max_len=MAXLEN)
        with pytest.raises(ValueError, match="vocab"):
            bad = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                              filter_size=32, num_hidden_layers=1)
            SpeculativeKernels(model, bad)

    def test_speculative_engine_behind_router_and_replicaset(self, lm):
        """The model-family wiring: a draft+target pair serves behind
        the ModelRouter, and a LIST of speculative engines registers as
        a ReplicaSet — outputs through the front door equal plain
        greedy decode."""
        from bigdl_tpu.serving import ModelRouter

        model, params, _, _, _ = lm
        peng = plain_engine(lm, max_slots=2)
        want = [peng.submit(p, max_new_tokens=m).result(timeout=60)
                for p, m in zip(PROMPTS[:3], LENS[:3])]
        peng.close()
        router = ModelRouter()
        router.register("lm", make_engine(lm, k=2, max_slots=2))
        router.register("lm-fleet", [make_engine(lm, k=2, max_slots=2)
                                     for _ in range(2)])
        outs = [router.submit("lm", p, max_new_tokens=m)
                .result(timeout=120)
                for p, m in zip(PROMPTS[:3], LENS[:3])]
        fleet = [router.submit("lm-fleet", p, max_new_tokens=m)
                 .result(timeout=120)
                 for p, m in zip(PROMPTS[:3], LENS[:3])]
        router.close()
        assert outs == want
        assert fleet == want


# -------------------------------------------------------------- metrics ----


def test_speculative_metrics_rows_append_after_golden_order():
    """PR-10 golden contract: speculative rows render strictly AFTER
    the PR-9 quantized block, which renders after the PR-7 replica
    block — append-only, never reordered."""
    from bigdl_tpu.serving import ServingMetrics

    m = ServingMetrics()
    m.record_batch(3, 4)
    m.record_served(0.010, 0.004)
    m.record_prefill(5, 8, 0.002)
    m.record_decode_step(3, 4)
    m.record_chunk(8, 8)
    m.set_pages(5, 32)
    m.record_reload()
    m.set_replicas(2, 2, {"r0": 1})
    m.set_kv_cache(4096, "int8")
    m.set_quantized_gemms(13)
    pre_lines = m.format_table().splitlines()

    m.record_verify_step(8, 5, 5)
    full_lines = m.format_table().splitlines()
    assert ([ln.split()[0] for ln in full_lines[:len(pre_lines)]]
            == [ln.split()[0] for ln in pre_lines])
    extra = [ln.split()[0] for ln in full_lines[len(pre_lines):]]
    assert extra == ["draft_tokens", "accepted_tokens", "acceptance_rate",
                     "verify_steps"]
    snap = m.snapshot()
    assert snap["draft_tokens"] == 8
    assert snap["accepted_tokens"] == 5
    assert snap["acceptance_rate"] == pytest.approx(5 / 8)
    assert snap["verify_steps"] == 1
    # extra emitted tokens folded into tokens_out (prefill 1 + decode 3
    # + 5 speculative extras)
    assert snap["tokens_out"] == 9
    keys = list(snap)
    # the PR-10 block sits immediately before the PR-11 step-timeline,
    # PR-12 prefix-cache, PR-18 KV-tier, PR-19 async-scheduling, and
    # PR-20 structured-generation keys (append-only: each PR's rows
    # land AFTER every earlier block)
    assert keys[-31:-27] == ["draft_tokens", "accepted_tokens",
                            "acceptance_rate", "verify_steps"]


def test_page_pool_owner_tagging_unit():
    """PagePool owner accounting: tags ride alloc/release by page id,
    untagged allocs stay anonymous, totals always reconcile."""
    pool = PagePool(8, 4, 16)
    a = pool.alloc(2, owner="target")
    b = pool.alloc(3, owner="draft")
    c = pool.alloc(1)
    assert pool.in_use == 6
    assert pool.in_use_by("target") == 2
    assert pool.in_use_by("draft") == 3
    assert pool.in_use_by("nobody") == 0
    pool.release(b)
    assert pool.in_use_by("draft") == 0 and pool.in_use == 3
    pool.release(a)
    pool.release(c)
    assert pool.in_use == 0 and pool.in_use_by("target") == 0
    # recycled pages take fresh tags
    d = pool.alloc(4, owner="draft")
    assert pool.in_use_by("draft") == 4
    pool.release(d)
    assert pool.in_use_by("draft") == 0
