"""GenerationEngine — continuous-batching autoregressive generation.

PR 1's :class:`~bigdl_tpu.serving.service.InferenceService` batches
run-to-completion requests, the wrong shape for autoregressive decoding:
one long sequence holds the whole micro-batch hostage and new requests
wait for the full batch to finish. This module is the iteration-level
scheduler (Orca, OSDI '22; vLLM's slot-managed KV cache, SOSP '23 —
PAPERS.md): the unit of scheduling is ONE decode step, not one request.

Design, in XLA terms:

- **fixed-shape slot table** — the KV cache is ``(max_slots, heads,
  max_len, head_dim)`` per layer, built once by ``model.init_cache``.
  The jitted decode step closes over nothing dynamic: tokens ``(S,)``
  and positions ``(S,)`` are the only per-step inputs, so the loop
  compiles exactly once at warmup and NEVER recompiles, however
  admissions and retirements reshuffle the slots (test-enforced via the
  :class:`DecodeKernels` trace counters).
- **donated cache** — the cache pytree is donated to every prefill and
  decode call, so the steady-state loop allocates no new cache buffers.
- **admission between steps** — new requests prefill into free slots at
  decode-step boundaries (one bucket-padded prompt forward each);
  finished sequences (EOS, max-tokens, deadline expiry, cancel) retire
  mid-flight and free their slot immediately.
- **iterator-futures** — ``submit`` returns a :class:`GenerationStream`
  that yields tokens as the loop produces them; time-to-first-token and
  per-stream tokens/sec land in the shared
  :class:`~bigdl_tpu.serving.metrics.ServingMetrics`.

:func:`static_generate` is the run-to-completion baseline over the SAME
jitted kernels — ``bench.py --mode serving --generate`` and the CI smoke
gate measure continuous vs static tokens/sec with it (the win is
scheduling, so it shows even on one core).

PR 6 replaces the dense slot lanes with a **paged KV cache**
(:class:`PagedDecodeKernels`, the default for paged-capable models):
per layer the cache is a shared pool of fixed-size pages plus a per-slot
int32 page map, reserved/released by the host-side
:class:`~bigdl_tpu.serving.paging.PagePool` as sequences are admitted
and retire — KV memory scales with each request's actual token budget
instead of ``max_slots x max_len``, the direct capacity lever on
concurrent users. Riding on the paged step:

- **in-step sampling** — temperature / top-k / top-p run INSIDE the
  jitted decode step with per-request params batched as ``(max_slots,)``
  arrays and one raw threefry key per slot (``core.rng``); a request's
  stream depends only on its seed, so sampled output is deterministic
  across runs, admission orderings, and schedulers. Greedy
  (``temperature=0``, the default) stays bit-identical to the dense
  PR-5 engine — test-enforced.
- **chunked prefill** — prompts longer than ``prefill_chunk`` advance
  one chunk per engine iteration, interleaved with decode steps, so a
  max-length prompt no longer stalls every neighbour's next token; the
  ``max_prompt_len < max_len`` admission wall is gone (any prompt up to
  ``max_len - 1`` is admitted and chunked).

The dense :class:`DecodeKernels` path is kept verbatim as the PR-5
baseline (and for decode-capable models without the paged API); the
bit-identity acceptance tests decode the same prompts through both.

PR 12 adds **prefix caching** (``prefix_cache=True``, paged engines
only): retiring sequences publish their full prompt pages to a
host-side radix index (``serving.prefix_cache.PrefixCache``) keyed by
(model version, page-aligned token prefix); an admission whose prompt
matches attaches those pages by refcounted reference and chunked
prefill SKIPS the covered chunks entirely — only the divergent tail
runs the chunk/prefill kernels. Zero device-side changes: the kernels
already take page ids as data, so compile-once is untouched, and
because cached bits equal freshly-computed bits, output with the cache
on is bit-identical to off (test-enforced). Unreferenced cached
prefixes evict LRU under page pressure before the FIFO admission wait
triggers.
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time
import uuid
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import faults
from bigdl_tpu.core.rng import request_seed, threefry_key_data
from bigdl_tpu.faults import StallError, Watchdog
from bigdl_tpu.obs.timeline import StepTimeline
from bigdl_tpu.obs.trace import submit_trace
from bigdl_tpu.ops.sampling import (
    EXTRA_STREAM,
    draft_sample,
    filtered_probs,
    pick_token,
    position_uniform,
    sample_tokens,
    speculative_sample,
)
from bigdl_tpu.serving.batcher import bucket_sizes_for
from bigdl_tpu.utils.errors import fresh_exception
from bigdl_tpu.serving.errors import (
    DeadlineExceeded,
    GrammarViolation,
    Overloaded,
    StreamCancelled,
)
from bigdl_tpu.serving.kv_tiers import HostPageStore
from bigdl_tpu.serving.metrics import ServingMetrics
from bigdl_tpu.serving.paging import PagePool, page_bytes, pages_per_lane
from bigdl_tpu.serving.prefix_cache import PrefixCache

log = logging.getLogger("bigdl_tpu.serving")

_SENTINEL = object()


class _TraceCounts:
    """Mutable trace counters, deliberately a separate tiny object: the
    jitted closures capture THIS (and the model), never the object that
    owns the pjit executables — a closure capturing the owner would put
    it in a cycle through the C++ pjit object, which the GC cannot
    break, leaking model+params on an unclosed engine."""

    __slots__ = ("prefill", "decode", "chunk")

    def __init__(self):
        self.prefill = 0
        self.decode = 0
        self.chunk = 0


def _jit(fn, cache_sharding, donate: bool):
    """``jax.jit`` with the cache (argument 1) donated. A sharded engine
    traces under its cache's mesh, which is how attention dispatch sees
    it: a Mosaic kernel has no GSPMD partitioning rule, so auto-selection
    takes the XLA path there (``ops/attention.py:_kernel_platform``)."""
    if cache_sharding is None:
        return jax.jit(fn, donate_argnums=(1,) if donate else ())
    from bigdl_tpu.parallel.mesh import use_mesh

    mesh = (cache_sharding[0] if isinstance(cache_sharding, tuple)
            else cache_sharding).mesh

    def in_mesh(*args):
        with use_mesh(mesh):
            return fn(*args)

    return jax.jit(in_mesh, donate_argnums=(1,) if donate else ())


def _cache_pinner(cache_sharding):
    """Constraint applied to the new cache INSIDE every jitted kernel
    when the engine runs sharded: pins the output cache to the exact
    NamedSharding the input cache carries, so (a) donation of the sharded
    cache holds call after call (donor and result layouts match) and
    (b) GSPMD can never drift the cache layout between steps, which would
    miss the executable cache and break compile-once. ``None`` (the
    single-device engine) is the identity.

    An int8 paged cache passes a PAIR ``(page_sharding, scale_sharding)``
    — 4-D page pools pin to the heads-sharded spec, the 2-D per-token
    scale pools to the replicated one (``parallel.tp.kv_scale_pspec``)."""
    if cache_sharding is None:
        return lambda cache: cache

    def pin(cache):
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, cache,
            _cache_sharding_tree(cache, cache_sharding))

    return pin


def _cache_sharding_tree(cache, cache_sharding):
    """Expand an engine cache sharding (a single sharding, or the int8
    (pages, scales) pair) into the per-leaf tree both ``jax.device_put``
    and the in-jit pinner consume — the ONE place the leaf-to-sharding
    dispatch rule lives (4-D leaves are page pools, 2-D leaves are
    per-token scale pools)."""
    if isinstance(cache_sharding, tuple):
        page_s, scale_s = cache_sharding
        return jax.tree_util.tree_map(
            lambda a: page_s if a.ndim == 4 else scale_s, cache)
    return jax.tree_util.tree_map(lambda _: cache_sharding, cache)


class DecodeKernels:
    """The jitted ``(prefill, decode)`` pair over a decode-capable model
    (one exposing ``init_cache`` / ``prefill`` / ``decode_step``, e.g.
    ``nn.Transformer`` in ``language_model`` mode).

    Greedy argmax sampling happens INSIDE the jitted step so only the
    ``int32`` next-token vector crosses to the host each iteration.
    ``prefill_traces`` / ``decode_traces`` increment only when XLA
    actually traces (= compiles) — the compile-count assertions in the
    tests read them. The cache argument is donated: the steady-state
    loop never reallocates cache buffers.

    ``cache_sharding`` (a ``NamedSharding``, typically
    ``parallel.tp.kv_cache_pspec`` over a serving mesh) turns the pair
    into pjit over tensor-parallel params: the returned cache is pinned
    to that sharding so donation and compile-once survive sharding.
    """

    def __init__(self, model, *, donate: bool = True, cache_sharding=None):
        self.model = model
        self.cache_sharding = cache_sharding
        self.counts = _TraceCounts()
        counts = self.counts
        pin = _cache_pinner(cache_sharding)

        def prefill(params, cache, slot, tokens, length):
            counts.prefill += 1
            logits, cache = model.prefill(params, cache, slot, tokens, length)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pin(cache)

        def decode(params, cache, tokens, positions):
            counts.decode += 1
            logits, cache = model.decode_step(params, cache, tokens, positions)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pin(cache)

        self._prefill = _jit(prefill, cache_sharding, donate)
        self._decode = _jit(decode, cache_sharding, donate)

    @property
    def prefill_traces(self) -> int:
        return self.counts.prefill

    @property
    def decode_traces(self) -> int:
        return self.counts.decode

    def prefill(self, params, cache, slot: int, tokens, length: int):
        """-> (first generated token, new cache); donates ``cache``."""
        return self._prefill(params, cache, int(slot),
                             np.asarray(tokens, np.int32), int(length))

    def decode(self, params, cache, tokens, positions):
        """-> (next token per slot (S,), new cache); donates ``cache``."""
        return self._decode(params, cache, np.asarray(tokens, np.int32),
                            np.asarray(positions, np.int32))


class PagedDecodeKernels:
    """The jitted ``(prefill, chunk, decode)`` triple over a PAGED
    decode-capable model (one exposing ``init_paged_cache`` /
    ``prefill_paged`` / ``decode_step_paged``, e.g. ``nn.Transformer``).

    Differences from the dense :class:`DecodeKernels`:

    - the cache is the shared page pool; every call additionally takes
      int32 page ids (a ``(ppn,)`` row for prefill chunks, the full
      ``(max_slots, ppn)`` map for decode) — dynamic VALUES with static
      shapes, so the compile-once guarantee is untouched;
    - sampling runs inside the step: per-slot ``temperature`` / ``top_k``
      / ``top_p`` arrays plus one raw threefry key per slot, split once
      per call (``ops.sampling.sample_tokens``). ``temperature=0`` rows
      take the bitwise PR-5 greedy-argmax path;
    - ``chunk`` is prefill WITHOUT logits/sampling — the non-final
      pieces of a chunked prompt. It always runs at exactly
      ``prefill_chunk`` tokens, so it traces once.

    The cache is donated on every call; only token/key vectors cross to
    the host per step. ``use_kernel`` routes decode attention through
    the Pallas paged kernel (auto: TPU only). ``cache_sharding`` shards
    the page pools (heads axis) exactly like :class:`DecodeKernels`.
    """

    def __init__(self, model, *, donate: bool = True,
                 use_kernel: Optional[bool] = None, cache_sharding=None):
        self.model = model
        self.cache_sharding = cache_sharding
        self.counts = _TraceCounts()
        counts = self.counts
        pin = _cache_pinner(cache_sharding)

        def prefill(params, cache, pages, tokens, start, length, trash,
                    temp, top_k, top_p, key, bias):
            counts.prefill += 1
            logits, cache = model.prefill_paged(
                params, cache, pages, tokens, start, length, trash)
            toks, new_key = sample_tokens(logits[None], temp, top_k, top_p,
                                          key, bias)
            return toks[0], new_key, pin(cache)

        def chunk(params, cache, pages, tokens, start, length, trash):
            counts.chunk += 1
            return pin(model.prefill_paged(params, cache, pages, tokens,
                                           start, length, trash,
                                           need_logits=False))

        def decode(params, cache, tokens, positions, page_map,
                   temps, top_ks, top_ps, keys, bias):
            counts.decode += 1
            logits, cache = model.decode_step_paged(
                params, cache, tokens, positions, page_map,
                use_kernel=use_kernel)
            toks, new_keys = sample_tokens(logits, temps, top_ks, top_ps,
                                           keys, bias)
            return toks, new_keys, pin(cache)

        self._prefill = _jit(prefill, cache_sharding, donate)
        self._chunk = _jit(chunk, cache_sharding, donate)
        self._decode = _jit(decode, cache_sharding, donate)

    @property
    def prefill_traces(self) -> int:
        return self.counts.prefill

    @property
    def chunk_traces(self) -> int:
        return self.counts.chunk

    @property
    def decode_traces(self) -> int:
        return self.counts.decode

    def prefill(self, params, cache, pages, tokens, start, length, trash,
                temperature=0.0, top_k=0, top_p=1.0, key=None, bias=None):
        """Final (or only) chunk of one prompt: writes its K/V rows and
        samples the first generated token (under the optional ``(1, V)``
        grammar mask ``bias``). -> ``(token, new_key (1, 2), new
        cache)``; donates ``cache``."""
        if key is None:
            key = np.zeros(2, np.uint32)
        return self._prefill(
            params, cache, np.asarray(pages, np.int32),
            np.asarray(tokens, np.int32), int(start), int(length),
            int(trash), np.asarray([temperature], np.float32),
            np.asarray([top_k], np.int32), np.asarray([top_p], np.float32),
            np.asarray(key, np.uint32).reshape(1, 2),
            None if bias is None else np.asarray(bias, np.float32))

    def chunk(self, params, cache, pages, tokens, start, length, trash):
        """Non-final prompt chunk: K/V writes only. -> new cache
        (donates the old one)."""
        return self._chunk(
            params, cache, np.asarray(pages, np.int32),
            np.asarray(tokens, np.int32), int(start), int(length),
            int(trash))

    def decode(self, params, cache, tokens, positions, page_map,
               temps, top_ks, top_ps, keys, bias=None):
        """One decode step for every slot (``bias``: optional ``(S, V)``
        grammar mask, a traced value — pass it consistently, None or
        array, to keep the one-executable contract). -> ``(next token
        per slot (S,), new keys (S, 2), new cache)``; donates
        ``cache``."""
        return self._decode(
            params, cache, np.asarray(tokens, np.int32),
            np.asarray(positions, np.int32),
            np.asarray(page_map, np.int32),
            np.asarray(temps, np.float32), np.asarray(top_ks, np.int32),
            np.asarray(top_ps, np.float32), np.asarray(keys, np.uint32),
            None if bias is None else np.asarray(bias, np.float32))


class _SpecTraceCounts:
    """Trace counters for the speculative kernel set (same GC discipline
    as :class:`_TraceCounts` — the jitted closures capture THIS, never
    the kernel owner)."""

    __slots__ = ("prefill", "chunk", "draft_write", "draft", "verify")

    def __init__(self):
        self.prefill = 0
        self.chunk = 0
        self.draft_write = 0
        self.draft = 0
        self.verify = 0


class SpeculativeKernels:
    """The jitted kernel set for draft-verified (speculative) generation
    over TWO paged decode-capable models sharing one positional
    contract: a cheap ``draft_model`` proposes candidate tokens with
    ordinary single-row decode steps, and the ``model`` (the target)
    scores all of them in ONE multi-token ``verify`` forward
    (``Transformer.decode_verify_paged``), whose logits feed the
    rejection sampler (``ops.sampling.speculative_sample``).

    Kernels (cache argument donated in every one):

    - ``prefill`` / ``chunk`` — the target's prompt path, as in
      :class:`PagedDecodeKernels`, except the first generated token is
      drawn with the speculative tier's per-(request, output-position)
      keys (position 0) instead of the per-step split chain — so a
      sampled stream is a pure function of its request seed under ANY
      acceptance history;
    - ``draft_write`` — the draft model's prompt path (K/V writes only,
      no logits): the draft needs the prompt in its own cache before it
      can propose;
    - ``draft`` — one draft decode step for every slot: the draft's
      logits are sampled into ``(tokens, dists)`` where ``dists`` is the
      draft's full filtered distribution per slot — the verify step
      needs it for the accept ratio and the residual;
    - ``verify`` — the target's multi-token step over ``[last_token,
      d_1..d_k]`` plus the rejection sampler: returns ``(n_accepted,
      emitted tokens, new cache)``.

    All shapes are fixed (``k`` is baked into the verify width), so each
    kernel compiles exactly once — the compile-once contract of the
    paged engine survives speculation, whatever the acceptance lengths
    do (trace-counter test-enforced). ``cache_sharding`` pins BOTH
    models' page pools (the leaf-shape dispatch in
    ``_cache_sharding_tree`` is dimension-based, so one sharding serves
    both caches)."""

    def __init__(self, model, draft_model, *, donate: bool = True,
                 use_kernel: Optional[bool] = None, cache_sharding=None):
        if not hasattr(draft_model, "decode_step_paged"):
            raise ValueError(
                "speculative decoding needs a PAGED draft model "
                "(decode_step_paged — see nn.Transformer)")
        if getattr(model, "vocab_size", None) != getattr(
                draft_model, "vocab_size", None):
            raise ValueError(
                f"draft and target models must share one vocabulary, got "
                f"{getattr(draft_model, 'vocab_size', None)} vs "
                f"{getattr(model, 'vocab_size', None)}")
        self.model = model
        self.draft_model = draft_model
        self.cache_sharding = cache_sharding
        self.counts = _SpecTraceCounts()
        counts = self.counts
        pin = _cache_pinner(cache_sharding)

        def prefill(params, cache, pages, tokens, start, length, trash,
                    temp, top_k, top_p, key, bias):
            counts.prefill += 1
            logits, cache = model.prefill_paged(
                params, cache, pages, tokens, start, length, trash)
            dist = filtered_probs(logits[None], temp, top_k, top_p, bias)
            u = position_uniform(key, EXTRA_STREAM,
                                 jnp.zeros((1,), jnp.int32))
            return pick_token(dist, u)[0], pin(cache)

        def chunk(params, cache, pages, tokens, start, length, trash):
            counts.chunk += 1
            return pin(model.prefill_paged(params, cache, pages, tokens,
                                           start, length, trash,
                                           need_logits=False))

        def draft_write(dparams, dcache, pages, tokens, start, length,
                        trash):
            counts.draft_write += 1
            return pin(draft_model.prefill_paged(
                dparams, dcache, pages, tokens, start, length, trash,
                need_logits=False))

        def draft(dparams, dcache, tokens, positions, page_map, temps,
                  top_ks, top_ps, keys, out_pos, bias):
            counts.draft += 1
            logits, dcache = draft_model.decode_step_paged(
                dparams, dcache, tokens, positions, page_map,
                use_kernel=use_kernel)
            toks, dists = draft_sample(logits, temps, top_ks, top_ps,
                                       keys, out_pos, bias)
            return toks, dists, pin(dcache)

        def verify(params, cache, last_tokens, draft_tokens, positions,
                   page_map, trash, temps, top_ks, top_ps, keys,
                   out_base, draft_dists, bias):
            counts.verify += 1
            tokens = jnp.stack((last_tokens,) + tuple(draft_tokens),
                               axis=1)
            logits, cache = model.decode_verify_paged(
                params, cache, tokens, positions, page_map, trash)
            if bias is not None:
                # grammar mask per verify position: masked tokens get
                # zero target probability, so speculative_sample itself
                # is untouched (an illegal draft is rejected w.p. 1)
                logits = logits.astype(jnp.float32) + bias
            n_acc, out = speculative_sample(
                logits, jnp.stack(tuple(draft_tokens), axis=1),
                jnp.stack(tuple(draft_dists), axis=1),
                temps, top_ks, top_ps, keys, out_base)
            return n_acc, out, pin(cache)

        self._prefill = _jit(prefill, cache_sharding, donate)
        self._chunk = _jit(chunk, cache_sharding, donate)
        self._draft_write = _jit(draft_write, cache_sharding, donate)
        self._draft = _jit(draft, cache_sharding, donate)
        self._verify = _jit(verify, cache_sharding, donate)

    # trace counters (compile-once assertions read these)
    @property
    def prefill_traces(self) -> int:
        return self.counts.prefill

    @property
    def chunk_traces(self) -> int:
        return self.counts.chunk

    @property
    def draft_write_traces(self) -> int:
        return self.counts.draft_write

    @property
    def draft_traces(self) -> int:
        return self.counts.draft

    @property
    def verify_traces(self) -> int:
        return self.counts.verify

    # decode_traces aliases verify for surfaces (engine properties,
    # step-cost wrappers) that treat "the per-iteration kernel" uniformly
    @property
    def decode_traces(self) -> int:
        return self.counts.verify

    def prefill(self, params, cache, pages, tokens, start, length, trash,
                temperature=0.0, top_k=0, top_p=1.0, key=None, bias=None):
        """Final (or only) chunk of one prompt through the TARGET:
        writes its K/V rows and samples the first generated token (the
        EXTRA_STREAM draw at output position 0, under the optional
        ``(1, V)`` grammar mask). -> ``(token, new cache)``; donates
        ``cache``."""
        if key is None:
            key = np.zeros(2, np.uint32)
        return self._prefill(
            params, cache, np.asarray(pages, np.int32),
            np.asarray(tokens, np.int32), int(start), int(length),
            int(trash), np.asarray([temperature], np.float32),
            np.asarray([top_k], np.int32), np.asarray([top_p], np.float32),
            np.asarray(key, np.uint32).reshape(1, 2),
            None if bias is None else np.asarray(bias, np.float32))

    def chunk(self, params, cache, pages, tokens, start, length, trash):
        """Non-final prompt chunk through the TARGET: K/V writes only.
        -> new cache (donates the old one)."""
        return self._chunk(
            params, cache, np.asarray(pages, np.int32),
            np.asarray(tokens, np.int32), int(start), int(length),
            int(trash))

    def draft_write(self, dparams, dcache, pages, tokens, start, length,
                    trash):
        """Prompt chunk through the DRAFT (final or not — the draft
        never samples during prefill). -> new draft cache (donated)."""
        return self._draft_write(
            dparams, dcache, np.asarray(pages, np.int32),
            np.asarray(tokens, np.int32), int(start), int(length),
            int(trash))

    def draft(self, dparams, dcache, tokens, positions, page_map, temps,
              top_ks, top_ps, keys, out_pos, bias=None):
        """One draft decode step for every slot (``bias``: optional
        ``(S, V)`` grammar mask — the draft proposes only legal
        tokens). -> ``(tokens (S,), dists (S, V), new draft cache)``;
        donates ``dcache``."""
        return self._draft(
            dparams, dcache, np.asarray(tokens, np.int32),
            np.asarray(positions, np.int32),
            np.asarray(page_map, np.int32), np.asarray(temps, np.float32),
            np.asarray(top_ks, np.int32), np.asarray(top_ps, np.float32),
            np.asarray(keys, np.uint32), np.asarray(out_pos, np.int32),
            None if bias is None else np.asarray(bias, np.float32))

    def verify(self, params, cache, last_tokens, draft_tokens, positions,
               page_map, trash, temps, top_ks, top_ps, keys, out_base,
               draft_dists, bias=None):
        """The target's verify forward + rejection sampler.
        ``draft_tokens`` / ``draft_dists`` are the k-tuples of device
        arrays the draft steps returned; ``bias`` is the optional
        ``(S, k+1, V)`` stacked grammar mask added to the target logits
        before the sampler. -> ``(n_accepted (S,), tokens (S, k+1), new
        cache)``; donates ``cache``."""
        return self._verify(
            params, cache, np.asarray(last_tokens, np.int32),
            tuple(draft_tokens), np.asarray(positions, np.int32),
            np.asarray(page_map, np.int32), int(trash),
            np.asarray(temps, np.float32), np.asarray(top_ks, np.int32),
            np.asarray(top_ps, np.float32), np.asarray(keys, np.uint32),
            np.asarray(out_base, np.int32), tuple(draft_dists),
            None if bias is None else np.asarray(bias, np.float32))


class GenerationStream:
    """Iterator-future for one generation request.

    The engine pushes tokens as decode steps complete; the consumer
    either iterates (``for tok in stream`` — single-pass, yields each
    token once then raises the terminal error, if any) or blocks for the
    whole sequence with :meth:`result`. :meth:`cancel` asks the engine
    to retire the slot at the next step boundary (the stream then ends
    with :class:`StreamCancelled`; tokens produced so far stay readable
    via :attr:`tokens`).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._tokens: List[int] = []
        self._q: "_queue.SimpleQueue" = _queue.SimpleQueue()
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._cancelled = False
        self._callbacks: List[Callable[["GenerationStream"], None]] = []
        self.t_submit = time.monotonic()
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        # per-request trace context (obs.RequestTrace); rides the stream
        # so routers/replica sets can annotate it without new signatures
        self.trace = None

    # ------------------------------------------------- engine side ----

    def _push(self, token: int, now: float) -> None:
        with self._lock:
            if self.t_first is None:
                self.t_first = now
            self._tokens.append(token)
        self._q.put(token)

    def _finish(self, error: Optional[BaseException] = None,
                now: Optional[float] = None) -> None:
        with self._lock:
            if self._done.is_set():
                return
            self._error = error
            self.t_done = now if now is not None else time.monotonic()
            callbacks = list(self._callbacks)
            self._done.set()
        self._q.put(_SENTINEL)
        for cb in callbacks:
            try:
                cb(self)
            except Exception:
                log.exception("GenerationStream done-callback failed")

    # ----------------------------------------------- consumer side ----

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _SENTINEL:
                if self._error is not None:
                    # the stored terminal error may be raised again by any
                    # number of result()/__iter__ calls on other threads —
                    # raise a per-call copy so no raise mutates the
                    # __traceback__ a sibling already captured (GL001)
                    raise fresh_exception(self._error)
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream finishes; the full token list (raises
        the stream's terminal error instead, e.g. ``DeadlineExceeded``)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation stream did not finish in time")
        if self._error is not None:
            raise fresh_exception(self._error)  # per-call copy (GL001)
        return list(self._tokens)

    def cancel(self) -> None:
        """Ask the engine to retire this request at the next step
        boundary (no-op once the stream is done)."""
        self._cancelled = True

    def add_done_callback(self, fn: Callable[["GenerationStream"], None]) -> None:
        with self._lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    # ------------------------------------------------------ queries ----

    @property
    def tokens(self) -> List[int]:
        """Tokens produced so far (snapshot copy)."""
        with self._lock:
            return list(self._tokens)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled

    @property
    def error(self) -> Optional[BaseException]:
        return self._error

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit -> first token, seconds (None before the first token)."""
        return None if self.t_first is None else self.t_first - self.t_submit


def _start_host_copy(leaf):
    """Kick an async device->host transfer for one gathered block leaf
    (the offload double-buffer overlaps with decode steps; the drain
    poll reads it back with ``np.asarray`` once landed). Best-effort:
    backends without the API just pay the copy at read time."""
    try:
        leaf.copy_to_host_async()
    except (AttributeError, NotImplementedError, RuntimeError):
        pass
    return leaf


def _block_ready(block) -> bool:
    """True when every leaf of a gathered block has its data available
    (the non-blocking completion poll between scheduler iterations)."""
    for leaf in jax.tree_util.tree_leaves(block):
        ready = getattr(leaf, "is_ready", None)
        if ready is not None and not ready():
            return False
    return True


class _GenRequest:
    __slots__ = ("prompt", "max_new_tokens", "deadline", "stream",
                 "temperature", "top_k", "top_p", "seed", "tag", "handoff",
                 "priority", "grammar")

    def __init__(self, prompt: List[int], max_new_tokens: int,
                 deadline: Optional[float], stream: GenerationStream,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: Optional[int] = None,
                 tag: Any = None, handoff: Optional[dict] = None,
                 priority: int = 0, grammar=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline
        self.stream = stream
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed
        self.tag = tag            # opaque caller context, rides the handoff
        self.handoff = handoff    # adopt payload (decode-role admission)
        self.priority = int(priority)  # QoS tier (PR 18): a page-blocked
        #                                higher-priority head may swap out
        #                                lower-priority active streams
        self.grammar = grammar    # compiled TokenAutomaton (PR 20) or None

    @property
    def sampled(self) -> bool:
        return self.temperature > 0.0


class _SlotState:
    """Host-side bookkeeping for one occupied slot. ``phase`` is
    "decode" for the dense engine always; the paged engine admits into
    "prefill" and flips to "decode" once the final prompt chunk has run
    (chunked prefill interleaves with neighbours' decode steps)."""

    __slots__ = ("req", "last_token", "position", "generated", "t_admit",
                 "phase", "pages", "page_row", "prefill_pos",
                 "draft_pages", "dpage_row", "cache_version", "t_last",
                 "grammar_state", "grammar_error")

    def __init__(self, req: _GenRequest, last_token: int, position: int,
                 generated: int, t_admit: float, phase: str = "decode",
                 pages: Optional[List[int]] = None,
                 page_row=None, prefill_pos: int = 0,
                 draft_pages: Optional[List[int]] = None,
                 dpage_row=None):
        self.req = req
        self.last_token = last_token
        self.position = position          # cache row the NEXT token writes
        self.generated = generated
        self.t_admit = t_admit
        self.phase = phase
        self.pages = pages                # reserved physical pages (paged)
        self.page_row = page_row          # (ppn,) int32 map row (paged)
        self.prefill_pos = prefill_pos    # next prompt index to prefill
        self.draft_pages = draft_pages    # draft-lane pages (speculative)
        self.dpage_row = dpage_row        # draft (ppn,) map row (spec)
        self.cache_version = 0            # prefix-index version at admit
        self.t_last = 0.0                 # last token's push time (ITL)
        self.grammar_state = None         # automaton state (None until armed)
        self.grammar_error = None         # pending GrammarViolation


class _StepTicket:
    """One in-flight async decode step (PR 19): the device futures plus
    the dispatch-time view the land needs. ``parts`` pins the exact
    ``(slot, _SlotState)`` pairs the step computed for — at land time a
    participant whose slot no longer maps to the SAME state (retired
    and re-admitted, swapped out, cancelled) is skipped: its token is
    the discarded rider token of the one-step scheduling lag.
    ``positions`` is the dispatched position snapshot (unclamped rows
    feed ``position + 1`` back into the live dispatch arrays)."""

    __slots__ = ("parts", "positions", "toks", "keys", "overlap_s")

    def __init__(self, parts: List[Tuple[int, "_SlotState"]],
                 positions: "np.ndarray", toks, keys):
        self.parts = parts
        self.positions = positions
        self.toks = toks          # device future: int32[max_slots]
        self.keys = keys          # device future (paged) or None (dense)
        self.overlap_s = 0.0      # host work done while in flight


class _Core:
    """State shared between the engine facade and the loop thread:
    request/stream bookkeeping only, nothing heavy — so the loop can
    fail every stream and exit even if the facade (holding params,
    cache, and the jitted kernels) has been garbage-collected."""

    __slots__ = ("cond", "pending", "active", "free", "closed", "drain")

    def __init__(self, max_slots: int):
        self.cond = threading.Condition()
        self.pending: "deque[_GenRequest]" = deque()
        self.active: Dict[int, _SlotState] = {}
        self.free: List[int] = list(range(max_slots))
        self.closed = False
        self.drain = True


def _fail_streams(core: _Core, error: BaseException,
                  engine: "Optional[GenerationEngine]" = None) -> None:
    """Fail every pending/active stream. Pass the engine (when a strong
    ref is still live) so a PAGED engine's reserved pages return to the
    pool — close(drain=False) and step-failure must not strand the
    ``pages_in_use`` gauge non-zero in a shared ServingMetrics. Callers
    are the loop thread or a post-join close(): never concurrent with a
    running step, so touching the pool here is safe."""
    with core.cond:
        reqs = list(core.pending) + [s.req for s in core.active.values()]
        states = list(core.active.items())
        core.pending.clear()
        core.free.extend(core.active.keys())
        core.active.clear()
    if engine is not None and engine.paged:
        for slot, st in states:
            engine._pool.release(st.pages or ())
            st.pages = None
            engine._page_map[slot] = engine._pool.trash
            if engine.speculative:
                # BOTH lanes of a speculative slot return to the pool —
                # a mid-verify failure must not strand the draft lane
                engine._pool.release(st.draft_pages or ())
                st.draft_pages = None
                engine._dpage_map[slot] = engine._pool.trash
        if engine._prefix is not None:
            # terminal path (step failure, close, GC): the prefix index
            # must drop its page references too, or a shared
            # ServingMetrics reports phantom shared_pages/pages_in_use
            # forever (chaos drain gate: shared_pages == 0)
            engine._prefix.clear()
            if engine._dprefix is not None:
                engine._dprefix.clear()
        if engine._host is not None:
            # the host tier drains with the device tier: in-flight
            # offload copies drop (their device pages already evicted
            # cleanly) and every resident entry/booking releases, so
            # both tiers' gauges reach zero together (chaos drain gate)
            engine._pending_offloads.clear()
            engine._host.clear()
        if states or engine._prefix is not None or engine._host is not None:
            engine._report_pages()
    for r in reqs:
        if not r.stream.done:
            r.stream._finish(error)
        tr = r.stream.trace
        if tr is not None and not tr.done:
            tr.finish(outcome="failed", error=type(error).__name__)


def _engine_loop(engine_ref: "weakref.ref[GenerationEngine]",
                 core: _Core) -> None:
    """Loop thread body. Holds only a weak ref to the engine while idle
    (same discipline as the batcher worker): an engine whose owner
    forgot ``close()`` becomes collectable and the loop exits, failing
    any stranded streams, instead of pinning params + KV cache forever."""
    try:
        _engine_loop_body(engine_ref, core)
    finally:
        # the LOOP owns watchdog retirement: close() skips it while the
        # loop is still alive (a wedged step outliving the join
        # timeout), so when the stuck step finally returns and the loop
        # exits, the watchdog thread — and its strong engine ref — must
        # be released here or they leak for the process lifetime
        engine = engine_ref()
        if engine is not None and engine._watchdog is not None:
            engine._watchdog.close(timeout=0)


def _notify_core(core: _Core) -> None:
    """``weakref.finalize`` callback registered on every engine: when
    the owner drops the last strong reference without ``close()``, the
    idle loop thread is parked in a PURE ``cond.wait()`` (no timeout —
    the last GL003 busy-wait left the hot loop in PR 19), so GC itself
    must deliver the wakeup that lets the loop observe the dead weakref
    and exit. Takes the core, never the engine: a strong engine ref in
    the finalizer's args would keep the engine alive forever."""
    try:
        with core.cond:
            core.cond.notify_all()
    except Exception:  # graftlint: disable=GL006
        # interpreter teardown can run finalizers after the lock
        # machinery is gone (nothing to log TO either); the daemon loop
        # thread dies with the process anyway, so swallowing is safe
        pass


def _engine_loop_body(engine_ref: "weakref.ref[GenerationEngine]",
                      core: _Core) -> None:
    while True:
        with core.cond:
            while not core.pending and not core.active and not core.closed:
                # check the weakref BEFORE waiting, under the lock: the
                # finalize hook notifies under this same lock, so a GC
                # that lands between iterations (the collector holds the
                # GIL, so the loop can be parked anywhere) is either seen
                # here or its notify arrives after wait() releases the
                # lock — the wakeup cannot be lost
                if engine_ref() is None:
                    break
                # pure wait: close() notifies, submit() notifies, and
                # engine GC notifies via the weakref.finalize hook —
                # every wake source is explicit, so no polling timeout
                core.cond.wait()
                if engine_ref() is None:
                    break
            if core.closed:
                if not core.drain:
                    _fail_streams(core, RuntimeError(
                        "generation engine closed before request ran"),
                        engine_ref())
                    return
                if not core.pending and not core.active:
                    return
        engine = engine_ref()
        if engine is None:
            _fail_streams(core, RuntimeError(
                "generation engine was garbage-collected with requests "
                "in flight"))
            return
        if engine._failed is not None:
            # the watchdog fired while a step was stuck; the streams are
            # already failed — now that the loop has control again, do
            # the slot/page reconciliation HERE (the only thread allowed
            # to touch them) and stop
            _fail_streams(core, engine._failed, engine)
            return
        wd = engine._watchdog
        if wd is not None:
            wd.arm("decode step")
        try:
            engine._step()
        except Exception as e:
            # a broken step cannot be retried: the donated cache may be
            # consumed — fail every stream loudly and stop the loop
            engine._failed = e
            log.exception("generation engine step failed; engine stopped")
            _fail_streams(core, e, engine)
            return
        finally:
            if wd is not None:
                wd.disarm()
        del engine


class GenerationEngine:
    """Continuous-batching generation front door over one decode-capable
    model (``init_cache`` / ``prefill`` / ``decode_step`` — see
    ``nn.Transformer``).

    ``submit(prompt, max_new_tokens=..., deadline=...)`` returns a
    :class:`GenerationStream`; a persistent loop thread admits pending
    prompts into free slots between decode steps, decodes every active
    slot per iteration, and retires finished sequences mid-flight.
    Admission control mirrors :class:`InferenceService`: a full pending
    queue raises :class:`Overloaded` on the caller's thread.

    ``warmup()`` compiles the decode step (once — its shapes never
    change) and every prompt bucket; call it before traffic so no
    request pays a compile. ``reload(params)`` swaps weights atomically
    between steps (see the hot-reload satellite).
    """

    def __init__(self, model, params, *, max_slots: int = 8,
                 max_len: int = 256, max_prompt_len: Optional[int] = None,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 max_queue: int = 64,
                 metrics: Optional[ServingMetrics] = None,
                 cache_dtype=jnp.float32,
                 kernels=None,
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 seed: int = 0,
                 use_paged_kernel: Optional[bool] = None,
                 mesh=None,
                 param_pspecs=None,
                 shard_axis: str = "tp",
                 stall_timeout: Optional[float] = None,
                 quantize: Optional[str] = None,
                 speculate: Optional[tuple] = None,
                 prefix_cache: bool = False,
                 cache_aware_admission: bool = False,
                 host_pages: Optional[int] = None,
                 role: str = "both",
                 tracer=None,
                 timeline_capacity: int = 512,
                 profile_dir: Optional[str] = None,
                 profile_iters: int = 10,
                 async_scheduling: bool = False):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if max_len < 2:
            raise ValueError("max_len must be >= 2 (prompt + 1 token)")
        self.model = model
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.pad_id = int(pad_id)
        self.max_queue = int(max_queue)
        self.metrics = metrics or ServingMetrics()
        self.seed = int(seed)
        # observability plane (PR 11): `tracer` (an obs.Tracer) turns on
        # per-request span traces — None (the default) costs one `is
        # None` test on the submit path and one attribute load per
        # decode step (the disarmed-fault-site budget, test-pinned).
        # `timeline` is the always-on bounded per-iteration breakdown
        # (host vs device, prefill/decode/verify split, queue depth and
        # occupancy); its aggregate feeds the metrics' engine_steps
        # block. `profile_dir` arms an opt-in jax.profiler trace
        # bracketing the first `profile_iters` scheduler iterations.
        # `async_scheduling` (PR 19) overlaps the host share of every
        # iteration with the in-flight decode step — same stream
        # bytes, one step of scheduling lag; see _step_async.
        self.tracer = tracer
        self.timeline = StepTimeline(timeline_capacity)
        self._profile_dir = profile_dir
        self._profile_iters = int(profile_iters)
        self._profile_state = 0   # 0 = armed/idle, 1 = tracing, 2 = done
        self._profile_count = 0
        # the int8 serving tier (PR 9): `quantize="int8"` rewrites the
        # GEMM weights to per-channel int8 ONCE here (and again inside
        # every reload, so checkpoint watchers keep feeding float
        # params); `cache_dtype="int8"` stores KV pages int8 with
        # per-token fp32 scale pools riding alongside. Both knobs keep
        # every standing contract: the quantized tree's shapes/dtypes
        # are a pure function of the float tree (reload never
        # recompiles), and the int8 cache donates/pins/shards exactly
        # like the float one.
        if quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', "
                             f"got {quantize!r}")
        self.quantize = quantize
        # speculative decoding (PR 10): `speculate=(draft_model,
        # draft_params, k)` pairs the target with a cheap draft of the
        # same model family (same vocabulary). Each scheduler iteration
        # then runs k+1 draft decode steps (the +1 pre-writes the
        # would-be bonus row in the draft cache, so a full acceptance
        # leaves no K/V hole) and ONE target verify forward that scores
        # all k candidates at once — the memory-bandwidth-bound target
        # decode is amortized over up to k+1 emitted tokens per round.
        # Greedy speculative output is token-identical to plain greedy
        # decode (test-enforced); the draft and target reserve
        # side-by-side lanes in the ONE PagePool, tagged per owner.
        self.speculative = False
        self.spec_k = 0
        self.draft_model = None
        draft_params = None
        # prefix caching (PR 12): content-addressed sharing of full,
        # immutable prompt pages across requests over the one PagePool.
        # Off by default — the cache holds page references past request
        # lifetimes, so pool-drain invariants change shape with it on
        # (output does NOT: cache on vs off is bit-identical,
        # test-enforced). Built per lane below; a speculative engine
        # keeps separate target/draft indexes because the two models'
        # pages hold different K/V for the same tokens and must never
        # be shared across owners.
        self.prefix_caching = bool(prefix_cache)
        self._prefix: Optional[PrefixCache] = None
        self._dprefix: Optional[PrefixCache] = None
        self._prefix_flush = False
        # True after an eviction scan freed nothing; cleared whenever
        # pages release or publish (evictability can only change then),
        # so a page-blocked FIFO head does not re-walk the whole index
        # every scheduler iteration
        self._evict_stale = False
        # cache-aware admission (PR 14): when the FIFO head is
        # page-blocked, admit a LATER pending request that fits —
        # preferring resident prefixes (they allocate fewer fresh
        # pages) — instead of idling free pages behind the head. The
        # head's wait stays bounded: at most `_bypass_limit` bypasses
        # per blocked head, then strict FIFO resumes (fairness is
        # test-enforced). Off by default: it is a scheduling-order
        # change, never an output change.
        self.cache_aware_admission = bool(cache_aware_admission)
        self._bypass_limit = 4
        self._head_bypasses = 0   # consecutive bypasses of the current head
        self.admission_bypasses = 0  # total (snapshot counter)
        # prefill/decode disaggregation (PR 15): role="prefill" runs ONLY
        # the prefill/chunk kernels — the final chunk, instead of
        # flipping the slot to decode, gathers the finished KV pages into
        # a device block and invokes `_handoff_cb` (set by the
        # DisaggregatedEngine front door) with the handoff payload; the
        # slot's pages are then export_pages()d and the slot freed.
        # role="decode" runs ONLY the decode kernel and admits via
        # `submit_prefilled` — pages already materialized, scattered into
        # its own pool at adoption. role="both" (default) is the
        # monolithic engine, bit-identically untouched.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got {role!r}")
        self.role = role
        self._handoff_cb: Optional[Callable[[dict], None]] = None
        # content-identity namespace for exported pages: unique per
        # engine INSTANCE across processes (adopt-side dedup keys on it,
        # and two prefill workers' page ids must never alias)
        self.handoff_source = f"prefill-{uuid.uuid4().hex[:12]}"
        self._mover = None
        if speculate is not None:
            try:
                self.draft_model, draft_params, self.spec_k = speculate
            except (TypeError, ValueError):
                raise ValueError(
                    "speculate must be a (draft_model, draft_params, k) "
                    "triple")
            self.spec_k = int(self.spec_k)
            if self.spec_k < 1:
                raise ValueError("speculate k must be >= 1")
            self.speculative = True
        if quantize == "int8":
            from bigdl_tpu.nn.quantized import (
                count_quantized_gemms,
                quantize_for_serving,
            )

            self._quantize_params = quantize_for_serving
            params = quantize_for_serving(params)
            if draft_params is not None:
                # the draft serves too: its GEMMs ride the same int8 tier
                draft_params = quantize_for_serving(draft_params)
            self.metrics.set_quantized_gemms(count_quantized_gemms(params))
        else:
            self._quantize_params = None
        self.cache_dtype_name = np.dtype(cache_dtype).name
        # sharded (tensor-parallel) mode: params placed per the Megatron
        # pspecs (parallel.tp), the KV cache — dense lanes or paged pools
        # — sharded on the HEADS axis; the jitted kernels become pjit and
        # GSPMD derives the collectives. Greedy decode stays bit-identical
        # to the single-device engine and compile-once survives because
        # every call sees the same input shardings (test-enforced).
        self.mesh = mesh
        self._param_shardings = None
        self._cache_sharding = None
        self._draft_param_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from bigdl_tpu.parallel.mesh import tree_shardings
            from bigdl_tpu.parallel.tp import (
                kv_cache_pspec,
                kv_scale_pspec,
                transformer_tp_pspecs,
            )

            if param_pspecs is None:
                param_pspecs = transformer_tp_pspecs(model, mesh,
                                                     axis=shard_axis,
                                                     params=params)
            self._param_shardings = tree_shardings(mesh, params, param_pspecs)
            params = jax.device_put(params, self._param_shardings)
            if draft_params is not None:
                # the draft shards on the same mesh with its OWN Megatron
                # pspecs (tp must divide its head count too); its page
                # pools reuse the target's heads-axis cache sharding
                dspecs = transformer_tp_pspecs(self.draft_model, mesh,
                                               axis=shard_axis,
                                               params=draft_params)
                self._draft_param_shardings = tree_shardings(
                    mesh, draft_params, dspecs)
                draft_params = jax.device_put(draft_params,
                                              self._draft_param_shardings)
            self._cache_sharding = NamedSharding(mesh,
                                                 kv_cache_pspec(shard_axis))
            if self.cache_dtype_name == "int8":
                # int8 pools carry 2-D per-token scale pools next to the
                # 4-D pages: pages shard on heads, scales replicate
                self._cache_sharding = (
                    self._cache_sharding,
                    NamedSharding(mesh, kv_scale_pspec()))
            if kernels is not None and getattr(
                    kernels, "cache_sharding",
                    None) != self._cache_sharding:
                # not just non-None: kernels pinned to a DIFFERENT mesh or
                # spec would return caches whose layout disagrees with the
                # engine's placement every step — donation mismatch and a
                # silent compile-once violation
                raise ValueError(
                    "a sharded engine needs kernels built with the engine's "
                    "exact cache_sharding (NamedSharding of this mesh + "
                    f"{kv_cache_pspec(shard_axis)}; int8 caches pair it "
                    "with a replicated scale-pool sharding); pass "
                    "kernels=None to build matching ones")
        # mode: the kernels pick it when given; otherwise paged whenever
        # the model speaks the paged API (the dense lanes are the PR-5
        # baseline, kept for bit-identity tests and plain-cache models).
        # `chunk` is the paged-triple discriminator so wrappers (fixed
        # step-cost shims, failure injectors) duck-type either flavour.
        if kernels is not None:
            if hasattr(kernels, "verify") != self.speculative:
                # a speculative engine needs the draft model/params from
                # `speculate=` AND kernels that carry the verify step;
                # half of either is a silent wrong-mode engine
                raise ValueError(
                    "speculate=(draft_model, draft_params, k) and "
                    "SpeculativeKernels go together: pass both or "
                    "neither")
            self.paged = hasattr(kernels, "chunk")
        else:
            self.paged = bool(page_size) and hasattr(model,
                                                    "decode_step_paged")
        if self.speculative and not (
                bool(page_size) and hasattr(model, "decode_step_paged")):
            raise ValueError(
                "speculative decoding needs the paged engine (the draft "
                "and target caches live side by side in one PagePool)")
        if self.cache_dtype_name == "int8" and not self.paged:
            raise ValueError(
                "cache_dtype='int8' needs the paged engine (int8 KV lives "
                "in the page pools with per-token scale pools; the dense "
                "slot-lane path is the float PR-5 baseline, kept bitwise "
                "untouched)")
        if self.role != "both":
            if not self.paged:
                raise ValueError(
                    "role='prefill'/'decode' needs the paged engine — the "
                    "handoff moves physical KV pages between pools")
            if self.speculative:
                raise ValueError(
                    "role='prefill'/'decode' excludes speculative decoding "
                    "(draft-lane pages do not cross the handoff yet)")
            if self.role == "decode" and self.prefix_caching:
                raise ValueError(
                    "the prefix index lives with the prefill role (pages "
                    "are published where prompts are written); pass "
                    "prefix_cache=True to the prefill engine instead")
        # two-tier KV (PR 18): host_pages=N backs the device pool with a
        # HostPageStore — prefix chains the device index would evict LRU
        # offload to host RAM instead (async device->host, double-
        # buffered, polled between iterations) and restore on a later
        # hit bit-identically; a page-blocked higher-priority head may
        # swap OUT a lower-priority active stream through the same tier.
        self._host: Optional[HostPageStore] = None
        self._pending_offloads: List[dict] = []
        self._offload_inflight_cap = 2   # double-buffer: never more
        #                                  in-flight copies than overlap
        self._swap_seq = 0               # swap booking ids (engine-local)
        if host_pages is not None:
            if not self.paged:
                raise ValueError(
                    "host_pages needs the paged engine (the host tier "
                    "stores physical KV pages; the dense slot-lane path "
                    "has none)")
            if self.speculative:
                raise ValueError(
                    "host_pages excludes speculative decoding (draft-"
                    "lane pages do not offload yet)")
            if self.role == "decode":
                raise ValueError(
                    "the host tier lives with the prefix index "
                    "(prefill/both roles — pages offload where prompts "
                    "are written); pass host_pages to the prefill "
                    "engine instead")
            if not self.prefix_caching:
                raise ValueError(
                    "host_pages needs prefix_cache=True — the host tier "
                    "is indexed by the same (version, prefix) radix keys "
                    "the device prefix index files pages under")
        if self.paged:
            # chunked prefill lifts the prompt-length wall: anything that
            # leaves room for one generated token is admitted and chunked
            self.max_prompt_len = int(max_prompt_len or (max_len - 1))
        else:
            self.max_prompt_len = int(max_prompt_len or max(1, max_len // 2))
        if not 1 <= self.max_prompt_len < self.max_len:
            raise ValueError(
                f"max_prompt_len {self.max_prompt_len} must be in "
                f"[1, max_len) = [1, {self.max_len})")
        if self.paged:
            self.page_size = int(page_size)
            self.prefill_chunk = int(
                prefill_chunk or min(64, self.max_prompt_len))
            if self.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            self.prompt_buckets = bucket_sizes_for(
                min(self.max_prompt_len, self.prefill_chunk))
            # dense-equivalent pool by default; shrink num_pages to trade
            # worst-case capacity for more concurrent typical requests.
            # A speculative engine reserves TWO lanes per slot (target +
            # draft) out of the one pool, so its default doubles — the
            # device pools of both models span the shared id space.
            ppn = pages_per_lane(self.max_len, self.page_size)
            self._lanes = lanes = 2 if self.speculative else 1
            self.num_pages = int(num_pages or self.max_slots * ppn * lanes)
            self._pool = PagePool(self.num_pages, self.page_size,
                                  self.max_len)
            if kernels is not None:
                self.kernels = kernels
            elif self.speculative:
                self.kernels = SpeculativeKernels(
                    model, self.draft_model, use_kernel=use_paged_kernel,
                    cache_sharding=self._cache_sharding)
            else:
                self.kernels = PagedDecodeKernels(
                    model, use_kernel=use_paged_kernel,
                    cache_sharding=self._cache_sharding)
            self._cache = model.init_paged_cache(
                self.num_pages + 1, self.page_size, cache_dtype)
            # per-slot step inputs, mutated on admission/retirement only
            self._page_map = np.full((self.max_slots, ppn),
                                     self._pool.trash, np.int32)
            self._temps = np.zeros((self.max_slots,), np.float32)
            self._top_ks = np.zeros((self.max_slots,), np.int32)
            self._top_ps = np.ones((self.max_slots,), np.float32)
            self._keys = np.zeros((self.max_slots, 2), np.uint32)
            # grammar-constrained decoding (PR 20): per-slot additive
            # mask rows, a traced (S, V) input of every sampling kernel.
            # Always the SAME kind of argument per engine (array, or
            # consistently None when the model exposes no vocab_size):
            # jit treats None as an empty pytree, so flip-flopping would
            # double the executable set. Unconstrained slots keep
            # all-zero rows — a constant shift, bitwise no-op.
            vocab = getattr(model, "vocab_size", None)
            self._bias = (np.zeros((self.max_slots, int(vocab)), np.float32)
                          if vocab else None)
            # distinct grammar keys seen by THIS engine: a submit whose
            # automaton key is already here shares the compiled tables
            # (the module compile cache made that sharing free) — the
            # grammar_compile_cache_hits metric counts those reuses
            self._grammars: set = set()
            if self.speculative:
                # the draft cache spans the same page-id space; its map
                # rows park on the shared trash page exactly like the
                # target's. In speculative mode `_keys` holds each
                # slot's REQUEST key (constant — draws are keyed by
                # output position, never by step).
                self._dcache = self.draft_model.init_paged_cache(
                    self.num_pages + 1, self.page_size, cache_dtype)
                self._dpage_map = np.full((self.max_slots, ppn),
                                          self._pool.trash, np.int32)
            # dtype-aware byte accounting for the kv_bytes_in_use gauge:
            # bytes one reserved page costs across ALL layers, scale
            # pools included (paging.page_bytes); 0 for models that do
            # not expose transformer dims (the gauge then stays silent)
            heads = getattr(model, "num_heads", 0)
            hidden = getattr(model, "hidden_size", 0)
            layers = getattr(model, "num_hidden_layers", 0)
            self._kv_page_bytes = (
                layers * page_bytes(self.page_size, heads, hidden // heads,
                                    self.cache_dtype_name)
                if heads and hidden and layers else 0)
            self._kv_dpage_bytes = 0
            if self.speculative:
                dheads = getattr(self.draft_model, "num_heads", 0)
                dhidden = getattr(self.draft_model, "hidden_size", 0)
                dlayers = getattr(self.draft_model, "num_hidden_layers", 0)
                self._kv_dpage_bytes = (
                    dlayers * page_bytes(self.page_size, dheads,
                                         dhidden // dheads,
                                         self.cache_dtype_name)
                    if dheads and dhidden and dlayers else 0)
            if self.prefix_caching:
                self._prefix = PrefixCache(self._pool, name="target")
                if self.speculative:
                    self._dprefix = PrefixCache(self._pool, name="draft")
            if host_pages is not None:
                self._host = HostPageStore(
                    int(host_pages), page_bytes=self._kv_page_bytes)
            if self.role != "both" or self._host is not None:
                # gather (prefill export / host offload) / scatter
                # (decode adopt / host restore) jits: one executable
                # each, counted like the kernel triples (compile-once is
                # test-pinned). Lazy import: disagg.py imports this
                # module at its top.
                from bigdl_tpu.serving.disagg import PageBlockMover

                self._mover = PageBlockMover(
                    cache_sharding=self._cache_sharding)
            self._report_pages()
        else:
            if self.prefix_caching:
                raise ValueError(
                    "prefix_cache=True needs the paged engine (shared "
                    "prefixes live in refcounted KV pages; the dense "
                    "slot-lane path has no pages to share)")
            self.prompt_buckets = bucket_sizes_for(self.max_prompt_len)
            self.kernels = kernels or DecodeKernels(
                model, cache_sharding=self._cache_sharding)
            self._cache = model.init_cache(self.max_slots, self.max_len,
                                           cache_dtype)
        if self._cache_sharding is not None:
            # heads-axis placement from step zero: the kernels' in-step
            # constraint then keeps every successive donated cache here
            self._cache = jax.device_put(
                self._cache,
                _cache_sharding_tree(self._cache, self._cache_sharding))
            if self.speculative:
                self._dcache = jax.device_put(
                    self._dcache,
                    _cache_sharding_tree(self._dcache,
                                         self._cache_sharding))
        self._params = params
        self._draft_params = draft_params
        self._failed: Optional[BaseException] = None
        self._core = _Core(self.max_slots)
        # stall watchdog: a decode/prefill call that makes no progress
        # past `stall_timeout` seconds (wedged device, hung collective)
        # fails every pending/active STREAM with a StallError diagnostic
        # instead of hanging their consumers forever; the loop thread
        # reconciles slots/pages when (if) the stuck step returns. NOTE:
        # a watchdog-armed engine must be close()d — the watchdog holds
        # a strong ref, so the forgot-to-close GC path applies only to
        # unwatched engines.
        self._watchdog = None
        if stall_timeout is not None:
            self._watchdog = Watchdog(
                f"engine@{id(self):x}", stall_timeout, self._on_stall)
        # async scheduling (PR 19): the loop lands step N's tokens,
        # immediately dispatches step N+1 from snapshot inputs, and does
        # ALL host work (delivery, retirement, admission, prefill
        # chunks, KV-tier polls) while N+1 runs on device. Scheduling
        # decisions lag one step — see _step_async. The speculative
        # round's accept count is a host decision gating the round's
        # FIRST draft input, so there is no overlap window to exploit
        # without changing the speculative contract: a speculative
        # engine keeps the sync path whatever the knob says.
        self.async_scheduling = bool(async_scheduling)
        self._async = self.async_scheduling and not self.speculative
        self._inflight: Optional[_StepTicket] = None
        # live per-slot dispatch inputs, the host half of the double
        # buffer: arming (admission / final prefill chunk) and landing
        # write here; every dispatch hands the kernels private COPIES,
        # so mutations for step N+2 can never race the in-flight N+1
        # (jax may alias a numpy argument's buffer on the CPU backend)
        self._step_tokens = np.zeros((self.max_slots,), np.int32)
        self._step_positions = np.zeros((self.max_slots,), np.int32)
        # slots armed since the last dispatch: the next land must NOT
        # fold the old ticket's rows over their fresh arming (a retired
        # slot re-admitted while its last step was still in flight)
        self._armed_dirty: set = set()
        # GC-liveness wakeup for the pure cond.wait() idle loop: when
        # the last strong engine ref drops, this finalizer (which holds
        # only the core) nudges the loop awake to observe the dead
        # weakref and exit
        weakref.finalize(self, _notify_core, self._core)
        self._thread = threading.Thread(
            target=_engine_loop, args=(weakref.ref(self), self._core),
            name="bigdl-serving-engine", daemon=True)
        self._thread.start()

    # ------------------------------------------------------ submission ----

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               deadline: Optional[float] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0,
               seed: Optional[int] = None,
               tag: Any = None,
               priority: int = 0,
               grammar=None) -> GenerationStream:
        """Enqueue one prompt (sequence of token ids). ``max_new_tokens``
        caps generation (default: whatever fits in ``max_len``);
        ``deadline`` is seconds from now — an expired request retires
        mid-flight with :class:`DeadlineExceeded` on its stream. Raises
        :class:`Overloaded` when the pending queue is at its bound.

        Sampling (paged engine only): ``temperature > 0`` samples inside
        the jitted step, optionally filtered by ``top_k`` / nucleus
        ``top_p``; ``temperature=0`` (default) is greedy argmax. The
        stream's PRNG seed defaults to a pure function of the engine
        seed and the prompt bytes, so sampled output — like greedy — is
        identical across runs and admission orderings; pass ``seed`` to
        give byte-identical prompts distinct streams.

        ``tag`` is an opaque caller context that rides the request into
        a prefill-role engine's handoff payload (the DisaggregatedEngine
        threads its per-request routing state through it).

        ``priority`` (QoS, PR 18; meaningful on a host-tier engine —
        inert otherwise): when this request heads the FIFO queue
        page-blocked and nothing else frees room, active streams of
        STRICTLY lower priority may swap out through the host tier to
        admit it; they resume byte-exactly once pages free. Equal
        priorities never displace each other — default-0 traffic is
        plain FIFO.

        ``grammar`` (PR 20, paged engine only): a compiled
        :class:`~bigdl_tpu.grammar.TokenAutomaton` over this model's
        vocabulary. Every step of the stream then samples under the
        automaton's current-state mask (greedy = argmax over the LEGAL
        set), the state advances host-side per emitted token, and the
        stream is guaranteed to parse — a stream that cannot reach a
        parse (budget exhausted mid-grammar, or a stuck state) fails
        with :class:`GrammarViolation` instead of emitting garbage."""
        if self.role == "decode":
            raise RuntimeError(
                "a decode-role engine admits only prefilled requests "
                "(pages already materialized) — use submit_prefilled()")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds max_prompt_len "
                f"{self.max_prompt_len}")
        temperature = float(temperature)
        if temperature > 0.0 and not self.paged:
            raise ValueError(
                "sampling (temperature > 0) needs the paged engine — the "
                "dense DecodeKernels path is the greedy PR-5 baseline")
        if temperature < 0.0:
            raise ValueError("temperature must be >= 0")
        if grammar is not None:
            if not self.paged:
                raise ValueError(
                    "grammar-constrained decoding needs the paged engine "
                    "(the mask rides the in-step sampler)")
            if self._bias is None:
                raise ValueError(
                    "grammar-constrained decoding needs a model exposing "
                    "vocab_size (the per-slot mask is (S, vocab))")
            if self.role != "both":
                raise ValueError(
                    "grammar-constrained decoding does not cross the "
                    "prefill/decode handoff yet — submit to a monolithic "
                    "(role='both') engine")
            if not hasattr(grammar, "bias_row"):
                raise TypeError(
                    "grammar must be a compiled TokenAutomaton — build "
                    "one with grammar.compile_grammar(regex_grammar(...) "
                    "or json_schema_grammar(...), vocab, eos_id)")
            if grammar.vocab_size != self._bias.shape[1]:
                raise ValueError(
                    f"grammar compiled over a {grammar.vocab_size}-token "
                    f"vocabulary, model has {self._bias.shape[1]}")
            if grammar.eos_id != self.eos_id:
                raise ValueError(
                    f"grammar compiled with eos_id={grammar.eos_id}, "
                    f"engine has eos_id={self.eos_id} — the EOS mask "
                    f"column is how a constrained stream terminates")
        room = self.max_len - len(prompt)
        mnt = room if max_new_tokens is None else min(int(max_new_tokens), room)
        if mnt < 1:
            raise ValueError("no room to generate even one token")
        if self.paged:
            # a prefill-role engine reserves prompt pages only — the
            # generation budget is the DECODE pool's problem
            need = self._lanes * (
                self._pool.pages_for(len(prompt))
                if self.role == "prefill"
                else self._pool.pages_for(
                    min(len(prompt) + mnt - 1, self.max_len)))
            if need > self.num_pages:
                # a reservation the pool can NEVER satisfy would block the
                # FIFO head forever (page pressure is allowed to delay, not
                # to deadlock) — reject it on the caller's thread instead
                raise ValueError(
                    f"request needs {need} KV pages but the pool holds "
                    f"{self.num_pages}; shrink the prompt/max_new_tokens "
                    f"or grow num_pages")
        stream = GenerationStream()
        now = stream.t_submit
        # trace context attaches BEFORE the request can reach the loop
        # thread (admission reads stream.trace); tracer=None is free
        tr = submit_trace(self.tracer, "generate", prompt_len=len(prompt),
                          max_new_tokens=mnt, sampled=temperature > 0.0)
        stream.trace = tr
        req = _GenRequest(prompt, mnt,
                          None if deadline is None else now + float(deadline),
                          stream, temperature=temperature, top_k=int(top_k),
                          top_p=float(top_p),
                          seed=None if seed is None else int(seed),
                          tag=tag, priority=int(priority), grammar=grammar)
        core = self._core
        try:
            with core.cond:
                if self._failed is not None:
                    raise RuntimeError(
                        "generation engine stopped after a step failure"
                    ) from self._failed
                if core.closed:
                    raise RuntimeError("generation engine is closed")
                if len(core.pending) >= self.max_queue:
                    self.metrics.record_rejected()
                    raise Overloaded(len(core.pending), self.max_queue)
                if tr is not None:
                    # BEFORE the enqueue: once the loop thread can see
                    # the request it may admit, run, and finish() the
                    # trace — a post-notify event would mutate a trace
                    # already retired into the finished ring
                    tr.event("submit", queue_depth=len(core.pending) + 1)
                if grammar is not None:
                    # shared-grammar accounting: a key this engine has
                    # already served means the compiled automaton (and
                    # its mask tables) were reused via the module
                    # compile cache rather than rebuilt
                    if grammar.key in self._grammars:
                        self.metrics.record_grammar_cache_hit()
                    else:
                        self._grammars.add(grammar.key)
                core.pending.append(req)
                depth = len(core.pending)
                core.cond.notify_all()
        except BaseException:
            if tr is not None:
                tr.finish(outcome="rejected")
            raise
        self.metrics.set_queue_depth(depth)
        return stream

    def generate(self, prompt: Sequence[int], *,
                 max_new_tokens: Optional[int] = None,
                 deadline: Optional[float] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: Optional[int] = None,
                 timeout: Optional[float] = None) -> List[int]:
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           deadline=deadline, temperature=temperature,
                           top_k=top_k, top_p=top_p,
                           seed=seed).result(timeout)

    def submit_prefilled(self, payload: dict, *,
                         stream: Optional[GenerationStream] = None
                         ) -> GenerationStream:
        """Enqueue a request whose prompt a PREFILL-role engine already
        ran (decode-role engines only): ``payload`` is the handoff dict
        that engine's ``_handoff_cb`` produced — prompt, first token,
        post-prefill PRNG key, sampling params, the gathered KV block
        and the page manifest. Admission adopts the prompt pages into
        this engine's pool (shared prefixes dedup to one local copy),
        scatters the block, pushes the first token, and decodes on —
        the stream continues bit-identically to a monolithic engine's.

        ``payload["deadline"]`` is ABSOLUTE ``time.monotonic()`` time:
        meaningful same-process only, so a cross-process front door
        re-stamps it from its own clock before dispatching here. Pass
        ``stream`` to continue an existing consumer-facing stream (the
        front door's); omitted, a fresh one is returned."""
        if self.role != "decode":
            raise RuntimeError(
                "submit_prefilled() needs a role='decode' engine — "
                "monolithic engines prefill their own prompts")
        prompt = [int(t) for t in np.asarray(payload["prompt"]).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt in handoff payload")
        mnt = int(payload["max_new_tokens"])
        if mnt < 1:
            raise ValueError("handoff payload has no generation budget")
        need = self._pool.pages_for(min(len(prompt) + mnt - 1, self.max_len))
        if need > self.num_pages:
            raise ValueError(
                f"request needs {need} KV pages but the decode pool holds "
                f"{self.num_pages}; shrink the prompt/max_new_tokens or "
                f"grow num_pages")
        stream = stream or GenerationStream()
        deadline = payload.get("deadline")
        req = _GenRequest(prompt, mnt,
                          None if deadline is None else float(deadline),
                          stream,
                          temperature=float(payload.get("temperature", 0.0)),
                          top_k=int(payload.get("top_k", 0)),
                          top_p=float(payload.get("top_p", 1.0)),
                          handoff=payload,
                          priority=int(payload.get("priority", 0)))
        core = self._core
        with core.cond:
            if self._failed is not None:
                raise RuntimeError(
                    "generation engine stopped after a step failure"
                ) from self._failed
            if core.closed:
                raise RuntimeError("generation engine is closed")
            if len(core.pending) >= self.max_queue:
                self.metrics.record_rejected()
                raise Overloaded(len(core.pending), self.max_queue)
            core.pending.append(req)
            depth = len(core.pending)
            core.cond.notify_all()
        self.metrics.set_queue_depth(depth)
        return stream

    def _on_stall(self, err: StallError) -> None:
        """Watchdog callback (runs on the WATCHDOG thread): the loop is
        stuck inside a step past the deadline. Mark the engine failed so
        new submits are refused, and finish every pending/active stream
        with the diagnostic so their consumers unblock. Slot and page
        bookkeeping is deliberately NOT touched here — only the loop
        thread may mutate it, and it reconciles via ``_fail_streams``
        the moment the stuck step returns (see ``_engine_loop``)."""
        core = self._core
        with core.cond:
            if self._failed is not None:
                return
            self._failed = err
            reqs = list(core.pending)
            streams = [st.req.stream for st in core.active.values()]
            core.pending.clear()
            core.cond.notify_all()
        log.error("generation engine stalled: %s", err)
        for r in reqs:
            r.stream._finish(err)
        for s in streams:
            s._finish(err)

    # ------------------------------------------------- loop internals ----
    # Everything below here runs on the loop thread only (except warmup,
    # which the caller must run before traffic).

    def _step(self) -> None:
        """One scheduler iteration: admit pending prompts into free slots
        (paged: only while the pool can cover the head request's full
        reservation — FIFO, so page pressure delays rather than reorders),
        advance one prefill chunk per prefilling slot, then one decode
        step over every decoding slot. Each iteration lands one row in
        the step timeline (host vs device split) and the aggregate in
        the metrics' ``engine_steps`` block.

        With ``async_scheduling=True`` (and no speculative draft) the
        iteration runs :meth:`_step_async` instead: land step N,
        dispatch step N+1, then do the host work under the in-flight
        step — same stream bytes, same executables, one step of
        scheduling lag."""
        if self._async:
            return self._step_async()
        t_iter = time.monotonic()
        self._profile_tick()
        self._maybe_flush_prefix()
        if self._pending_offloads:
            # reap landed device->host offload copies between
            # iterations — a non-blocking poll; a copy still in flight
            # waits for the next iteration, never a decode step
            self._drain_offloads()
        decode_s = verify_s = 0.0
        core = self._core
        prefill_s = self._admit_and_prefill()
        with core.cond:
            active = sorted((s, st) for s, st in core.active.items()
                            if st.phase == "decode")
        if active:
            t0 = time.monotonic()
            if self.speculative:
                self._speculative_round(active)
                verify_s = time.monotonic() - t0
            else:
                self._decode_once(active)
                decode_s = time.monotonic() - t0
        with core.cond:
            depth = len(core.pending)
            n_active = len(core.active)
        device_s = prefill_s + decode_s + verify_s
        host_s = max(0.0, time.monotonic() - t_iter - device_s)
        self.timeline.record(
            host_s=host_s, prefill_s=prefill_s, decode_s=decode_s,
            verify_s=verify_s, active=n_active, queue_depth=depth,
            occupancy=n_active / self.max_slots,
            pages_in_use=self._pool.in_use if self.paged else 0)
        self.metrics.record_engine_step(host_s, device_s)

    def _maybe_flush_prefix(self) -> None:
        """Apply a pending ``reload()`` prefix flush on the loop thread
        (the only thread allowed to touch the pool)."""
        if self._prefix is not None and self._prefix_flush:
            # reload() ran on another thread: cached pages hold K/V the
            # OLD params wrote — drop them here before any admission
            # can probe
            self._prefix_flush = False
            self._prefix.clear()
            if self._dprefix is not None:
                self._dprefix.clear()
            if self._host is not None:
                # host entries are keyed by the OLD index version and
                # can never match again — drop them (and any copies
                # still in flight) so the tier gauge drains with the
                # device index
                self._pending_offloads.clear()
                self._host.clear()
            self._evict_stale = False
            self._report_pages()

    def _admit_and_prefill(self) -> float:
        """Admission + chunked-prefill pass shared by the sync and
        async iterations; returns the prefill wall share. In the async
        iteration this runs AFTER the next decode step was dispatched,
        i.e. inside the overlap window."""
        core = self._core
        while True:
            swap_head = None
            swap_need = 0
            with core.cond:
                if not core.pending or not core.free:
                    break
                take = 0
                if self.paged:
                    need_alloc, probes = self._admit_need(core.pending[0])
                    if not self._pool.can_reserve(need_alloc) and \
                            not self._evict_for(need_alloc, probes):
                        # page pressure: evict unreferenced cached
                        # prefixes (LRU) first; only when the cache
                        # cannot cover the shortfall does the FIFO
                        # head-of-line wait trigger — a delay, never a
                        # reorder, unless cache-aware admission is on
                        # and a LATER pending request fits as-is (then
                        # a bounded bypass keeps the pool busy while
                        # the head waits)
                        bypass = self._pick_bypass()
                        if bypass is None:
                            # last resort before the FIFO wait: a host-
                            # tier engine may swap OUT lower-priority
                            # active streams for the head (QoS, PR 18)
                            # — decided outside the lock below, then
                            # the head re-evaluates
                            swap_head = core.pending[0]
                            swap_need = need_alloc
                        else:
                            take = bypass
                if swap_head is None:
                    if take == 0:
                        self._head_bypasses = 0
                        req = core.pending.popleft()
                    else:
                        self._head_bypasses += 1
                        self.admission_bypasses += 1
                        req = core.pending[take]
                        del core.pending[take]
                    depth = len(core.pending)
            if swap_head is not None:
                if self._swap_out_for(swap_head, swap_need):
                    continue
                break
            self.metrics.set_queue_depth(depth)
            if req.handoff is not None:
                self._admit_prefilled(req)
            elif self.paged:
                self._admit_paged(req)
            else:
                self._admit(req)
        prefill_s = 0.0
        if self.paged:
            with core.cond:
                prefilling = sorted((s, st) for s, st in core.active.items()
                                    if st.phase == "prefill")
            if prefilling:
                t0 = time.monotonic()
                for slot, st in prefilling:
                    self._prefill_chunk_once(slot, st)
                prefill_s = time.monotonic() - t0
        return prefill_s

    def _step_async(self) -> None:
        """One ASYNC scheduler iteration (``async_scheduling=True``):

        1. LAND the in-flight step's token/key futures — the only
           device sync in the loop;
        2. DISPATCH the next decode step immediately, from the live
           step arrays (landed rows folded in, re-armed rows skipped),
           before ANY host bookkeeping runs;
        3. PROCESS the landed step under the in-flight one: token
           delivery, ITL, retirement — then admission, prefill chunks,
           and the KV-tier offload poll, all inside the overlap window.

        Scheduling decisions lag one step: a slot whose landed token
        hits EOS / max-tokens / the deadline already rides in the step
        dispatched at (2). Its extra token is discarded at the next
        land (the participant no longer maps to the same slot state),
        and its garbage K/V write goes to its own — by then possibly
        recycled — pages at a clamped position: device program order
        puts that write BEFORE any later owner's prefill, and causal
        masking hides whatever the prefill does not overwrite (the same
        recycled-page argument the sync engine already relies on).

        Stream bytes are identical to the sync path: decode is per-row
        independent (per-slot attention lanes, per-slot sampling keys),
        so rider rows and stale garbage rows cannot perturb a live
        row's token, and every dispatch input is a host numpy array
        exactly like the sync path's — same executable signature, so
        compile-once holds with zero new traces."""
        t_iter = time.monotonic()
        self._profile_tick()
        self._maybe_flush_prefix()
        core = self._core
        decode_s = 0.0
        ticket = self._inflight
        toks = None
        t_land_end = None
        if ticket is not None:
            self._inflight = None
            t0 = time.monotonic()
            toks = np.asarray(ticket.toks)
            keys = (np.asarray(ticket.keys) if ticket.keys is not None
                    else None)
            decode_s = time.monotonic() - t0
            t_land_end = time.monotonic()
            # fold the landed rows into the live dispatch arrays —
            # skipping rows armed since the ticket left: a slot retired
            # and re-admitted while its last step was still in flight
            # must keep its fresh arming, not the old ticket's output
            for slot, _st in ticket.parts:
                if slot in self._armed_dirty:
                    continue
                self._step_tokens[slot] = toks[slot]
                self._step_positions[slot] = ticket.positions[slot] + 1
                if keys is not None:
                    self._keys[slot] = keys[slot]
                # grammar (PR 20): the advance must land HERE, before
                # the next dispatch reads self._bias — the mask for
                # step N+1 reflects the token step N emitted. Verdicts
                # (stuck/violation) are recorded on the slot state and
                # surfaced by _process_landed below; the fold-in filter
                # (armed-dirty skip) matches _process_landed's identity
                # filter, so exactly the delivered slots advance.
                self._grammar_step(slot, _st, int(toks[slot]))
        # dispatch the next step BEFORE any host bookkeeping: from here
        # to the next land, the device and the host run concurrently
        with core.cond:
            active = sorted((s, st) for s, st in core.active.items()
                            if st.phase == "decode")
        step_gap_s = 0.0
        t_disp = None
        if active:
            t0 = time.monotonic()
            self._dispatch_decode(active)
            t_disp = time.monotonic()
            if t_land_end is not None:
                # host-side gap between landing step N and dispatching
                # step N+1 — a lower bound on device idle per step
                step_gap_s = t0 - t_land_end
        self._armed_dirty.clear()
        # ---- overlap window: everything below runs while the step
        # dispatched above is in flight on device ----
        if ticket is not None:
            self._process_landed(ticket, toks)
        if self._pending_offloads:
            # KV-tier poll (PR 18), relocated into the overlap window:
            # reap landed device->host offload copies while the decode
            # step runs instead of serializing before the next dispatch
            self._drain_offloads()
        prefill_s = self._admit_and_prefill()
        with core.cond:
            depth = len(core.pending)
            n_active = len(core.active)
        t_end = time.monotonic()
        overlapped_s = 0.0
        if t_disp is not None:
            # host share of the iteration spent under the in-flight
            # step (the prefill-chunk device waits are not host work)
            overlapped_s = max(0.0, t_end - t_disp - prefill_s)
            if self._inflight is not None:
                self._inflight.overlap_s = overlapped_s
        device_s = prefill_s + decode_s
        host_s = max(0.0, t_end - t_iter - device_s)
        self.timeline.record(
            host_s=host_s, prefill_s=prefill_s, decode_s=decode_s,
            step_gap_s=step_gap_s, host_overlapped_s=overlapped_s,
            active=n_active, queue_depth=depth,
            occupancy=n_active / self.max_slots,
            pages_in_use=self._pool.in_use if self.paged else 0)
        self.metrics.record_engine_step(host_s, device_s,
                                        overlapped=overlapped_s > 0)

    def _dispatch_decode(self, active: List[Tuple[int, _SlotState]]) -> None:
        """Launch one decode step without waiting for it (async path).
        Inputs are freshly built / copied host arrays — the device-side
        half of the double buffer: the engine may mutate the live
        arrays for step N+2 the moment this returns. Positions clamp at
        the lane end for rider rows (the speculative round's clamp
        precedent); a rider's write lands in its own lane/pages and is
        causally invisible to every later owner."""
        faults.fire("engine.decode", engine=self)
        tokens = np.zeros((self.max_slots,), np.int32)
        positions = np.zeros((self.max_slots,), np.int32)
        for slot, _st in active:
            tokens[slot] = self._step_tokens[slot]
            positions[slot] = min(int(self._step_positions[slot]),
                                  self.max_len - 1)
        if self.paged:
            toks_dev, keys_dev, self._cache = self.kernels.decode(
                self._params, self._cache, tokens, positions,
                self._page_map.copy(), self._temps.copy(),
                self._top_ks.copy(), self._top_ps.copy(),
                self._keys.copy(),
                bias=None if self._bias is None else self._bias.copy())
        else:
            toks_dev, self._cache = self.kernels.decode(
                self._params, self._cache, tokens, positions)
            keys_dev = None
        self._inflight = _StepTicket(list(active), positions, toks_dev,
                                     keys_dev)

    def _arm_async_slot(self, slot: int, st: _SlotState) -> None:
        """Arm a slot's live dispatch inputs (async path). Every site
        that hands a slot its first decodable token — dense admission,
        the final prefill chunk, a decode-role / swap-resume admission
        — writes the token and position HERE; the dispatch side reads
        only these rows, because the slot state itself is updated by
        the landing side one step late. Marking the row dirty keeps an
        in-flight ticket's land from folding stale output over a fresh
        arming (the slot retired and was re-admitted mid-flight)."""
        if not self._async:
            return
        self._step_tokens[slot] = st.last_token
        self._step_positions[slot] = st.position
        self._armed_dirty.add(slot)

    def _process_landed(self, ticket: _StepTicket,
                        toks: "np.ndarray") -> None:
        """Deliver a landed async step: push tokens, tick traces, record
        ITL, retire — the sync `_decode_once` tail, one step late.
        Participants whose slot no longer maps to the SAME state
        (retired rider, swapped-out victim, re-admitted slot) are
        skipped: their token is discarded, their stream untouched."""
        core = self._core
        with core.cond:
            live = [(slot, st) for slot, st in ticket.parts
                    if core.active.get(slot) is st]
        now = time.monotonic()
        self.metrics.record_decode_step(len(ticket.parts), self.max_slots)
        sampled = 0
        retired = []
        for slot, st in live:
            tok = int(toks[slot])
            st.last_token = tok
            st.position += 1
            st.generated += 1
            sampled += st.req.sampled
            tr = st.req.stream.trace
            if tr is not None:
                tr.tick("decode")
            if st.t_last:
                self.metrics.record_itl(now - st.t_last)
            st.t_last = now
            st.req.stream._push(tok, now)
            # the automaton already advanced in the fold-in (the mask
            # had to be live before the dispatch above) — only the
            # verdict is read here
            why = self._grammar_why(st, self._retire_why(st, st.req, now))
            if why is not None:
                retired.append((slot, st, why))
        if sampled:
            self.metrics.record_sampled(sampled)
        for slot, st, why in retired:
            self._release_slot(slot, st)
            self._finish_slot(st, why, now)

    def _profile_tick(self) -> None:
        """Opt-in ``jax.profiler`` bracket: with ``profile_dir`` set,
        start a device trace at the first scheduler iteration and stop
        it after ``profile_iters`` — the on-chip step breakdown the
        BENCH/MFU round reads. Never lets a profiler failure (no
        backend support, a second concurrent trace) break serving."""
        if self._profile_dir is None or self._profile_state == 2:
            return
        try:
            if self._profile_state == 0:
                jax.profiler.start_trace(self._profile_dir)
                self._profile_state = 1
                return
            self._profile_count += 1
            if self._profile_count >= self._profile_iters:
                jax.profiler.stop_trace()
                self._profile_state = 2
        except Exception:
            log.exception("engine profiler bracket failed; disabled")
            self._profile_state = 2

    def _report_pages(self) -> None:
        """Publish page occupancy plus the dtype-aware byte gauge (the
        same reserved pages, priced in the cache's ACTUAL dtype with
        scale pools included; a speculative engine prices target and
        draft lanes at their own models' per-page cost)."""
        self.metrics.set_pages(self._pool.in_use, self._pool.num_pages)
        if self._prefix is not None:
            self.metrics.set_shared_pages(
                self._prefix.pages
                + (self._dprefix.pages if self._dprefix is not None
                   else 0))
        if self._host is not None:
            self.metrics.set_host_pages(self._host.pages,
                                        self._host.bytes_used)
        if not self._kv_page_bytes:
            return
        if self.speculative:
            in_bytes = (self._pool.in_use_by("target")
                        * self._kv_page_bytes
                        + self._pool.in_use_by("draft")
                        * self._kv_dpage_bytes)
        else:
            in_bytes = self._pool.in_use * self._kv_page_bytes
        self.metrics.set_kv_cache(in_bytes, self.cache_dtype_name)

    def _pages_needed(self, req: _GenRequest) -> int:
        # PER-LANE pages: rows written = prompt + generated - 1 (the
        # final token is returned but never written back before the slot
        # retires). A speculative slot reserves this many for EACH of
        # its two lanes (`_lanes` — the draft writes the same positions).
        # A prefill-role slot writes prompt rows only — generation pages
        # are reserved by the adopting decode pool.
        if self.role == "prefill":
            return self._pool.pages_for(len(req.prompt))
        return self._pool.pages_for(
            min(len(req.prompt) + req.max_new_tokens - 1, self.max_len))

    # --------------------------------------------- prefix-cache hooks ----

    def _prefix_probe(self, req: _GenRequest):
        """Probe the per-lane prefix indexes for ``req``'s page-aligned
        prompt prefix. Returns ``(cached token count, [(pages, nodes)
        per lane])``; a speculative engine clamps to the COMMON hit
        depth of both lanes — the chunk skip is shared, so a page one
        lane lost to eviction forces the other to re-prefill it too.

        A pending reload flush (``_prefix_flush``) forces a MISS: the
        reload already swapped the params this request will decode
        with, so every cached entry is stale even though the loop has
        not cleared the index yet (that happens at the next ``_step``
        top — admissions run after that check, but reload can land
        between the check and this probe)."""
        if self._prefix_flush:
            empty = ([], [])
            return 0, [empty, empty] if self._dprefix is not None \
                else [empty]
        n_tok, pages, nodes = self._prefix.lookup(req.prompt)
        if self._dprefix is None:
            return n_tok, [(pages, nodes)]
        dn_tok, dpages, dnodes = self._dprefix.lookup(req.prompt)
        k = min(n_tok, dn_tok) // self.page_size
        return k * self.page_size, [(pages[:k], nodes[:k]),
                                    (dpages[:k], dnodes[:k])]

    def _admit_need(self, req: _GenRequest):
        """Pages the pool must ALLOCATE to admit ``req`` (cache-attached
        prefix pages are shared, not allocated), plus the probe result
        protecting the matched chains from eviction."""
        need = self._lanes * self._pages_needed(req)
        if self._prefix is None or req.handoff is not None:
            # handoff admissions never probe the prefix index (it lives
            # with the prefill role); adopt-side dedup may still make
            # some of `need` shares instead of allocs — gating on the
            # full count is the conservative bound
            return need, None
        cached_len, probes = self._prefix_probe(req)
        return need - self._lanes * (cached_len // self.page_size), probes

    def _evict_for(self, need_alloc: int, probes) -> bool:
        """Try to free enough cached pages for an admission short by
        ``need_alloc - free`` pages: LRU leaf eviction per lane, never
        touching the chains the admission itself matched. True when the
        pool can now cover the reservation."""
        if self._prefix is None or self._evict_stale:
            # a prior scan found nothing evictable and no release or
            # publish has happened since — the answer cannot have
            # changed, skip the index walk
            return False
        protect = set()
        for pr in probes or ():
            protect.update(pr[1])
        shortfall = need_alloc - self._pool.free_pages
        freed = 0
        # host tier (PR 18): target-lane victims offload instead of
        # vanishing — the hook dispatches each page's device gather
        # BEFORE evict() releases it (speculative engines never have a
        # host tier, so the draft lane below stays hook-less)
        on_evict = self._offload_page if self._host is not None else None
        for cache in (self._prefix, self._dprefix):
            if cache is None or shortfall <= freed:
                break
            freed += cache.evict(shortfall - freed, frozenset(protect),
                                 on_evict=on_evict)
            on_evict = None
        if freed == 0:
            self._evict_stale = True
        return self._pool.can_reserve(need_alloc)

    # ------------------------------------------------ host tier (PR 18) ----

    def _offload_page(self, prefix: Tuple[int, ...], page: int) -> None:
        """Prefix-eviction hook (``PrefixCache.evict`` ``on_evict``):
        gather the victim page into a fixed-shape device block — the
        SAME jitted gather the disaggregation handoff compiles, row 0
        real, the rest trash — and start its async device->host copy.
        Runs BEFORE evict() releases the page, so the pure-read gather
        can never race the page's next owner (donation waits on pending
        readers). Completion is polled between scheduler iterations
        (``_drain_offloads``); at most ``_offload_inflight_cap`` copies
        are ever in flight — past the cap the page just evicts (the
        pre-PR-18 behaviour), counted as a drop. Must not raise: the
        eviction proceeds regardless."""
        if len(self._pending_offloads) >= self._offload_inflight_cap:
            self._drain_offloads()   # non-blocking: reap what landed
        if len(self._pending_offloads) >= self._offload_inflight_cap:
            self._host.record_drop()
            self.metrics.record_offload_dropped()
            return
        try:
            faults.fire("kv.offload", engine=self, kind="prefix")
        except BaseException as exc:
            # fault-injected copy failure: the page evicts plainly
            # (never strands in either tier), only this entry is lost
            log.debug("kv.offload copy faulted; entry dropped: %s", exc)
            self._host.record_drop()
            self.metrics.record_offload_dropped()
            return
        idx = np.full((self._pool.pages_per_slot,), self._pool.trash,
                      np.int32)
        idx[0] = page
        block = self._mover.gather(self._cache, idx)
        jax.tree_util.tree_map(_start_host_copy, block)
        self._pending_offloads.append({
            "kind": "prefix", "key": tuple(prefix),
            "version": self._prefix.version, "block": block})

    def _drain_offloads(self, wait: bool = False) -> None:
        """Reap finished device->host offload copies, FIFO. Non-blocking
        by default (one poll per scheduler iteration — an unfinished
        copy waits, a decode step never does); ``wait=True`` blocks
        until everything lands (tests and drain paths only)."""
        host = self._host
        drained = False
        while self._pending_offloads:
            entry = self._pending_offloads[0]
            block = (entry["block"] if entry["kind"] == "prefix"
                     else entry["payload"]["block"])
            if not wait and not _block_ready(block):
                break
            self._pending_offloads.pop(0)
            drained = True
            if entry["kind"] == "prefix":
                if entry["version"] != self._prefix.version:
                    # a reload flush raced the copy: bytes the OLD
                    # params wrote must not enter the host index
                    host.record_drop()
                    self.metrics.record_offload_dropped()
                    continue
                rows = jax.tree_util.tree_map(
                    lambda leaf: np.asarray(leaf[0]), entry["block"])
                host.put_prefix(entry["version"], entry["key"], rows)
                self.metrics.record_offload(1)
            else:
                # swap payload: the block's device buffers release once
                # the rows live host-side (the payload itself already
                # rides the re-queued resume request; device_put at
                # adoption uploads np leaves identically)
                payload = entry["payload"]
                payload["block"] = jax.tree_util.tree_map(
                    lambda leaf: np.asarray(leaf), payload["block"])
        if drained:
            self._report_pages()

    def _restore_prefix(self, req: _GenRequest, cached_len: int
                        ) -> Tuple[List[int], int]:
        """Extend a device prefix hit from the HOST tier: consecutive
        page-aligned chunks past the device hit whose bytes were
        offloaded come back host->device — fresh pages allocate, ONE
        batched scatter (the warmed executable) writes them, and the
        chunks REPUBLISH into the device index, so the attach in
        ``_admit_paged`` sees them exactly as never-evicted entries
        (the copy is a memcpy both ways — bit-identity is free, int8
        scale pools ride as ordinary leaves). Returns ``(restored
        pages, new cached_len)``; the restored pages replace tail
        allocations one for one, so the admission gate's reservation
        arithmetic is unchanged. An injected ``kv.restore`` fault
        degrades the affected entries to a plain miss (they leave the
        host store; the request re-prefills; the stream is unharmed)."""
        host = self._host
        ps = self.page_size
        prompt = req.prompt
        version = self._prefix.version
        start_k = cached_len // ps
        hits: List[Tuple[int, ...]] = []
        for k in range(start_k, (len(prompt) - 1) // ps):
            key = tuple(prompt[:(k + 1) * ps])
            if not host.has_prefix(version, key):
                break
            hits.append(key)
        if not hits:
            return [], cached_len
        try:
            faults.fire("kv.restore", engine=self, kind="prefix")
        except BaseException:
            for key in hits:
                host.drop_prefix(version, key)
            self._report_pages()
            return [], cached_len
        pages = self._pool.alloc(len(hits), owner="target")
        ppn = self._pool.pages_per_slot
        idx = np.full((ppn,), self._pool.trash, np.int32)
        idx[:len(pages)] = pages
        rows = [host.take_prefix(version, key) for key in hits]

        def _fill(leaf, *page_rows):
            out = np.zeros((ppn,) + leaf.shape[1:], leaf.dtype)
            for i, r in enumerate(page_rows):
                out[i] = r
            return out

        block = jax.tree_util.tree_map(_fill, self._cache, *rows)
        if self._cache_sharding is not None:
            block = jax.device_put(
                block, _cache_sharding_tree(block, self._cache_sharding))
        else:
            block = jax.device_put(block)
        self._cache = self._mover.scatter(self._cache, block, idx)
        # republish: the restored chunks re-enter the device index with
        # their own cache references (request ref + cache ref, the
        # never-evicted end state). Rows before start_k descend the
        # live device chain — publish only reads the row for NEW nodes.
        end = (start_k + len(hits)) * ps
        pub_row = np.full((ppn,), self._pool.trash, np.int32)
        pub_row[start_k:start_k + len(pages)] = pages
        self._prefix.publish(prompt[:end], pub_row)
        self._evict_stale = False
        self.metrics.record_restore(len(pages))
        self._report_pages()
        return pages, end

    def _swap_out_for(self, head: _GenRequest, need_alloc: int) -> bool:
        """QoS swap (PR 18): the FIFO head is page-blocked and neither
        eviction nor bypass helped — swap OUT lowest-priority, longest-
        idle active decode streams (pages + PRNG key + position through
        the host tier; the stream parks on a re-queued resume request)
        until the head's reservation fits. Only STRICTLY lower priority
        yields, so a swap chain terminates and equal-priority traffic
        never thrashes. False leaves the plain FIFO wait in place."""
        if self._host is None or self.role != "both":
            return False
        core = self._core
        swapped = False
        while not self._pool.can_reserve(need_alloc):
            with core.cond:
                victims = [
                    (st.req.priority, st.t_last, slot, st)
                    for slot, st in core.active.items()
                    if st.phase == "decode" and st.pages
                    and st.req.priority < head.priority
                    and st.req.grammar is None
                    # constrained streams never swap: the resume payload
                    # carries no automaton state, and replaying the
                    # advance through the host tier buys nothing — the
                    # head waits for a different victim instead
                    and st.generated < st.req.max_new_tokens
                    and st.position < self.max_len]
            if not victims:
                return swapped and self._pool.can_reserve(need_alloc)
            victims.sort(key=lambda v: (v[0], v[1]))
            _, _, slot, st = victims[0]
            if not self._swap_out_slot(slot, st):
                return False
            swapped = True
        return True

    def _swap_out_slot(self, slot: int, st: _SlotState) -> bool:
        """Export one active decode stream to the host tier: gather its
        whole lane (the handoff gather), start the async host copy,
        export the pages, park the slot, and re-queue a resume request
        carrying the handoff-shaped payload — adoption replays it
        byte-exactly (the PRNG key splits once per emitted token while
        resident, so park/resume never skews a sampled stream). A
        faulted swap-out aborts BEFORE anything moves: the victim stays
        resident with all its pages."""
        try:
            faults.fire("kv.offload", engine=self, kind="swap")
        except BaseException:
            self._host.record_drop()
            self.metrics.record_offload_dropped()
            return False
        req = st.req
        self._swap_seq += 1
        swap_id = self._swap_seq
        ps = self.page_size
        plen = len(req.prompt)
        meta = np.asarray(
            [(int(p), self._pool.generation(p), int((i + 1) * ps <= plen))
             for i, p in enumerate(st.pages)], np.int64).reshape(-1, 3)
        block = self._mover.gather(self._cache, st.page_row)
        jax.tree_util.tree_map(_start_host_copy, block)
        payload = {
            "prompt": np.asarray(req.prompt, np.int32),
            "first_token": int(st.last_token),
            "key": self._keys[slot].copy(),
            "plen": plen,
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "top_k": int(req.top_k),
            "top_p": float(req.top_p),
            "deadline": req.deadline,
            "page_row": st.page_row.copy(),
            "page_meta": meta,
            "source": self.handoff_source,
            "tag": req.tag,
            "block": block,
            "swap": True,
            "swap_id": swap_id,
            "position": int(st.position),
            "generated": int(st.generated),
            "priority": int(req.priority),
            "t_admit": float(st.t_admit),
        }
        self._pending_offloads.append({"kind": "swap", "payload": payload})
        core = self._core
        with core.cond:
            core.active.pop(slot, None)
            core.free.append(slot)
        # the request's references leave through handoff accounting: the
        # gather above captured the bytes (a pure read the pages' next
        # owner must wait on), the ids free for the head. No publish —
        # nothing may newly enter the device index off a parked stream.
        self._pool.export_pages(st.pages or ())
        st.pages = None
        self._page_map[slot] = self._pool.trash
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._keys[slot] = 0
        if self._bias is not None:
            self._bias[slot] = 0.0
        self._evict_stale = False
        self._host.park_stream(swap_id, len(meta))
        self.metrics.record_swap_out()
        resume = _GenRequest(req.prompt, req.max_new_tokens, req.deadline,
                             req.stream, temperature=req.temperature,
                             top_k=req.top_k, top_p=req.top_p,
                             seed=req.seed, tag=req.tag, handoff=payload,
                             priority=req.priority)
        with core.cond:
            # FIFO tail: the resumed stream waits its turn like any
            # arrival — fairness under repeated pressure is bounded by
            # the strict-priority rule, not by queue position
            core.pending.append(resume)
        self._report_pages()
        return True

    def _chunk_invocations(self, n_tokens: int) -> int:
        """Kernel invocations (non-final chunks + the final prefill) a
        prompt tail of ``n_tokens`` costs — the unit the
        ``prefill_chunks_skipped`` saving is counted in."""
        if n_tokens <= 0:
            return 0
        return (n_tokens - 1) // self.prefill_chunk + 1

    def _request_key(self, req: _GenRequest) -> np.ndarray:
        seed = req.seed
        if seed is None:
            seed = request_seed(
                self.seed, np.asarray(req.prompt, np.int32).tobytes(),
                len(req.prompt))
        return threefry_key_data(seed)

    def _admit_paged(self, req: _GenRequest) -> None:
        """Paged admission is bookkeeping only: reserve the slot and its
        full page budget. The prompt itself runs as chunks inside the
        iteration loop so a long prompt interleaves with neighbours'
        decode steps.

        CRITICAL ordering: the slot's row of ``self._page_map`` stays
        parked on the trash page (and its sampling params/key stay
        disarmed) until the FINAL chunk completes — interleaved decode
        steps scatter a pad-token K/V row for every slot in the batch,
        prefilling ones included, and split every slot's PRNG key. Expose
        the real pages or the request key early and those decode steps
        would corrupt the prompt's first page and make the sampled
        stream depend on neighbour traffic. The chunk/prefill kernels
        take the page row as an explicit argument instead."""
        now = time.monotonic()
        why = self._retire_why(None, req, now)
        if why is not None:
            self._finish_request(req, why, now, queue_wait=None)
            return
        if req.grammar is not None:
            self.metrics.record_constrained_stream()
        core = self._core
        with core.cond:
            core.free.sort()
            slot = core.free.pop(0)
        need = self._pages_needed(req)
        tr = req.stream.trace
        reserve_sp = None
        if tr is not None:
            tr.span("queue_wait", tr.t0)
            reserve_sp = tr.begin_span("page_reserve")
        # prefix-cache probe: hit pages attach by REFERENCE (share) and
        # their tokens never re-prefill; only the divergent tail and the
        # generation budget allocate fresh pages. The attach is what
        # copy-on-write protects — and because hits are page-ALIGNED and
        # always leave >= 1 tail token, every write the request will
        # ever issue (tail chunks, decode rows) lands at positions past
        # the attached prefix, in pages it allocated itself: CoW
        # reduces to the alignment assertion below.
        cached_len = 0
        hit_k = 0
        shared_pages: List[int] = []
        dshared_pages: List[int] = []
        restored: List[int] = []
        if self._prefix is not None:
            cached_len, probes = self._prefix_probe(req)
            assert cached_len % self.page_size == 0 \
                and cached_len < len(req.prompt), \
                "prefix attach must be page-aligned with a live tail"
            hit_k = cached_len // self.page_size
            if hit_k:
                shared_pages = list(probes[0][0])
                self._pool.share(shared_pages)
                if self._dprefix is not None:
                    dshared_pages = list(probes[1][0])
                    self._pool.share(dshared_pages)
            if self._host is not None and not self._prefix_flush:
                # host tier (PR 18): chains the device index evicted may
                # live one tier down — restored pages slot in right
                # after the device hit and count as cached from here on
                restored, cached_len = self._restore_prefix(req,
                                                            cached_len)
                hit_k = cached_len // self.page_size
            skipped = (self._chunk_invocations(len(req.prompt))
                       - self._chunk_invocations(len(req.prompt)
                                                 - cached_len))
            self._prefix.record_probe(hit_k > 0, cached_len)
            if self._dprefix is not None:
                self._dprefix.record_probe(hit_k > 0, cached_len)
            self.metrics.record_prefix_probe(hit_k > 0,
                                             skipped * self._lanes)
        pages = shared_pages + restored + self._pool.alloc(
            need - hit_k, owner="target")
        row = np.full((self._pool.pages_per_slot,), self._pool.trash,
                      np.int32)
        row[:len(pages)] = pages
        draft_pages = None
        drow = None
        if self.speculative:
            # the draft lane reserves the same row budget side by side
            # (one pool, owner-tagged so the drain invariants are
            # assertable per lane)
            draft_pages = dshared_pages + self._pool.alloc(
                need - hit_k, owner="draft")
            drow = np.full((self._pool.pages_per_slot,), self._pool.trash,
                           np.int32)
            drow[:len(draft_pages)] = draft_pages
        if tr is not None:
            tr.end_span(reserve_sp, pages=need * self._lanes, slot=slot,
                        prefix_pages=hit_k * self._lanes)
        st = _SlotState(req, self.pad_id, cached_len, 0, now,
                        phase="prefill", pages=pages, page_row=row,
                        prefill_pos=cached_len, draft_pages=draft_pages,
                        dpage_row=drow)
        if self._prefix is not None:
            # stamp the index version the prompt is prefilled under:
            # a retirement after a reload flush (version bumped) must
            # NOT publish its old-params pages into the fresh index.
            # One stamp covers both lanes — they flush in lockstep.
            st.cache_version = self._prefix.version
        with core.cond:
            core.active[slot] = st
        self._report_pages()
        if self._prefix is not None:
            # fault site: an armed exception lands between the prefix
            # attach (references taken) and the first prefill/decode
            # step — the loop's failure path must release every
            # refcount and leak zero shared pages (chaos-gated)
            faults.fire("engine.prefix_attach", engine=self)

    def _admit_prefilled(self, req: _GenRequest) -> None:
        """Decode-role admission: the prompt's KV rows arrive as a
        gathered device block plus a page manifest instead of running
        prefill here. Adopt the pages (shared prefixes dedup to one
        local copy), scatter the block into this pool's cache, arm the
        slot exactly as a monolithic final chunk would — same last
        token, position, sampling params and post-prefill PRNG key, so
        the decode continuation is bit-identical — and push the first
        token. A failure between adopt and scatter is REQUEST-scoped:
        the cache is untouched until the scatter lands, so only this
        stream fails and its pages release; the engine keeps serving."""
        payload = req.handoff
        swap = bool(payload.get("swap"))
        if swap:
            # the parked booking ends the moment the resume admission
            # runs, whatever its outcome — expiry, cancellation, an
            # injected fault, or a clean adoption; the payload is the
            # only thing that survives a failed resume, and it dies
            # with the request
            self._host.unpark_stream(int(payload["swap_id"]))
            self.metrics.record_swap_in()
            self._report_pages()
        now = time.monotonic()
        why = self._retire_why(None, req, now)
        if why is not None:
            self._finish_request(req, why, now, queue_wait=None)
            return
        core = self._core
        with core.cond:
            core.free.sort()
            slot = core.free.pop(0)
        meta = np.asarray(payload["page_meta"]).reshape(-1, 3)
        need = self._pages_needed(req)
        k_p = len(meta)
        pages: List[int] = []
        try:
            if swap:
                # fault site: before a parked stream's resume adoption —
                # an injected fault fails ONLY this stream (the except
                # below releases its pages); the engine keeps serving
                faults.fire("kv.restore", engine=self, kind="swap")
            # fault site: between the prefill engine's export and this
            # pool's adopt — the chaos gate proves a mid-handoff fault
            # drains BOTH pools' per-owner gauges to zero
            faults.fire("engine.page_handoff", engine=self, stage="adopt")
            pages = self._pool.adopt_pages(
                [(int(m[0]), int(m[1]), bool(m[2])) for m in meta],
                source=str(payload["source"]), owner="target")
            pages = pages + self._pool.alloc(need - k_p, owner="target")
            row = np.full((self._pool.pages_per_slot,), self._pool.trash,
                          np.int32)
            row[:len(pages)] = pages
            idx = np.full((self._pool.pages_per_slot,), self._pool.trash,
                          np.int32)
            idx[:k_p] = pages[:k_p]
            # identity for committed arrays (the local gather's output,
            # wherever it is sharded), an upload for the RPC path's np
            # leaves — both land as ONE committed executable signature
            block = jax.device_put(payload["block"])
            self._cache = self._mover.scatter(self._cache, block, idx)
        except BaseException as e:
            self._pool.release(pages)
            with core.cond:
                core.free.append(slot)
            self._report_pages()
            self.metrics.record_failed()
            req.stream._finish(e, time.monotonic())
            return
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._top_ps[slot] = req.top_p
        self._keys[slot] = np.asarray(payload["key"], np.uint32)
        self._page_map[slot] = row
        tok = int(payload["first_token"])
        now = time.monotonic()
        if swap:
            # a resumed stream continues MID-generation: position,
            # progress and the queue-wait base restore from the payload,
            # and the consumer already holds every pushed token — push
            # nothing, decode on from the parked key (which split once
            # per emitted token while resident: byte-exact resume)
            st = _SlotState(req, tok, int(payload["position"]),
                            int(payload["generated"]),
                            float(payload["t_admit"]), phase="decode",
                            pages=pages, page_row=row)
        else:
            st = _SlotState(req, tok, len(req.prompt), 1, now,
                            phase="decode", pages=pages, page_row=row)
        st.t_last = now
        with core.cond:
            core.active[slot] = st
        self._arm_async_slot(slot, st)
        self._report_pages()
        if not swap:
            req.stream._push(tok, now)
        why = self._retire_why(st, req, now)
        if why is not None:
            self._release_slot(slot, st)
            self._finish_slot(st, why, now)

    def _handoff_payload(self, slot: int, st: _SlotState,
                         tok: int) -> dict:
        """Everything a decode-role engine needs to continue ``st``'s
        stream bit-identically: the first token, the POST-prefill PRNG
        key (sampled token i draws from split i whatever engine holds
        the slot), sampling params, and the page manifest —
        ``(page id, write generation, shareable)`` rows naming each
        prompt page's content under this engine's ``handoff_source``
        namespace (full prompt pages are shareable; the partial tail
        page keeps taking decode writes and always fresh-copies). The
        KV block itself is gathered by the handoff callback while the
        pages are still owned. np-typed throughout so the payload
        crosses rpc.py npy frames unchanged."""
        req = st.req
        ps = self.page_size
        plen = len(req.prompt)
        meta = np.asarray(
            [(int(p), self._pool.generation(p), int((i + 1) * ps <= plen))
             for i, p in enumerate(st.pages)], np.int64).reshape(-1, 3)
        return {
            "prompt": np.asarray(req.prompt, np.int32),
            "first_token": int(tok),
            "key": self._keys[slot].copy(),
            "plen": plen,
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "top_k": int(req.top_k),
            "top_p": float(req.top_p),
            "deadline": req.deadline,
            "page_row": st.page_row.copy(),
            "page_meta": meta,
            "source": self.handoff_source,
            "tag": req.tag,
            "priority": int(req.priority),
        }

    def _handoff_slot(self, slot: int, st: _SlotState) -> None:
        """Retire a prefill-role slot whose pages were handed off:
        publish the full prompt pages to the prefix index (it lives with
        THIS role — the next same-prefix prompt attaches by reference
        and skips its covered chunks), then export the request's
        references and free the slot. Mirrors ``_release_slot`` except
        the pages leave through ``export_pages`` accounting."""
        core = self._core
        with core.cond:
            core.active.pop(slot, None)
            core.free.append(slot)
        if (self._prefix is not None and st.pages
                and st.cache_version == self._prefix.version):
            self._prefix.publish(st.req.prompt, st.page_row)
            self._evict_stale = False
            self._dedup_after_publish()
        self._pool.export_pages(st.pages or ())
        st.pages = None
        self._page_map[slot] = self._pool.trash
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._keys[slot] = 0
        self._evict_stale = False
        self._report_pages()

    def _abort_handoff(self, slot: int, st: _SlotState,
                       err: BaseException) -> None:
        """A handoff failed before its pages left this pool: release
        them (no publish — the stream is failing, nothing should newly
        enter the index off its back), free the slot, fail the stream
        with the error. REQUEST-scoped on purpose: the gather is a pure
        read, the cache was never touched, so the engine keeps serving
        its other slots."""
        core = self._core
        with core.cond:
            core.active.pop(slot, None)
            core.free.append(slot)
        self._pool.release(st.pages or ())
        st.pages = None
        self._page_map[slot] = self._pool.trash
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._keys[slot] = 0
        self._evict_stale = False
        self._report_pages()
        self.metrics.record_failed()
        now = time.monotonic()
        st.req.stream._finish(err, now)
        tr = st.req.stream.trace
        if tr is not None:
            tr.finish(outcome="failed", tokens=st.generated)

    def _prefill_chunk_once(self, slot: int, st: _SlotState) -> None:
        """Advance one prompt chunk for a prefilling slot. Non-final
        chunks are always exactly ``prefill_chunk`` tokens (one compiled
        shape); the final chunk is bucket-padded and samples the first
        generated token."""
        req = st.req
        now = time.monotonic()
        why = self._retire_why(None, req, now)
        if why is not None:
            self._release_slot(slot, st)
            self._finish_slot(st, why, now)
            return
        faults.fire("engine.prefill", engine=self)
        prompt = req.prompt
        start = st.prefill_pos
        remaining = len(prompt) - start
        pages_row = st.page_row  # NOT self._page_map: see _admit_paged
        tr = req.stream.trace
        if remaining > self.prefill_chunk:
            sp = (tr.begin_span("prefill_chunk") if tr is not None
                  else None)
            tokens = np.asarray(prompt[start:start + self.prefill_chunk],
                                np.int32)
            self._cache = self.kernels.chunk(
                self._params, self._cache, pages_row, tokens, start,
                self.prefill_chunk, self._pool.trash)
            if self.speculative:
                # the draft needs the prompt in its own cache before it
                # can propose: same chunk, draft lane
                self._dcache = self.kernels.draft_write(
                    self._draft_params, self._dcache, st.dpage_row,
                    tokens, start, self.prefill_chunk, self._pool.trash)
            st.prefill_pos += self.prefill_chunk
            st.position = st.prefill_pos
            self.metrics.record_chunk(self.prefill_chunk, self.prefill_chunk)
            if tr is not None:
                tr.end_span(sp, tokens=self.prefill_chunk, final=False)
            return
        final_sp = tr.begin_span("prefill_chunk") if tr is not None else None
        bucket = next(b for b in self.prompt_buckets if b >= remaining)
        padded = np.full((bucket,), self.pad_id, np.int32)
        padded[:remaining] = prompt[start:]
        # the final chunk arms the slot's step inputs: sampling params,
        # the request's PRNG key (fresh HERE, so token i always draws
        # from split i whatever decode traffic ran during the prefill),
        # the grammar start-state mask row (a stale async fold-in may
        # have scribbled a retired owner's row — reset then arm), and —
        # after the K/V writes land — the live page-map row
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._top_ps[slot] = req.top_p
        if self._bias is not None:
            self._bias[slot] = 0.0
            self._grammar_arm(slot, st)
        bias1 = (None if self._bias is None
                 else self._bias[slot:slot + 1].copy())
        if self.speculative:
            # speculative sampling is keyed by (request, output
            # position), never by step — `_keys[slot]` holds the CONSTANT
            # request key and the kernels fold positions in
            key = self._request_key(req)
            tok_dev, self._cache = self.kernels.prefill(
                self._params, self._cache, pages_row, padded, start,
                remaining, self._pool.trash, self._temps[slot],
                self._top_ks[slot], self._top_ps[slot], key, bias=bias1)
            self._dcache = self.kernels.draft_write(
                self._draft_params, self._dcache, st.dpage_row, padded,
                start, remaining, self._pool.trash)
            self._keys[slot] = key
            self._dpage_map[slot] = st.dpage_row
        else:
            tok_dev, key_dev, self._cache = self.kernels.prefill(
                self._params, self._cache, pages_row, padded, start,
                remaining, self._pool.trash, self._temps[slot],
                self._top_ks[slot], self._top_ps[slot],
                self._request_key(req), bias=bias1)
            self._keys[slot] = np.asarray(key_dev)[0]
        tok = int(np.asarray(tok_dev))
        self._page_map[slot] = pages_row
        now = time.monotonic()
        self.metrics.record_prefill(remaining, bucket,
                                    now - req.stream.t_submit)
        if req.sampled:
            self.metrics.record_sampled(1)
        if tr is not None:
            tr.end_span(final_sp, tokens=remaining, final=True)
            tr.event("first_token")
        req.stream._push(tok, now)
        st.phase = "decode"
        st.last_token = tok
        st.position = len(prompt)
        st.generated = 1
        st.t_last = now
        self._grammar_step(slot, st, tok)
        self._arm_async_slot(slot, st)
        why = self._grammar_why(st, self._retire_why(st, req, now))
        if why is not None:
            self._release_slot(slot, st)
            self._finish_slot(st, why, now)
            return
        if self.role == "prefill":
            # the whole prompt is written (phase just flipped) and the
            # request still wants tokens: hand the finished pages to the
            # decode role instead of decoding here. The callback gathers
            # the block from this cache ON THIS THREAD while the pages
            # are still owned, then routes it; only after it returns do
            # the pages export and the slot free. A fault or callback
            # failure is request-scoped — pages release, stream fails,
            # the engine keeps prefilling its other slots.
            try:
                faults.fire("engine.page_handoff", engine=self,
                            stage="export")
                cb = self._handoff_cb
                if cb is None:
                    raise RuntimeError(
                        "prefill-role engine has no handoff consumer "
                        "(set by DisaggregatedEngine / PrefillWorker)")
                cb(self._handoff_payload(slot, st, tok))
            except BaseException as e:
                self._abort_handoff(slot, st, e)
                return
            self._handoff_slot(slot, st)
            self._finish_slot(st, "done", now)

    def _release_slot(self, slot: int, st: _SlotState) -> None:
        """Return a slot (and, paged, its pages + step-input rows) to the
        free state. The page-map row parks on the trash page so the
        still-running decode step can neither read nor clobber a page the
        next owner gets."""
        core = self._core
        with core.cond:
            core.active.pop(slot, None)
            core.free.append(slot)
        if self.paged:
            if (self._prefix is not None and st.pages
                    and st.phase == "decode"
                    and st.cache_version == self._prefix.version):
                # publish the sequence's FULL prompt pages back to the
                # index (phase=="decode" means the whole prompt is
                # written; a mid-prefill retirement has nothing whole
                # to share). New nodes take their own pool references
                # BEFORE the request's are dropped below, so the pages
                # never graze the free heap in between. The version
                # check drops retirements that straddled a reload
                # flush: their pages hold K/V the OLD params wrote and
                # must never re-enter the fresh index.
                self._prefix.publish(st.req.prompt, st.page_row)
                if self._dprefix is not None:
                    self._dprefix.publish(st.req.prompt, st.dpage_row)
                self._evict_stale = False
                # publish-time dedup (PR 14): concurrent same-prefix
                # prefills that all missed the index each wrote their
                # own physical copies of these now-canonical pages —
                # repoint still-active duplicates and free the copies
                self._dedup_after_publish()
            self._pool.release(st.pages or ())
            st.pages = None
            self._page_map[slot] = self._pool.trash
            if self.speculative:
                self._pool.release(st.draft_pages or ())
                st.draft_pages = None
                self._dpage_map[slot] = self._pool.trash
            self._temps[slot] = 0.0
            self._top_ks[slot] = 0
            self._top_ps[slot] = 1.0
            self._keys[slot] = 0
            if self._bias is not None:
                self._bias[slot] = 0.0  # unconstrained no-op row
            st.grammar_state = None
            self._evict_stale = False   # released pages: re-scan is live
            self._report_pages()

    def _dedup_after_publish(self) -> None:
        """Repoint every still-active decode slot whose full prompt
        pages now have canonical cached twins (same chunk chain in the
        index, different physical page) at the cached pages, releasing
        its private duplicates. Bit-identity is free: a FULL prompt
        page is a pure function of ``(params, its page-aligned token
        prefix)``, and a decode slot only ever writes at positions
        ``>= len(prompt)`` — pages past index ``len(prompt) //
        page_size``, never the repointed ones. Loop-thread only, like
        every pool/index mutation."""
        core = self._core
        with core.cond:
            slots = [(s, st) for s, st in core.active.items()
                     if st.phase == "decode" and st.pages]
        for slot, st in slots:
            if st.cache_version != self._prefix.version:
                continue
            n_full = len(st.req.prompt) // self.page_size
            if not n_full:
                continue
            canon = self._prefix.match_pages(st.req.prompt, n_full)
            self._dedup_row(self._prefix, st.pages, st.page_row,
                            self._page_map[slot], canon)
            if self._dprefix is not None and st.draft_pages:
                dcanon = self._dprefix.match_pages(st.req.prompt, n_full)
                self._dedup_row(self._dprefix, st.draft_pages,
                                st.dpage_row, self._dpage_map[slot],
                                dcanon)

    def _dedup_row(self, cache: PrefixCache, pages: List[int], row,
                   map_row, canon: List[int]) -> None:
        swapped = 0
        for i, page in enumerate(canon):
            if i >= len(pages) or pages[i] == page:
                continue
            # order matters: take the cached page's reference BEFORE
            # dropping the duplicate's, the same never-graze-the-free-
            # heap discipline as publish/attach. BOTH rows must repoint:
            # st.page_row feeds publish at retirement, but the decode
            # kernels read the engine's live _page_map row (a separate
            # array — _admit_paged copies values in), and a decoding
            # slot left reading the released duplicate would see the
            # page's NEXT owner overwrite it
            self._pool.share([page])
            self._pool.release([pages[i]])
            pages[i] = page
            row[i] = page
            map_row[i] = page
            swapped += 1
        if swapped:
            cache.deduped_pages += swapped
            self._evict_stale = False  # freed pages: re-scan is live

    def _pick_bypass(self) -> Optional[int]:
        """Cache-aware admission (PR 14): index into ``core.pending``
        of a later request to admit while the page-blocked FIFO head
        waits, or ``None`` (strict FIFO wait). Caller holds the core
        lock. A candidate must fit the pool AS-IS — no eviction runs
        on its behalf, freed pages belong to the head. Among fitting
        candidates the longest resident prefix wins (it allocates the
        fewest fresh pages and strictly extends the pool's runway);
        FIFO position breaks ties. At most ``_bypass_limit``
        consecutive bypasses per blocked head, so the head's wait is
        bounded by construction."""
        if (not self.cache_aware_admission
                or self._head_bypasses >= self._bypass_limit):
            return None
        best: Optional[Tuple[int, int]] = None   # (cached_len, index)
        pending = self._core.pending
        for j in range(1, len(pending)):
            need, _ = self._admit_need(pending[j])
            if not self._pool.can_reserve(need):
                continue
            cached = 0
            if self._prefix is not None:
                cached, _ = self._prefix_probe(pending[j])
            if best is None or cached > best[0]:
                best = (cached, j)
        return None if best is None else best[1]

    def _admit(self, req: _GenRequest) -> None:
        now = time.monotonic()
        why = self._retire_why(None, req, now)
        if why is not None:
            self._finish_request(req, why, now, queue_wait=None)
            return
        faults.fire("engine.prefill", engine=self)
        core = self._core
        with core.cond:
            core.free.sort()
            slot = core.free.pop(0)
        tr = req.stream.trace
        sp = None
        if tr is not None:
            tr.span("queue_wait", tr.t0)
            sp = tr.begin_span("prefill_chunk", slot=slot)
        n = len(req.prompt)
        bucket = next(b for b in self.prompt_buckets if b >= n)
        padded = np.full((bucket,), self.pad_id, np.int32)
        padded[:n] = req.prompt
        tok_dev, self._cache = self.kernels.prefill(
            self._params, self._cache, slot, padded, n)
        tok = int(np.asarray(tok_dev))
        now = time.monotonic()
        self.metrics.record_prefill(n, bucket, now - req.stream.t_submit)
        if tr is not None:
            tr.end_span(sp, tokens=n, final=True)
            tr.event("first_token")
        req.stream._push(tok, now)
        st = _SlotState(req, tok, n, 1, now)
        st.t_last = now
        why = self._retire_why(st, req, now)
        if why is None:
            with core.cond:
                core.active[slot] = st
            self._arm_async_slot(slot, st)
        else:
            with core.cond:
                core.free.append(slot)
            self._finish_slot(st, why, now)

    def _decode_once(self, active: List[Tuple[int, _SlotState]]) -> None:
        # fault site: an armed exception is exactly a kernel/step failure
        # (the loop fails every stream and stops); armed latency models a
        # slow or wedged device for the stall watchdog
        faults.fire("engine.decode", engine=self)
        tokens = np.zeros((self.max_slots,), np.int32)
        positions = np.zeros((self.max_slots,), np.int32)
        for slot, st in active:
            tokens[slot] = st.last_token
            positions[slot] = st.position
        if self.paged:
            toks_dev, keys_dev, self._cache = self.kernels.decode(
                self._params, self._cache, tokens, positions,
                self._page_map, self._temps, self._top_ks, self._top_ps,
                self._keys, bias=self._bias)
            self._keys = np.array(keys_dev)  # writable copy (host-mutated)
        else:
            toks_dev, self._cache = self.kernels.decode(
                self._params, self._cache, tokens, positions)
        toks = np.asarray(toks_dev)
        now = time.monotonic()
        self.metrics.record_decode_step(len(active), self.max_slots)
        sampled = 0
        retired = []
        for slot, st in active:
            tok = int(toks[slot])
            st.last_token = tok
            st.position += 1
            st.generated += 1
            sampled += st.req.sampled
            tr = st.req.stream.trace
            if tr is not None:
                tr.tick("decode")
            if st.t_last:
                # gap since this stream's previous token — the decode
                # stall gauge prefill interference inflates (PR 15)
                self.metrics.record_itl(now - st.t_last)
            st.t_last = now
            st.req.stream._push(tok, now)
            self._grammar_step(slot, st, tok)
            why = self._grammar_why(st, self._retire_why(st, st.req, now))
            if why is not None:
                retired.append((slot, st, why))
        if sampled:
            self.metrics.record_sampled(sampled)
        for slot, st, why in retired:
            self._release_slot(slot, st)
            self._finish_slot(st, why, now)

    def _speculative_round(self, active: List[Tuple[int, _SlotState]]) -> None:
        """One speculative iteration over every decoding slot: k+1 draft
        decode steps (each feeding the previous step's device-resident
        tokens straight back in — the +1 pre-writes the bonus token's
        K/V row in the draft cache so a full acceptance leaves no hole),
        then ONE target verify forward scoring all k candidates, then
        host-side accept/rollback bookkeeping.

        Rollback is free by construction: a rejection just leaves the
        slot's position at the last accepted row, and the rejected
        candidates' K/V rows sit causally masked past it until the next
        round overwrites them — the same recycled-page bit-cleanliness
        the paged cache already guarantees."""
        faults.fire("engine.draft", engine=self)
        k = self.spec_k
        tokens = np.zeros((self.max_slots,), np.int32)
        positions = np.zeros((self.max_slots,), np.int32)
        out_base = np.zeros((self.max_slots,), np.int32)
        for slot, st in active:
            tokens[slot] = st.last_token
            positions[slot] = st.position
            out_base[slot] = st.generated
        # grammar (PR 20): draft step i and verify position i share one
        # mask — the automaton state after the first i draft proposals,
        # walked on a per-round SCRATCH copy of each constrained slot's
        # state (the canonical state only advances on EMITTED tokens,
        # below). The accepted prefix always equals the draft prefix, so
        # verify's residual resample at the first rejection is masked by
        # exactly its true predecessor state; rows past a terminal go
        # through a dead scratch state, whose all-zero bias row is the
        # uniform-shift no-op the emit cap discards anyway.
        gslots = [(slot, st) for slot, st in active
                  if st.req.grammar is not None]
        g_scratch = {slot: st.grammar_state for slot, st in gslots}
        d_tokens = []
        d_dists = []
        bias_list = []
        cur = tokens
        for i in range(k + 1):
            if self._bias is None:
                bias_i = None
            elif gslots:
                bias_i = self._bias.copy()
                for slot, st in gslots:
                    bias_i[slot] = st.req.grammar.bias_row(g_scratch[slot])
            else:
                bias_i = self._bias
            # positions clamp at the lane end: a slot about to retire at
            # max_len keeps fixed shapes (garbage proposals there are
            # rejected or discarded by the room cap below)
            pos_i = np.minimum(positions + i, self.max_len - 1)
            cur, dist, self._dcache = self.kernels.draft(
                self._draft_params, self._dcache, cur, pos_i,
                self._dpage_map, self._temps, self._top_ks, self._top_ps,
                self._keys, out_base + i, bias=bias_i)
            # host round trip on purpose: feeding the committed device
            # output straight back would key a SECOND pjit executable
            # (committed vs uncommitted int32[S]) — compile-once pins
            # exactly one entry per kernel
            cur = np.asarray(cur)
            for slot, st in gslots:
                g_scratch[slot] = st.req.grammar.advance(
                    g_scratch[slot], int(cur[slot]))
            bias_list.append(bias_i)
            if i < k:
                d_tokens.append(cur)
                d_dists.append(dist)
        faults.fire("engine.verify", engine=self)
        bias_v = (None if self._bias is None
                  else np.stack(bias_list, axis=1))  # (S, k+1, V)
        n_dev, out_dev, self._cache = self.kernels.verify(
            self._params, self._cache, tokens, d_tokens, positions,
            self._page_map, self._pool.trash, self._temps, self._top_ks,
            self._top_ps, self._keys, out_base, d_dists, bias=bias_v)
        n_acc = np.asarray(n_dev)
        outs = np.asarray(out_dev)
        now = time.monotonic()
        self.metrics.record_decode_step(len(active), self.max_slots)
        accepted_total = 0
        pushed_total = 0
        sampled = 0
        retired = []
        for slot, st in active:
            room = min(st.req.max_new_tokens - st.generated,
                       self.max_len - st.position)
            emit = min(int(n_acc[slot]) + 1, room)
            pushed = 0
            for j in range(emit):
                tok = int(outs[slot, j])
                st.req.stream._push(tok, now)
                pushed += 1
                if self.eos_id is not None and tok == self.eos_id:
                    break
                # canonical advance per EMITTED token (the scratch walk
                # above covered proposals); a stuck verdict stops the
                # emission — nothing unparseable streams past it
                self._grammar_step(slot, st, tok)
                if st.grammar_error is not None:
                    break
            accepted_total += min(int(n_acc[slot]), pushed)
            pushed_total += pushed
            tr = st.req.stream.trace
            if tr is not None:
                tr.tick("verify_round")
            if pushed and st.t_last:
                # one amortized sample per emitted token: the round's
                # wall gap spread over everything it pushed
                self.metrics.record_itl((now - st.t_last) / pushed, pushed)
            st.t_last = now
            st.last_token = int(outs[slot, pushed - 1])
            st.position += pushed
            st.generated += pushed
            sampled += pushed if st.req.sampled else 0
            why = self._grammar_why(st, self._retire_why(st, st.req, now))
            if why is not None:
                retired.append((slot, st, why))
        self.metrics.record_verify_step(k * len(active), accepted_total,
                                        pushed_total - len(active))
        if sampled:
            self.metrics.record_sampled(sampled)
        for slot, st, why in retired:
            self._release_slot(slot, st)
            self._finish_slot(st, why, now)

    def _retire_why(self, st: Optional[_SlotState], req: _GenRequest,
                    now: float) -> Optional[str]:
        """Retirement disposition, or None to keep decoding. Order:
        explicit cancel wins, a normally-completed sequence beats a
        deadline that expired on the same step."""
        if req.stream.cancelled:
            return "cancelled"
        if st is not None:
            if self.eos_id is not None and st.last_token == self.eos_id:
                return "done"
            if st.generated >= req.max_new_tokens:
                return "done"
            if st.position >= self.max_len:
                return "done"
        if req.deadline is not None and now > req.deadline:
            return "expired"
        return None

    # ---------------------------------------- grammar (PR 20) hooks ----

    def _grammar_arm(self, slot: int, st: _SlotState) -> None:
        """Arm a constrained slot's mask row for its FIRST sampled token
        (the final prefill chunk): the automaton begins at its start
        state, and the start state's bias row must be live in
        ``self._bias`` BEFORE the prefill kernel samples."""
        g = st.req.grammar
        if g is None:
            return
        st.grammar_state = g.start_state
        self._bias[slot] = g.bias_row(st.grammar_state)
        self.metrics.record_masked_frac(g.masked_frac(st.grammar_state))

    def _grammar_step(self, slot: int, st: _SlotState, tok: int) -> None:
        """Advance a constrained slot's automaton on one emitted token
        and re-arm ``self._bias[slot]`` for the NEXT step. A verdict
        (stuck terminal, or the defensive illegal-token case) is
        recorded on ``st.grammar_error`` — surfaced by
        :meth:`_grammar_why` at the retirement decision, never raised
        here (this runs inside the scheduler loop / the async fold-in,
        where an exception would take down every stream)."""
        g = st.req.grammar
        if g is None or st.grammar_error is not None:
            return
        if self.eos_id is not None and tok == self.eos_id:
            # the EOS column is legal only in ACCEPTING states, so
            # sampling it IS the parse — nothing left to re-arm
            return
        state = g.advance(st.grammar_state, tok)
        st.grammar_state = state
        if state < 0:
            # defensive: the mask makes illegal tokens unsampleable
            # (exp(-1e9) underflows to exact f32 zero), so a dead state
            # here means the mask was not applied — fail the stream
            # rather than emit unparseable text
            st.grammar_error = GrammarViolation(
                f"token {tok} is not legal from the previous state",
                state=state, tokens_out=st.generated, grammar_key=g.key)
            return
        if not g.has_continuation(state) and not g.is_accepting(state):
            st.grammar_error = GrammarViolation(
                "stuck state: no legal continuation and no legal EOS "
                "over this vocabulary", state=state,
                tokens_out=st.generated, grammar_key=g.key)
            return
        self._bias[slot] = g.bias_row(state)
        self.metrics.record_masked_frac(g.masked_frac(state))

    def _grammar_why(self, st: _SlotState,
                     why: Optional[str]) -> Optional[str]:
        """Fold the grammar verdict into the retirement disposition:

        - a recorded violation (stuck state, defensive illegal token)
          always fails the stream;
        - a budget/length ``done`` in a NON-accepting state is a
          violation — the emitted text does not parse (an EOS-sampled
          ``done`` always lands accepting: EOS is only legal there);
        - with no EOS id configured, an accepting state with nothing
          legal left retires ``done`` — the parse is complete and the
          next mask row would be the all-illegal no-op.
        Cancel/expired dispositions pass through: their own errors win.
        """
        g = st.req.grammar
        if g is None:
            return why
        if st.grammar_error is not None:
            return "grammar"
        if why == "done" and not g.is_accepting(st.grammar_state):
            st.grammar_error = GrammarViolation(
                "token budget exhausted before the grammar could "
                "complete", state=st.grammar_state,
                tokens_out=st.generated, grammar_key=g.key)
            return "grammar"
        if (why is None and self.eos_id is None
                and g.is_accepting(st.grammar_state)
                and not g.has_continuation(st.grammar_state)):
            return "done"
        return why

    def _finish_slot(self, st: _SlotState, why: str, now: float) -> None:
        if why == "grammar":
            err = st.grammar_error
            self.metrics.record_failed()
            st.req.stream._finish(err, now)
            tr = st.req.stream.trace
            if tr is not None:
                tr.finish(outcome="grammar_violation", tokens=st.generated)
            return
        self._finish_request(st.req, why, now,
                             queue_wait=st.t_admit - st.req.stream.t_submit,
                             generated=st.generated)

    def _finish_request(self, req: _GenRequest, why: str, now: float, *,
                        queue_wait: Optional[float],
                        generated: int = 0) -> None:
        stream = req.stream
        dur = now - stream.t_submit
        if why == "expired":
            self.metrics.record_expired()
            stream._finish(DeadlineExceeded(
                dur, req.deadline - stream.t_submit), now)
        elif why == "cancelled":
            stream._finish(StreamCancelled(
                "generation stream cancelled by its consumer"), now)
        else:
            self.metrics.record_served(dur, queue_wait or 0.0)
            self.metrics.record_stream(generated, dur)
            stream._finish(None, now)
        tr = stream.trace
        if tr is not None:
            tr.finish(outcome=why, tokens=generated)

    # -------------------------------------------------------- lifecycle ----

    def warmup(self) -> None:
        """Compile the decode step and every prompt-bucket prefill BEFORE
        traffic arrives. Must run before the first submit (it touches the
        cache from the caller's thread); the garbage keys it writes are
        causally invisible and overwritten by real admissions."""
        core = self._core
        with core.cond:
            if core.pending or core.active:
                raise RuntimeError("warmup() must run before traffic")
        zeros = np.zeros((self.max_slots,), np.int32)
        if self.paged and self.speculative:
            # every write routes to the trash page (the map rows are
            # parked there). One call per kernel shape: the draft step
            # and the verify step each have exactly ONE shape however
            # the acceptance lengths vary at runtime.
            trash_row = np.full((self._pool.pages_per_slot,),
                                self._pool.trash, np.int32)
            k = self.spec_k
            # grammar bias rows warm as the same argument KIND traffic
            # passes (host arrays when the model has a vocab, else
            # consistently None) — a kind flip would key a second pjit
            # executable per kernel and break the compile-once pins
            wb = self._bias
            wb1 = None if wb is None else wb[:1]
            wbv = (None if wb is None else
                   np.zeros((self.max_slots, k + 1, wb.shape[1]),
                            np.float32))
            _, wd, self._dcache = self.kernels.draft(
                self._draft_params, self._dcache, zeros, zeros,
                self._dpage_map, self._temps, self._top_ks, self._top_ps,
                self._keys, zeros, bias=wb)
            # verify must see the RUNTIME argument kinds: draft tokens
            # arrive as host arrays (the round's committed-output
            # normalization) but dists stay device-resident — a numpy
            # dist here would warm a second executable for the same
            # trace (pjit keys on committed-ness, not just shape)
            zt = [np.zeros((self.max_slots,), np.int32)] * k
            zd = [wd] * k
            _, _, self._cache = self.kernels.verify(
                self._params, self._cache, zeros, zt, zeros,
                self._page_map, self._pool.trash, self._temps,
                self._top_ks, self._top_ps, self._keys, zeros, zd,
                bias=wbv)
            if self.max_prompt_len > self.prefill_chunk:
                chunk_pad = np.full((self.prefill_chunk,), self.pad_id,
                                    np.int32)
                self._cache = self.kernels.chunk(
                    self._params, self._cache, trash_row, chunk_pad, 0,
                    self.prefill_chunk, self._pool.trash)
                self._dcache = self.kernels.draft_write(
                    self._draft_params, self._dcache, trash_row,
                    chunk_pad, 0, self.prefill_chunk, self._pool.trash)
            for bucket in self.prompt_buckets:
                pad = np.full((bucket,), self.pad_id, np.int32)
                _, self._cache = self.kernels.prefill(
                    self._params, self._cache, trash_row, pad, 0, bucket,
                    self._pool.trash, bias=wb1)
                self._dcache = self.kernels.draft_write(
                    self._draft_params, self._dcache, trash_row, pad, 0,
                    bucket, self._pool.trash)
            jax.block_until_ready(self._dcache)
        elif self.paged:
            # every write below routes to the trash page (the map rows
            # are parked there), so warmup garbage can never surface.
            # Role-split engines warm ONLY their role's kernels: the
            # compile-once contract is per role (a prefill engine never
            # traces decode and vice versa — trace-counter-pinned).
            trash_row = np.full((self._pool.pages_per_slot,),
                                self._pool.trash, np.int32)
            # grammar bias rows warm as the same argument KIND traffic
            # passes (arrays when the model has a vocab, else None) —
            # a kind flip would key a second pjit executable
            wb = self._bias
            wb1 = None if wb is None else wb[:1]
            if self.role != "prefill":
                _, self._keys, self._cache = self.kernels.decode(
                    self._params, self._cache, zeros, zeros,
                    self._page_map, self._temps, self._top_ks,
                    self._top_ps, self._keys, bias=wb)
                self._keys = np.asarray(self._keys)
            if self.role != "decode":
                if self.max_prompt_len > self.prefill_chunk:
                    self._cache = self.kernels.chunk(
                        self._params, self._cache, trash_row,
                        np.full((self.prefill_chunk,), self.pad_id,
                                np.int32),
                        0, self.prefill_chunk, self._pool.trash)
                for bucket in self.prompt_buckets:
                    _, _, self._cache = self.kernels.prefill(
                        self._params, self._cache, trash_row,
                        np.full((bucket,), self.pad_id, np.int32), 0,
                        bucket, self._pool.trash, bias=wb1)
            if self.role == "prefill":
                # the export gather (pure read off the trash rows)
                jax.block_until_ready(
                    self._mover.gather(self._cache, trash_row))
            elif self.role == "decode":
                # the adopt scatter: a zero block routed to the trash
                # page, placed exactly as runtime blocks are (the
                # device_put the adopt path applies) so ONE executable
                # serves warmup and traffic
                block = jax.tree_util.tree_map(
                    lambda leaf: np.zeros(
                        (self._pool.pages_per_slot,) + leaf.shape[1:],
                        leaf.dtype), self._cache)
                if self._cache_sharding is not None:
                    block = jax.device_put(
                        block,
                        _cache_sharding_tree(block, self._cache_sharding))
                else:
                    block = jax.device_put(block)
                self._cache = self._mover.scatter(self._cache, block,
                                                  trash_row)
            if self._host is not None:
                # host tier (PR 18): the offload/swap gather and the
                # restore scatter warm exactly like the role-split
                # engines' — ONE executable each, runtime calls place
                # their blocks identically (compile-once is test-pinned)
                if self.role != "prefill":
                    jax.block_until_ready(
                        self._mover.gather(self._cache, trash_row))
                block = jax.tree_util.tree_map(
                    lambda leaf: np.zeros(
                        (self._pool.pages_per_slot,) + leaf.shape[1:],
                        leaf.dtype), self._cache)
                if self._cache_sharding is not None:
                    block = jax.device_put(
                        block,
                        _cache_sharding_tree(block, self._cache_sharding))
                else:
                    block = jax.device_put(block)
                self._cache = self._mover.scatter(self._cache, block,
                                                  trash_row)
            # warmup consumed one split per slot key: re-arm the zeros so
            # the first real admission starts from its request seed (it
            # overwrites the row anyway; this keeps the invariant obvious)
            self._keys = np.zeros((self.max_slots, 2), np.uint32)
        else:
            _, self._cache = self.kernels.decode(
                self._params, self._cache, zeros, zeros)
            for bucket in self.prompt_buckets:
                _, self._cache = self.kernels.prefill(
                    self._params, self._cache, 0,
                    np.full((bucket,), self.pad_id, np.int32), bucket)
        jax.block_until_ready(self._cache)

    def reload(self, params, state: Any = None) -> None:
        """Swap decode params atomically between steps: a decode/prefill
        call reads ``self._params`` exactly once, so every step sees one
        consistent tree — never torn halves. Signature-checked: matching
        shapes/dtypes mean the jitted step is NOT recompiled. ``state``
        is accepted for :func:`watch_checkpoints` symmetry but must be
        empty — incremental decode is stateless."""
        from bigdl_tpu.serving.service import require_matching_signature

        if state:
            raise ValueError(
                "GenerationEngine.reload takes params only: incremental "
                "decode runs stateless (no BN-style buffers)")
        if self._quantize_params is not None:
            # a quantized engine reloads from FLOAT checkpoints: the
            # transform is a pure function of shapes, so the quantized
            # tree's signature matches the serving one and the jitted
            # step is NOT recompiled (pjit-cache test-enforced)
            params = self._quantize_params(params)
        require_matching_signature("params", self._params, params)
        # device_put once: host arrays would re-transfer every step and
        # miss the jit cache (uncommitted args key a different executable).
        # A sharded engine re-places with the ORIGINAL shardings for the
        # same reason: differently-placed params key a fresh executable.
        if self._param_shardings is not None:
            self._params = jax.device_put(params, self._param_shardings)
        else:
            self._params = jax.device_put(params)
        if self._prefix is not None:
            # cached pages are keyed by (model version, prefix): pages
            # the OLD params wrote must never serve the new ones. The
            # pool is loop-thread-only, so flag the flush and let the
            # loop clear the index at its next iteration (the same
            # between-steps granularity the param swap itself has).
            self._prefix_flush = True
        self.metrics.record_reload()

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """Stop admitting; with ``drain`` (default) the loop keeps
        stepping until every pending and in-flight stream finishes,
        otherwise they fail with ``RuntimeError``."""
        core = self._core
        with core.cond:
            core.closed = True
            core.drain = drain
            core.cond.notify_all()
        self._thread.join(timeout)
        if self._profile_state == 1:
            # a profile bracket wider than the traffic that ran: close
            # it rather than leak an open device trace
            try:
                jax.profiler.stop_trace()
            except Exception:
                log.exception("stopping engine profiler trace failed")
            self._profile_state = 2
        if self._watchdog is not None and not self._thread.is_alive():
            self._watchdog.close()
        if not self._thread.is_alive():
            # the loop has exited: a request that raced the close flag in
            # must fail rather than strand its consumer. NOT safe while
            # the loop lives (a timed-out drain join) — it would fail
            # streams the loop is still legitimately serving and
            # double-free their slots mid-step.
            _fail_streams(core, RuntimeError(
                "generation engine closed before request ran"), self)

    def __enter__(self) -> "GenerationEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # --------------------------------------------------------- queries ----

    @property
    def failed(self) -> Optional[BaseException]:
        """The error that stopped the engine loop (``None`` while
        healthy). A fleet heal pass probes this instead of waiting for
        the next placement attempt to trip over the dead loop."""
        with self._core.cond:
            return self._failed

    @property
    def active_slots(self) -> int:
        with self._core.cond:
            return len(self._core.active)

    @property
    def pending_requests(self) -> int:
        with self._core.cond:
            return len(self._core.pending)

    @property
    def free_slots(self) -> List[int]:
        with self._core.cond:
            return sorted(self._core.free)

    @property
    def decode_compilations(self) -> int:
        return self.kernels.decode_traces

    @property
    def prefill_compilations(self) -> int:
        return self.kernels.prefill_traces

    @property
    def chunk_compilations(self) -> int:
        return getattr(self.kernels, "chunk_traces", 0)

    @property
    def draft_compilations(self) -> int:
        return getattr(self.kernels, "draft_traces", 0)

    @property
    def verify_compilations(self) -> int:
        return getattr(self.kernels, "verify_traces", 0)

    @property
    def handoff_gather_compilations(self) -> int:
        return self._mover.gather_traces if self._mover is not None else 0

    @property
    def handoff_scatter_compilations(self) -> int:
        return self._mover.scatter_traces if self._mover is not None else 0

    @property
    def pages_in_use(self) -> int:
        return self._pool.in_use if self.paged else 0

    @property
    def free_pages(self) -> int:
        return self._pool.free_pages if self.paged else 0

    @property
    def shared_pages(self) -> int:
        """Pages the prefix index(es) currently hold references for
        (0 without prefix caching) — the chaos drain gate's gauge."""
        if self._prefix is None:
            return 0
        return self._prefix.pages + (self._dprefix.pages
                                     if self._dprefix is not None else 0)

    @property
    def host_pages_in_use(self) -> int:
        """Pages resident in the host tier — offloaded prefix entries
        plus parked-stream bookings (0 without ``host_pages``); the
        second gauge the two-tier drain gate asserts reaches zero."""
        return self._host.pages if self._host is not None else 0

    @property
    def host_store(self) -> Optional[HostPageStore]:
        """The host tier itself (``None`` without ``host_pages``) —
        snapshot()-able like the PagePool, for registry scrapes."""
        return self._host


def _static_grammar_step(g, state, tok, eos_id, n_out):
    """``static_generate``'s per-token automaton advance — the engine's
    ``_grammar_step`` semantics, raising :class:`GrammarViolation`
    instead of failing a stream (the static baseline has no stream)."""
    if g is None or (eos_id is not None and tok == eos_id):
        return state
    state = g.advance(state, tok)
    if state < 0:
        raise GrammarViolation(
            f"token {tok} is not legal from the previous state",
            state=state, tokens_out=n_out, grammar_key=g.key)
    if not g.has_continuation(state) and not g.is_accepting(state):
        raise GrammarViolation(
            "stuck state: no legal continuation and no legal EOS over "
            "this vocabulary", state=state, tokens_out=n_out,
            grammar_key=g.key)
    return state


def _static_grammar_finish(g, state, tok, eos_id, n_out):
    """Completion check at a static stream's retirement: a budget /
    length ``done`` must land in an accepting state, or the emitted
    text does not parse (an EOS-terminated stream always does — the
    EOS column is only legal in accepting states)."""
    if g is None or (eos_id is not None and tok == eos_id):
        return
    if not g.is_accepting(state):
        raise GrammarViolation(
            "token budget exhausted before the grammar could complete",
            state=state, tokens_out=n_out, grammar_key=g.key)


def static_generate(model, params, requests, *, max_slots: int,
                    max_len: int, eos_id: Optional[int] = None,
                    pad_id: int = 0, cache_dtype=jnp.float32,
                    kernels=None,
                    prompt_buckets: Optional[Sequence[int]] = None,
                    page_size: int = 16, num_pages: Optional[int] = None,
                    prefill_chunk: Optional[int] = None, seed: int = 0,
                    sampling: Optional[Sequence[dict]] = None,
                    quantize: Optional[str] = None,
                    speculate: Optional[tuple] = None):
    """Run-to-completion static batching BASELINE over the same jitted
    kernels the engine uses: admit ``max_slots`` requests, decode until
    EVERY one finishes (the longest sequence holds the whole batch
    hostage), only then admit the next group. ``requests`` is a sequence
    of ``(prompt, max_new_tokens)``; returns ``(token lists, decode
    steps executed)``. This is the comparison the bench/CI smoke gate
    runs — continuous batching must beat it on mixed lengths because it
    retires short sequences mid-flight instead of idling their slots.

    With :class:`PagedDecodeKernels` (the default for paged-capable
    models) the baseline runs over the SAME paged + sampling kernels as
    the engine — apples to apples stays apples. ``sampling`` is an
    optional per-request list of dicts (``temperature`` / ``top_k`` /
    ``top_p`` / ``seed``); seeds derive exactly like the engine's, so a
    sampled run produces IDENTICAL streams under either scheduler.

    ``quantize="int8"`` / ``cache_dtype="int8"`` mirror the engine knobs
    (the transform is deterministic, so an int8 engine and an int8
    static run still emit identical tokens — the bench mismatch gate
    covers the quantized tier too).

    ``speculate=(draft_model, draft_params, k)`` mirrors the engine's
    draft-verified mode over :class:`SpeculativeKernels`: the same
    position-keyed draws make a speculative static run emit the
    ENGINE's exact streams (greedy and sampled), which is the
    schedule-invariance gate the speculative bench leans on."""
    draft_model = draft_params = None
    spec_k = 0
    if speculate is not None:
        draft_model, draft_params, spec_k = speculate
        spec_k = int(spec_k)
    if quantize == "int8":
        from bigdl_tpu.nn.quantized import quantize_for_serving

        params = quantize_for_serving(params)
        if draft_params is not None:
            draft_params = quantize_for_serving(draft_params)
    elif quantize is not None:
        raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
    if np.dtype(cache_dtype) == np.int8 and not (
            hasattr(kernels, "chunk") if kernels is not None
            else page_size and hasattr(model, "decode_step_paged")):
        # same guard the engine applies: the dense slot-lane path has no
        # scale pools, so an int8 cache there would truncate K/V to
        # zeros and decode garbage without a single error
        raise ValueError(
            "cache_dtype='int8' needs the paged kernels (int8 KV lives in "
            "the page pools with per-token scale pools)")
    if kernels is None:
        if speculate is not None:
            kernels = SpeculativeKernels(model, draft_model)
        else:
            kernels = (PagedDecodeKernels(model)
                       if page_size and hasattr(model, "decode_step_paged")
                       else DecodeKernels(model))
    requests = [([int(t) for t in p], int(m)) for p, m in requests]
    if hasattr(kernels, "verify"):  # speculative set (or a wrapper)
        if speculate is None:
            raise ValueError(
                "SpeculativeKernels need speculate=(draft_model, "
                "draft_params, k)")
        return _static_generate_spec(
            model, params, requests, kernels, draft_params, spec_k,
            max_slots=max_slots, max_len=max_len, eos_id=eos_id,
            pad_id=pad_id, cache_dtype=cache_dtype,
            prompt_buckets=prompt_buckets, page_size=page_size,
            num_pages=num_pages, prefill_chunk=prefill_chunk, seed=seed,
            sampling=sampling, draft_model=draft_model)
    if speculate is not None:
        raise ValueError(
            "speculate= needs SpeculativeKernels (pass kernels=None to "
            "build them)")
    if hasattr(kernels, "chunk"):  # paged triple (or a wrapper around one)
        return _static_generate_paged(
            model, params, requests, kernels, max_slots=max_slots,
            max_len=max_len, eos_id=eos_id, pad_id=pad_id,
            cache_dtype=cache_dtype, prompt_buckets=prompt_buckets,
            page_size=page_size, num_pages=num_pages,
            prefill_chunk=prefill_chunk, seed=seed, sampling=sampling)
    if sampling is not None:
        raise ValueError("sampling needs PagedDecodeKernels")
    buckets = list(prompt_buckets
                   or bucket_sizes_for(max(len(p) for p, _ in requests)))
    cache = model.init_cache(max_slots, max_len, cache_dtype)
    outputs: List[Optional[List[int]]] = [None] * len(requests)
    total_steps = 0
    for base in range(0, len(requests), max_slots):
        group = requests[base:base + max_slots]
        states = []
        for slot, (prompt, mnt) in enumerate(group):
            n = len(prompt)
            bucket = next(b for b in buckets if b >= n)
            padded = np.full((bucket,), pad_id, np.int32)
            padded[:n] = prompt
            tok_dev, cache = kernels.prefill(params, cache, slot, padded, n)
            tok = int(np.asarray(tok_dev))
            target = min(mnt, max_len - n)
            states.append({
                "tokens": [tok], "last": tok, "pos": n,
                "target": target,
                "done": (eos_id is not None and tok == eos_id) or target <= 1,
            })
        while not all(s["done"] for s in states):
            tokens = np.zeros((max_slots,), np.int32)
            positions = np.zeros((max_slots,), np.int32)
            for slot, s in enumerate(states):
                tokens[slot] = s["last"]
                positions[slot] = s["pos"]
            toks_dev, cache = kernels.decode(params, cache, tokens, positions)
            toks = np.asarray(toks_dev)
            total_steps += 1
            for slot, s in enumerate(states):
                if s["done"]:
                    continue
                tok = int(toks[slot])
                s["tokens"].append(tok)
                s["last"] = tok
                s["pos"] += 1
                if ((eos_id is not None and tok == eos_id)
                        or len(s["tokens"]) >= s["target"]
                        or s["pos"] >= max_len):
                    s["done"] = True
        for i, s in enumerate(states):
            outputs[base + i] = s["tokens"]
    return outputs, total_steps


def _static_generate_spec(model, params, requests, kernels, draft_params,
                          spec_k, *, max_slots, max_len, eos_id, pad_id,
                          cache_dtype, prompt_buckets, page_size,
                          num_pages, prefill_chunk, seed, sampling,
                          draft_model):
    """Speculative body of :func:`static_generate`: group-at-a-time
    run-to-completion over the SAME draft/verify kernels the engine
    runs. Draws are keyed by (request, output position), so the emitted
    streams are identical to the engine's under any grouping — the
    speculative analogue of the paged body's schedule invariance.
    Returns ``(token lists, verify rounds executed)``."""
    from bigdl_tpu.core.rng import request_seed as _request_seed
    from bigdl_tpu.core.rng import threefry_key_data as _tkd

    k = int(spec_k)
    chunk = int(prefill_chunk or min(64, max_len - 1))
    longest = max(len(p) for p, _ in requests)
    buckets = list(prompt_buckets or bucket_sizes_for(min(longest, chunk)))
    num_pages = int(num_pages
                    or max_slots * 2 * pages_per_lane(max_len, page_size))
    pool = PagePool(num_pages, page_size, max_len)
    cache = model.init_paged_cache(num_pages + 1, page_size, cache_dtype)
    dcache = draft_model.init_paged_cache(num_pages + 1, page_size,
                                          cache_dtype)
    ppn = pool.pages_per_slot
    page_map = np.full((max_slots, ppn), pool.trash, np.int32)
    dpage_map = np.full((max_slots, ppn), pool.trash, np.int32)
    temps = np.zeros((max_slots,), np.float32)
    top_ks = np.zeros((max_slots,), np.int32)
    top_ps = np.ones((max_slots,), np.float32)
    keys = np.zeros((max_slots, 2), np.uint32)
    # grammar (PR 20): same bias-kind rule as the engine (arrays iff the
    # model exposes a vocab — one executable per kernel, shared or not)
    vocab = getattr(model, "vocab_size", None)
    bias = (np.zeros((max_slots, int(vocab)), np.float32)
            if vocab else None)

    outputs: List[Optional[List[int]]] = [None] * len(requests)
    total_rounds = 0
    for base in range(0, len(requests), max_slots):
        group = requests[base:base + max_slots]
        states = []
        for slot, (prompt, mnt) in enumerate(group):
            n = len(prompt)
            target = min(mnt, max_len - n)
            spec = dict(sampling[base + slot] or {}) if sampling else {}
            req_seed = spec.get("seed")
            if req_seed is None:
                req_seed = _request_seed(
                    seed, np.asarray(prompt, np.int32).tobytes(), n)
            temps[slot] = float(spec.get("temperature", 0.0))
            top_ks[slot] = int(spec.get("top_k", 0))
            top_ps[slot] = float(spec.get("top_p", 1.0))
            keys[slot] = _tkd(req_seed)
            g = spec.get("grammar")
            gstate = None
            if g is not None:
                if bias is None:
                    raise ValueError(
                        "sampling['grammar'] needs a model exposing "
                        "vocab_size")
                gstate = g.start_state
                bias[slot] = g.bias_row(gstate)
            need = pool.pages_for(min(n + target - 1, max_len))
            if not pool.can_reserve(2 * need):
                raise ValueError(
                    f"num_pages={num_pages} cannot hold a speculative "
                    f"static group (needs {2 * need} more pages) — grow "
                    f"the pool or shrink max_slots")
            pages = pool.alloc(need, owner="target")
            dpages = pool.alloc(need, owner="draft")
            page_map[slot, :] = pool.trash
            page_map[slot, :len(pages)] = pages
            dpage_map[slot, :] = pool.trash
            dpage_map[slot, :len(dpages)] = dpages
            start = 0
            while n - start > chunk:
                piece = np.asarray(prompt[start:start + chunk], np.int32)
                cache = kernels.chunk(params, cache, page_map[slot],
                                      piece, start, chunk, pool.trash)
                dcache = kernels.draft_write(
                    draft_params, dcache, dpage_map[slot], piece, start,
                    chunk, pool.trash)
                start += chunk
            remaining = n - start
            bucket = next(b for b in buckets if b >= remaining)
            padded = np.full((bucket,), pad_id, np.int32)
            padded[:remaining] = prompt[start:]
            tok_dev, cache = kernels.prefill(
                params, cache, page_map[slot], padded, start, remaining,
                pool.trash, temps[slot], top_ks[slot], top_ps[slot],
                keys[slot],
                bias=None if bias is None else bias[slot:slot + 1].copy())
            dcache = kernels.draft_write(
                draft_params, dcache, dpage_map[slot], padded, start,
                remaining, pool.trash)
            tok = int(np.asarray(tok_dev))
            gstate = _static_grammar_step(g, gstate, tok, eos_id, 1)
            if g is not None:
                bias[slot] = g.bias_row(gstate)
            done = (eos_id is not None and tok == eos_id) or target <= 1
            if done:
                _static_grammar_finish(g, gstate, tok, eos_id, 1)
            states.append({
                "tokens": [tok], "last": tok, "pos": n,
                "target": target, "pages": pages, "dpages": dpages,
                "grammar": g, "gstate": gstate,
                "done": done,
            })
        while not all(s["done"] for s in states):
            tokens = np.zeros((max_slots,), np.int32)
            positions = np.zeros((max_slots,), np.int32)
            out_base = np.zeros((max_slots,), np.int32)
            for slot, s in enumerate(states):
                tokens[slot] = s["last"]
                positions[slot] = s["pos"]
                out_base[slot] = len(s["tokens"])
            # draft step i and verify position i share one mask, walked
            # on a per-round scratch copy of each live grammar state —
            # the engine's _speculative_round discipline exactly
            glive = [(slot, s) for slot, s in enumerate(states)
                     if not s["done"] and s["grammar"] is not None]
            g_scratch = {slot: s["gstate"] for slot, s in glive}
            d_tokens = []
            d_dists = []
            bias_list = []
            cur = tokens
            for i in range(k + 1):
                if bias is None:
                    bias_i = None
                elif glive:
                    bias_i = bias.copy()
                    for slot, s in glive:
                        bias_i[slot] = s["grammar"].bias_row(
                            g_scratch[slot])
                else:
                    bias_i = bias
                pos_i = np.minimum(positions + i, max_len - 1)
                cur, dist, dcache = kernels.draft(
                    draft_params, dcache, cur, pos_i, dpage_map, temps,
                    top_ks, top_ps, keys, out_base + i, bias=bias_i)
                cur = np.asarray(cur)   # one executable: see engine loop
                for slot, s in glive:
                    g_scratch[slot] = s["grammar"].advance(
                        g_scratch[slot], int(cur[slot]))
                bias_list.append(bias_i)
                if i < k:
                    d_tokens.append(cur)
                    d_dists.append(dist)
            n_dev, out_dev, cache = kernels.verify(
                params, cache, tokens, d_tokens, positions, page_map,
                pool.trash, temps, top_ks, top_ps, keys, out_base,
                d_dists,
                bias=None if bias is None else np.stack(bias_list, axis=1))
            n_acc = np.asarray(n_dev)
            outs = np.asarray(out_dev)
            total_rounds += 1
            for slot, s in enumerate(states):
                if s["done"]:
                    continue
                room = min(s["target"] - len(s["tokens"]),
                           max_len - s["pos"])
                emit = min(int(n_acc[slot]) + 1, room)
                g = s["grammar"]
                pushed = 0
                for j in range(emit):
                    tok = int(outs[slot, j])
                    s["tokens"].append(tok)
                    pushed += 1
                    if eos_id is not None and tok == eos_id:
                        break
                    s["gstate"] = _static_grammar_step(
                        g, s["gstate"], tok, eos_id, len(s["tokens"]))
                if g is not None:
                    bias[slot] = g.bias_row(s["gstate"])
                s["last"] = int(outs[slot, pushed - 1])
                s["pos"] += pushed
                if ((eos_id is not None and s["last"] == eos_id)
                        or len(s["tokens"]) >= s["target"]
                        or s["pos"] >= max_len):
                    s["done"] = True
                    _static_grammar_finish(g, s["gstate"], s["last"],
                                           eos_id, len(s["tokens"]))
        for i, s in enumerate(states):
            outputs[base + i] = s["tokens"]
            pool.release(s["pages"])
            pool.release(s["dpages"])
        page_map[:] = pool.trash
        dpage_map[:] = pool.trash
        temps[:] = 0.0
        top_ks[:] = 0
        top_ps[:] = 1.0
        keys[:] = 0
        if bias is not None:
            bias[:] = 0.0
    return outputs, total_rounds


def _static_generate_paged(model, params, requests, kernels, *, max_slots,
                           max_len, eos_id, pad_id, cache_dtype,
                           prompt_buckets, page_size, num_pages,
                           prefill_chunk, seed, sampling):
    """Paged body of :func:`static_generate`: same group-at-a-time
    run-to-completion schedule, over the paged + sampling kernels. Each
    group reserves its pages up front and releases them when the whole
    group finishes — which is exactly the capacity pathology the paged
    ENGINE fixes by releasing per sequence."""
    chunk = int(prefill_chunk or min(64, max_len - 1))
    longest = max(len(p) for p, _ in requests)
    buckets = list(prompt_buckets or bucket_sizes_for(min(longest, chunk)))
    num_pages = int(num_pages
                    or max_slots * pages_per_lane(max_len, page_size))
    pool = PagePool(num_pages, page_size, max_len)
    cache = model.init_paged_cache(num_pages + 1, page_size, cache_dtype)
    page_map = np.full((max_slots, pool.pages_per_slot), pool.trash,
                       np.int32)
    temps = np.zeros((max_slots,), np.float32)
    top_ks = np.zeros((max_slots,), np.int32)
    top_ps = np.ones((max_slots,), np.float32)
    keys = np.zeros((max_slots, 2), np.uint32)
    # grammar (PR 20): same bias-kind rule as the engine — arrays iff
    # the model exposes a vocab, so a kernels set shared with an engine
    # keeps its one executable per kernel
    vocab = getattr(model, "vocab_size", None)
    bias = (np.zeros((max_slots, int(vocab)), np.float32)
            if vocab else None)

    outputs: List[Optional[List[int]]] = [None] * len(requests)
    total_steps = 0
    for base in range(0, len(requests), max_slots):
        group = requests[base:base + max_slots]
        states = []
        for slot, (prompt, mnt) in enumerate(group):
            n = len(prompt)
            target = min(mnt, max_len - n)
            spec = dict(sampling[base + slot] or {}) if sampling else {}
            req_seed = spec.get("seed")
            if req_seed is None:
                req_seed = request_seed(
                    seed, np.asarray(prompt, np.int32).tobytes(), n)
            temps[slot] = float(spec.get("temperature", 0.0))
            top_ks[slot] = int(spec.get("top_k", 0))
            top_ps[slot] = float(spec.get("top_p", 1.0))
            keys[slot] = threefry_key_data(req_seed)
            g = spec.get("grammar")
            gstate = None
            if g is not None:
                if bias is None:
                    raise ValueError(
                        "sampling['grammar'] needs a model exposing "
                        "vocab_size")
                gstate = g.start_state
                bias[slot] = g.bias_row(gstate)
            need = pool.pages_for(min(n + target - 1, max_len))
            if not pool.can_reserve(need):
                raise ValueError(
                    f"num_pages={num_pages} cannot hold a static group "
                    f"(needs {need} more pages) — grow the pool or "
                    f"shrink max_slots")
            pages = pool.alloc(need)
            page_map[slot, :] = pool.trash
            page_map[slot, :len(pages)] = pages
            start = 0
            while n - start > chunk:
                cache = kernels.chunk(
                    params, cache, page_map[slot],
                    np.asarray(prompt[start:start + chunk], np.int32),
                    start, chunk, pool.trash)
                start += chunk
            remaining = n - start
            bucket = next(b for b in buckets if b >= remaining)
            padded = np.full((bucket,), pad_id, np.int32)
            padded[:remaining] = prompt[start:]
            tok_dev, key_dev, cache = kernels.prefill(
                params, cache, page_map[slot], padded, start, remaining,
                pool.trash, temps[slot], top_ks[slot], top_ps[slot],
                keys[slot],
                bias=None if bias is None else bias[slot:slot + 1].copy())
            tok = int(np.asarray(tok_dev))
            keys[slot] = np.asarray(key_dev)[0]
            gstate = _static_grammar_step(g, gstate, tok, eos_id, 1)
            if g is not None:
                bias[slot] = g.bias_row(gstate)
            done = (eos_id is not None and tok == eos_id) or target <= 1
            if done:
                _static_grammar_finish(g, gstate, tok, eos_id, 1)
            if (not done and g is not None and eos_id is None
                    and not g.has_continuation(gstate)):
                done = True  # parse complete, nothing legal remains
            states.append({
                "tokens": [tok], "last": tok, "pos": n,
                "target": target, "pages": pages,
                "grammar": g, "gstate": gstate,
                "done": done,
            })
        while not all(s["done"] for s in states):
            tokens = np.zeros((max_slots,), np.int32)
            positions = np.zeros((max_slots,), np.int32)
            for slot, s in enumerate(states):
                tokens[slot] = s["last"]
                positions[slot] = s["pos"]
            toks_dev, keys_dev, cache = kernels.decode(
                params, cache, tokens, positions, page_map, temps, top_ks,
                top_ps, keys, bias=bias)
            toks = np.asarray(toks_dev)
            keys = np.array(keys_dev)
            total_steps += 1
            for slot, s in enumerate(states):
                if s["done"]:
                    continue
                tok = int(toks[slot])
                s["tokens"].append(tok)
                s["last"] = tok
                s["pos"] += 1
                g = s["grammar"]
                s["gstate"] = _static_grammar_step(
                    g, s["gstate"], tok, eos_id, len(s["tokens"]))
                if g is not None:
                    bias[slot] = g.bias_row(s["gstate"])
                if ((eos_id is not None and tok == eos_id)
                        or len(s["tokens"]) >= s["target"]
                        or s["pos"] >= max_len):
                    s["done"] = True
                    _static_grammar_finish(g, s["gstate"], tok, eos_id,
                                           len(s["tokens"]))
                elif (g is not None and eos_id is None
                        and not g.has_continuation(s["gstate"])):
                    s["done"] = True
        for i, s in enumerate(states):
            outputs[base + i] = s["tokens"]
            pool.release(s["pages"])
        page_map[:] = pool.trash
        temps[:] = 0.0
        top_ks[:] = 0
        top_ps[:] = 1.0
        keys[:] = 0
        if bias is not None:
            bias[:] = 0.0
    return outputs, total_steps
