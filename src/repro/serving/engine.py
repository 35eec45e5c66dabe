"""Real-compute inference engine: jitted prefill/decode with continuous
batching (Orca-style slot recycling) over a shared KV cache.

Two KV backends (`kv_backend`):
  "dense": one max_batch x max_len reservation per slot (the seed layout,
      kept for A/B equivalence testing).
  "paged": vLLM-style paged cache (models/paged_cache.py) — pages are
      allocated on demand at add_request, appended per decode step, and freed
      on completion; when the pool runs dry the youngest request is evicted
      (preempted) and transparently resubmitted, so a small pool degrades to
      recompute instead of failing. Dense and paged are bit-identical on the
      same request stream (masked page garbage contributes exactly zero).

The step loop is structured plan/run (flashinfer's plan/run split and vLLM's
scheduler are the precedents): every host decision — page growth, eviction,
ragged ingest layout, decode inputs — is planned with numpy, the block table
is pushed to the device at most once per step, and the step dispatches at
most one batched ragged chunk-ingest call plus one fused decode call
(model step + sample + logprob in a single jit, cache donated) whose readback
is deferred to the NEXT step's harvest. The host therefore plans step N+1
while the device still runs step N, and per-step sync cost is one
`jax.device_get`.

This is the engine the examples and real-compute benchmarks run on CPU with
tiny models; on TPU the same code serves the full configs (the dry-run proves
the sharded lowering). Prompt lengths are bucketed to powers of two to bound
jit recompilation; `warmup()` precompiles the variants an arrival pattern
will need so the first serving window is not dominated by XLA compiles.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.kernels.paged_decode_attention import kernel as pda_kernel
from repro.models import attention as attn_lib
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models import paged_cache as pc
from repro.models.paged_cache import PageAllocator
from repro.serving.requests import BoundedRecord
from repro.serving.sampler import SamplerConfig, sample, token_logprob


def _bucket(n: int, lo: int = 32) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _pow2_bucket(n: int, hi: int) -> int:
    """Power-of-two bucket from 1, clamped to `hi` — upload widths (swap
    promote) and other small counts whose jit variants must stay bounded."""
    b = 1
    while b < n:
        b *= 2
    return min(b, hi)


# ---------------------------------------------------------------------------
# Jitted entry points, shared across engine instances. ModelConfig is a
# frozen dataclass (hashable), so engines with the same config — the edge
# fleet, A/B dense-vs-paged pairs, short-lived benchmark engines — reuse one
# trace cache instead of recompiling per instance.
# ---------------------------------------------------------------------------

def _prefill_dense_fn(cfg, params, tokens, cache, lengths):
    return transformer.prefill(cfg, params, tokens, cache,
                               prompt_lengths=lengths)


def _score_fn(cfg, params, tokens):
    """Teacher-forced mean logprob of tokens[1:] given tokens[:-1]."""
    logits, _ = transformer.forward(cfg, params, tokens[None, :-1])
    logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logp, tokens[1:][:, None], axis=-1)[:, 0]
    return jnp.mean(gold), gold


def _insert_fn(big, one, slot):
    """Insert a batch-1 cache into slot `slot` of the big cache.
    Cache layout: lengths (B,); segment leaves (L, B, ...) — batch axis 1."""
    out = {"lengths": jax.lax.dynamic_update_slice(
        big["lengths"], one["lengths"].astype(big["lengths"].dtype), (slot,))}
    segs = []
    for bseg, oseg in zip(big["segments"], one["segments"]):
        seg = {}
        for k in bseg:
            idx = (0, slot) + (0,) * (bseg[k].ndim - 2)
            seg[k] = jax.lax.dynamic_update_slice(
                bseg[k], oseg[k].astype(bseg[k].dtype), idx)
        segs.append(seg)
    out["segments"] = segs
    return out


def _decode_dense_fn(cfg, params, tokens, cache, active):
    return transformer.decode_step(cfg, params, tokens, cache, active=active)


def _prefill_ragged_fn(cfg, live_pages, params, tokens, cache, slots, offsets,
                       lens):
    """(logits, cache, expert rows: the serving expert layers' assignments
    per held expert, summed over layers, or None without such layers)."""
    st = {}
    logits, cache = transformer.prefill_ragged_paged(
        cfg, params, tokens, cache, slots, offsets, lens,
        live_pages=live_pages, stats=st)
    return logits, cache, st.get("expert_rows")


def _promote_fn(cfg, cache, upload_ids, payloads, slot, ctx_len):
    return transformer.promote_slot_paged(cfg, cache, upload_ids, payloads,
                                          slot, ctx_len)


# The "run" half of the plan/run decode step: model step + PRNG split +
# sample + logprob fused into ONE dispatch, returning device arrays the
# engine reads back a full step later (deferred harvest). The split/sample
# sequence is written exactly as the eager path ran it, so fused and eager
# draws are bitwise identical.

def _decode_dense_run_fn(cfg, sampler, params, tokens, cache, active, key):
    logits, cache = transformer.decode_step(cfg, params, tokens, cache,
                                            active=active)
    key, sub = jax.random.split(key)
    toks = sample(logits, sub, sampler)
    lps = token_logprob(logits, toks)
    return toks, lps, key, cache


def _decode_paged_run_fn(cfg, sampler, live_pages, params, tokens, cache,
                         active, key):
    st = {}
    logits, cache = transformer.decode_step_paged(cfg, params, tokens, cache,
                                                  active=active,
                                                  live_pages=live_pages,
                                                  stats=st)
    key, sub = jax.random.split(key)
    toks = sample(logits, sub, sampler)
    lps = token_logprob(logits, toks)
    return toks, lps, key, cache, st.get("expert_rows")


@functools.lru_cache(maxsize=None)
def _jitted(cfg: ModelConfig, kind: str,
            sampler: Optional[SamplerConfig] = None):
    if kind == "decode":
        return jax.jit(functools.partial(_decode_dense_fn, cfg))
    if kind == "decode_run":
        # SamplerConfig is frozen/hashable, so the fused variants share the
        # lru_cache exactly like cfg does
        return jax.jit(functools.partial(_decode_dense_run_fn, cfg, sampler),
                       donate_argnums=(2,))
    if kind == "decode_paged_run":
        # live_pages is static (the read width is a shape); the engine
        # buckets it to powers of two, so recompiles are bounded by
        # log2(max_pages_per_seq) variants per config
        return jax.jit(functools.partial(_decode_paged_run_fn, cfg, sampler),
                       static_argnums=(0,), donate_argnums=(3,))
    if kind == "prefill":
        return jax.jit(functools.partial(_prefill_dense_fn, cfg))
    if kind == "prefill_paged":
        return jax.jit(functools.partial(transformer.prefill_paged, cfg),
                       donate_argnums=(2,))
    if kind == "prefill_ragged":
        # batched ragged ingest: one call advances EVERY ingesting slot's
        # next chunk. live_pages is static (the read width is a shape),
        # bucketed like the decode step; the token shape is (R,
        # cfg.prefill_chunk) with R bucketed to powers of two (lo=1), so
        # variants are bounded by log2(max_batch) x log2(live widths)
        # instead of one prefill per prompt-length bucket
        return jax.jit(functools.partial(_prefill_ragged_fn, cfg),
                       static_argnums=(0,), donate_argnums=(3,))
    if kind == "promote":
        # swap-in scatter (host-tier resume): the upload width U is a shape
        # the engine buckets with _pow2_bucket, so variants are bounded by
        # log2(pages_per_seq) per config
        return jax.jit(functools.partial(_promote_fn, cfg),
                       donate_argnums=(0,))
    if kind == "fork":
        return jax.jit(functools.partial(transformer.fork_slot_paged, cfg),
                       donate_argnums=(0,))
    if kind == "insert":
        return jax.jit(_insert_fn, donate_argnums=(0,))
    if kind == "score":
        return jax.jit(functools.partial(_score_fn, cfg))
    raise ValueError(kind)


@dataclasses.dataclass
class Slot:
    req_id: int = -1
    active: bool = False
    tokens: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    max_new: int = 0
    generated: int = 0
    prompt: List[int] = dataclasses.field(default_factory=list)
    ctx_len: int = 0        # tokens currently in the KV cache for this slot
    arrival: int = 0        # admission order (eviction picks the youngest)
    evicted: bool = False   # preempted: requeue instead of completing
    parked: bool = False    # holds a shared prefix for forking, not decoding
    # suffix tokens still to be teacher-forced into the cache (fork path):
    # each decode step feeds pending[0] instead of the last sampled token
    pending: List[int] = dataclasses.field(default_factory=list)
    fork_src: int = -1      # parked slot this one was forked from (-1: none)
    suffix: List[int] = dataclasses.field(default_factory=list)
    # prompt tokens not yet ingested (chunked prefill): while non-empty the
    # slot is excluded from the decode batch and step() feeds it one chunk
    # at a time; the first sample comes from the final chunk's logits
    prefill_toks: List[int] = dataclasses.field(default_factory=list)
    # eviction priority (higher = more latency-critical, evicted last);
    # PICE maps cloud-sketch / SLA-bound work above opportunistic
    # ensemble expansions
    priority: int = 0
    # the admitted prompt was longer than max_len and kept only its tail
    # (surfaced so callers can tell a truncated completion from a full one;
    # eviction-resume replays the same truncation deterministically)
    truncated: bool = False


@dataclasses.dataclass
class StepPlan:
    """Host-side decode plan: every decision one decode step needs, computed
    with numpy only (the "plan" half of the plan/run split — flashinfer's
    plan/run and vLLM's scheduler are the precedents). Token-independent
    state (ctx_len advance, pending-suffix pops) is applied AT PLAN TIME;
    only the sampled token's commit waits for the deferred harvest, so the
    host can plan step N+1 while the device still runs step N."""
    active_ids: List[int]           # slots in this decode batch
    last: np.ndarray                # (B, 1) int32 decode inputs
    mask: np.ndarray                # (B,) bool active-row mask
    live: int                       # paged: static live-width bucket (0=dense)
    commits: List[int]              # slots whose sampled token commits later


@dataclasses.dataclass
class _Resume:
    """A queued request: fresh, or preempted with its generated prefix
    carried. share_from >= 0 routes admission through the COW fork path
    (prompt then holds the full prefix+suffix fallback for eviction resume).
    """
    req_id: int
    prompt: List[int]
    max_new: int
    carry_tokens: List[int]
    carry_lps: List[float]
    share_from: int = -1
    suffix: List[int] = dataclasses.field(default_factory=list)
    priority: int = 0
    # host-tier swap payload (paged backend, host_swap): the victim's page
    # bytes (+ quant scales) snapshotted at demotion, one dict per attention
    # segment, plus the slot state a promote restores verbatim. Non-None
    # routes admission through `_admit_swapped` (single-upload promote and
    # direct decode re-entry) instead of a prefill replay.
    swap: Optional[dict] = None


# Public name for the request-handle admission API (`InferenceEngine
# .try_admit`): the serving front-end builds these for fresh submissions.
EngineRequest = _Resume


class InferenceEngine:
    """Continuous-batching engine for one model."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_len: int = 1024, sampler: SamplerConfig = SamplerConfig(),
                 eos_id: int = 0, name: str = "engine",
                 kv_backend: str = "dense", page_size: int = 32,
                 n_pages: Optional[int] = None, host_swap: bool = True):
        assert kv_backend in ("dense", "paged"), kv_backend
        # The attention read path is chosen here, once, for every jitted
        # step this engine runs: the Pallas kernels when the config asks
        # for them and they can serve it on this backend, else the jnp
        # gather oracle, with the reason kept and warned.
        self.read_path_note = (
            attn_lib.pallas_unsupported(cfg, paged=kv_backend == "paged")
            if cfg.use_pallas else "")
        if self.read_path_note:
            warnings.warn(f"{name}: use_pallas=True cannot be served "
                          f"({self.read_path_note}); attention reads "
                          "through the jnp oracle")
            cfg = cfg.with_(use_pallas=False)
        self.read_path = "pallas" if cfg.use_pallas else "oracle"
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.sampler = sampler
        self.eos_id = eos_id
        self.name = name
        self.kv_backend = kv_backend
        self.slots = [Slot() for _ in range(max_batch)]
        self.key = jax.random.PRNGKey(0)
        self.tokens_generated = 0
        self._arrivals = 0
        self.evictions = 0
        self.peak_pages = 0
        self._window_peak = 0
        self._window_shared = 0
        self._window_logical = 0
        self._resume_queue: List[_Resume] = []
        self._prefix_logits: Dict[int, jax.Array] = {}   # parked slot -> (1,V)
        # per-request time-to-first-token telemetry: admission time survives
        # eviction/resume (TTFT spans the preemption), recorded once at the
        # first committed token; benchmarks read + clear `ttft`
        self._t_admit: Dict[int, float] = {}
        self._admit_stamp_cap = 4096
        # req_ids a _run loop is still driving: their admission stamps must
        # never be pruned even while they sit evicted in the resume queue
        self._inflight: set = set()
        self.ttft: Dict[int, float] = BoundedRecord(self._admit_stamp_cap)
        # req_id -> prompt tokens dropped at admission (prompt > max_len);
        # the matching Slot carries `truncated` while it lives
        self.truncations: Dict[int, int] = BoundedRecord(self._admit_stamp_cap)
        self.prefill_chunk = 0
        # deferred harvest: (commit slots, device toks, device lps) of the
        # decode step dispatched last step(), read back at the next step()
        self._pending_decode: Optional[Tuple[List[int], jax.Array,
                                             jax.Array]] = None
        # with the tracer on: (expert rows on the device, the attributes of
        # the decode span that dispatched them), read back at the harvest
        self._pending_rows: Optional[Tuple[jax.Array, dict]] = None
        self._decode_attrs: Optional[dict] = None
        self._table_dirty = False
        # host-tier swap telemetry (paged backend, host_swap)
        self.swap_outs = 0
        self.swap_ins = 0
        self.swap_bytes = 0         # host<->device bytes moved by swaps
        # decode/ingest KV read traffic in bytes (pages touched per step x
        # per-page pool+scale bytes): the signal the kv_dtype A/B benches
        # compare — int8 pools shrink it ~2x against bf16
        self.kv_bytes_read = 0
        self._page_kv_bytes = 0
        self.host_swap = False
        # fault-injection surfaces (serving/faults.py): step_hook(engine) is
        # called at the top of every step() and may cancel slots, stall, or
        # raise EngineCrash; swap_fault_hook(req_id) -> True marks a swap
        # promote's upload as lost, degrading that resume to evict-and-replay
        self.step_hook = None
        self.swap_fault_hook = None
        # cancellation / degradation telemetry
        self.cancels = 0
        self.deadline_cancels = 0
        self.swap_losses = 0

        if kv_backend == "paged":
            cfg.validate_paged(page_size, max_len)
            assert not cfg.is_mla or cfg.prefill_chunk, \
                "latent attention ingests through chunked prefill: set " \
                "prefill_chunk"
            self.page_size = page_size
            self.pages_per_seq = max_len // page_size
            self.n_pages = n_pages or max_batch * self.pages_per_seq
            self.alloc = PageAllocator(self.n_pages, page_size,
                                       self.pages_per_seq)
            self.block_table = np.full((max_batch, self.pages_per_seq), -1,
                                       np.int32)
            self.cache = transformer.init_paged_cache(
                cfg, max_batch, self.n_pages, page_size, self.pages_per_seq)
            self._push_table()
            self._decode_run = _jitted(cfg, "decode_paged_run", sampler)
            self._prefill_paged = _jitted(cfg, "prefill_paged")
            self._fork = _jitted(cfg, "fork")
            # chunked prefill needs an attention-only stack (recurrent
            # segments cannot resume their scan state mid-prompt): other
            # families silently keep the monolithic path
            chunkable = all(
                kind in ("attn", "moe", "shared_attn")
                for kind, _ in transformer.segments_of(cfg))
            self.prefill_chunk = cfg.prefill_chunk if chunkable else 0
            if self.prefill_chunk:
                self._prefill_ragged = _jitted(cfg, "prefill_ragged")
            # host-tier page swap (demote on eviction, promote on resume)
            # rides the same attention-only gate as chunked prefill:
            # recurrent segments would need their dense scan states
            # snapshotted too, so those families keep evict-and-replay
            self.host_swap = host_swap and chunkable
            if self.host_swap:
                self._promote = _jitted(cfg, "promote")
            # bytes one physical page contributes across every attention
            # segment's pool + scale leaves (drives kv_bytes_read)
            per_page = 0
            for seg in self.cache["segments"]:
                if not pc.is_pool(seg):
                    continue
                for k in seg:
                    n = seg[k].shape[0] * seg[k].dtype.itemsize
                    for d in seg[k].shape[2:]:
                        n *= d
                    per_page += n
            self._page_kv_bytes = per_page
        else:
            assert not cfg.kv_quantized, \
                "kv_dtype quantization needs the paged backend"
            self.cache = transformer.init_cache(cfg, max_batch, max_len)
            self._decode_run = _jitted(cfg, "decode_run", sampler)
            self._prefill = _jitted(cfg, "prefill")
            self._insert = _jitted(cfg, "insert")
        self._score = _jitted(cfg, "score")

    # ------------------------------------------------------------------
    # Paged-backend bookkeeping
    # ------------------------------------------------------------------
    def _push_table(self):
        self.cache["block_table"] = jnp.asarray(self.block_table)
        self._table_dirty = False

    def _mark_table_dirty(self):
        """Host block-table edits are batched: step() pushes the table to
        the device at most ONCE per step (`_sync_table`), right before the
        first dispatch that reads it. Deferring a freed slot's row clear is
        safe because decode writes are active-masked (see pc.write_token)
        and masked rows' reads are discarded."""
        self._table_dirty = True

    def _sync_table(self):
        if self._table_dirty:
            self._push_table()

    def _occupancy(self) -> Tuple[int, int, int]:
        """(physical, shared, logical) occupancy right now. Dense slots are
        counted as one "page" each with no sharing."""
        if self.kv_backend == "paged":
            return (self.alloc.pages_in_use, self.alloc.pages_shared,
                    self.alloc.logical_pages)
        used = sum(1 for s in self.slots if s.active)
        return used, 0, used

    def _track_peak(self):
        used, shared, logical = self._occupancy()
        self.peak_pages = max(self.peak_pages, used)
        self._window_peak = max(self._window_peak, used)
        self._window_shared = max(self._window_shared, shared)
        self._window_logical = max(self._window_logical, logical)

    def consume_window(self) -> Dict[str, int]:
        """High-water occupancy since the last call, then reset the window.
        The PICE pipeline is synchronous — pools drain to zero between
        requests — so instantaneous occupancy is always 0 at observation
        time; the windowed peak is the pressure signal that survives. Both
        backends window: a dense fleet otherwise always reports ~0 active
        slots between synchronous requests."""
        self._track_peak()
        out = {"pages": self._window_peak, "shared": self._window_shared,
               "logical": self._window_logical}
        (self._window_peak, self._window_shared,
         self._window_logical) = self._occupancy()
        return out

    def consume_peak(self) -> int:
        """Windowed physical peak (see consume_window)."""
        return self.consume_window()["pages"]

    def _release_slot_pages(self, slot: int):
        self.alloc.release(slot)
        self.block_table[slot, :] = -1
        self._mark_table_dirty()

    def _evict_victim(self, protect: int) -> bool:
        """Preempt one active slot other than `protect`: the lowest-priority
        one, youngest-first within a priority class. Latency-critical work
        (cloud sketches, SLA-bound requests — higher `priority`) is only
        preempted once every opportunistic expansion is gone, so a parallel
        fan-out can never push a critical slot off the pool. Victims' pages
        return to the pool and the request is queued for resubmission."""
        victims = [i for i, s in enumerate(self.slots)
                   if s.active and i != protect]
        if not victims:
            return False
        v = min(victims,
                key=lambda i: (self.slots[i].priority,
                               -self.slots[i].arrival))
        s = self.slots[v]
        if self.host_swap:
            # demote instead of free-and-replay: the victim's uniquely-owned
            # pages move to the host tier as raw storage bytes (+ quant
            # scales), shared prefix pages stay resident with a held
            # reference (COW siblings cannot free them). Resume promotes
            # the bytes back with one scatter and decode re-enters directly
            # — no prefill replay and no PRNG draw; the restore is
            # byte-exact, so greedy continuations are bit-identical to an
            # uninterrupted run.
            swapped = self.alloc.demote(v, s.req_id)
            ids = np.asarray([p for _, p in swapped], np.int32)
            # snapshot from the CURRENT (immutable) cache value: the last
            # dispatch that wrote these pages was harvested at step start,
            # and demote's freed ids cannot be re-written before the next
            # dispatch, which this plan phase precedes
            # repro-analysis: disable=RA103 reason=eviction swap-out snapshot; one batched readback per demotion, off the decode hot loop
            host = jax.device_get(
                [{k: seg[k][:, ids] for k in seg}
                 for seg in self.cache["segments"] if pc.is_pool(seg)])
            self.swap_outs += 1
            self.swap_bytes += sum(a.nbytes for seg in host
                                   for a in seg.values())
            self._resume_queue.append(_Resume(
                req_id=s.req_id, prompt=list(s.prompt),
                max_new=s.max_new, carry_tokens=list(s.tokens),
                carry_lps=list(s.logprobs), priority=s.priority,
                swap={"host": host, "ctx_len": s.ctx_len,
                      "pending": list(s.pending),
                      "prefill_toks": list(s.prefill_toks),
                      "fork_src": s.fork_src, "suffix": list(s.suffix),
                      "truncated": s.truncated}))
            self.block_table[v, :] = -1
            self._mark_table_dirty()
        else:
            # release only frees the victim's *unique* pages (refcounted),
            # never prefix pages its siblings still read. A fork whose
            # prefix is still parked resumes through the fork path
            # (replaying suffix + generated tokens through decode rebuilds
            # bit-identical KV without a second prefix prefill); otherwise
            # s.prompt holds the full prefix+suffix for a monolithic
            # resume.
            refork = (0 <= s.fork_src < self.max_batch
                      and self.slots[s.fork_src].parked)
            self._resume_queue.append(_Resume(
                req_id=s.req_id, prompt=list(s.prompt),
                max_new=s.max_new, carry_tokens=list(s.tokens),
                carry_lps=list(s.logprobs),
                share_from=s.fork_src if refork else -1,
                suffix=list(s.suffix) if refork else [],
                priority=s.priority))
            self._release_slot_pages(v)
        s.active, s.evicted, s.req_id = False, True, -1
        s.pending, s.fork_src, s.suffix = [], -1, []
        s.prefill_toks = []     # a mid-prefill victim restarts its chunks
        self.evictions += 1
        return True

    def cancel(self, req_id: int) -> bool:
        """Cancel a mid-flight request: ingesting, decoding, evicted-and-
        queued, or demoted to the host tier. Frees its pages (COW refcounts
        protect shared prefix pages), drops any host-tier snapshot, and
        prunes its slot from the deferred-harvest commit list so a slot
        reused by a later admission can never receive the cancelled
        request's in-flight token. Surviving requests are untouched:
        per-row attention reads only the survivor's own block-table row,
        decode writes are active-masked, and the engine PRNG key advances
        per step regardless of which rows are active — so survivors'
        outputs are bit-identical to a run without the cancellation.

        Returns True if the request was found in any live state. The slot
        keeps its partial tokens so a driving `_run` loop collects them as
        the (truncated) result."""
        hit = False
        for i, s in enumerate(self.slots):
            if s.active and s.req_id == req_id:
                s.active = False
                s.evicted = False
                s.pending, s.prefill_toks = [], []
                s.fork_src, s.suffix = -1, []
                if self.kv_backend == "paged":
                    self._release_slot_pages(i)
                if self._pending_decode is not None:
                    commits, toks, lps = self._pending_decode
                    if i in commits:
                        # the harvest guard alone is not enough: a request
                        # admitted into this slot before the next harvest
                        # would satisfy `slots[i].active` and absorb the
                        # cancelled request's token
                        self._pending_decode = (
                            [c for c in commits if c != i], toks, lps)
                hit = True
        kept = []
        for r in self._resume_queue:
            if r.req_id != req_id:
                kept.append(r)
                continue
            if r.swap is not None:
                self.alloc.drop_hosted(r.req_id)
            hit = True
        self._resume_queue = kept
        if hit:
            self.cancels += 1
            self._t_admit.pop(req_id, None)
        return hit

    def abort_all(self) -> int:
        """Cancel every live request — the recovery path after an injected
        (or real) engine crash mid-`_run`: pages return to the pool, host-
        tier snapshots are dropped, and the in-flight decode's commits are
        discarded. Parked prefix slots are left alone (their owner's
        `generate_fanout` finally-block releases them). Returns the number
        of requests aborted."""
        n = 0
        for s in list(self.slots):
            if s.active:
                self.cancel(s.req_id)
                n += 1
        for r in list(self._resume_queue):
            self.cancel(r.req_id)
            n += 1
        self._pending_decode = None
        return n

    def memory_stats(self) -> Dict[str, float]:
        """Engine-level KV memory telemetry (for RuntimeMonitor).

        `pages_shared` counts physical pages referenced by >1 slot;
        `pages_logical` is the sum of per-slot chains (what an unshared
        layout would hold) — logical - in_use is the COW saving."""
        if self.kv_backend == "paged":
            return {"backend": "paged", "pages_total": self.n_pages,
                    "pages_in_use": self.alloc.pages_in_use,
                    "pages_shared": self.alloc.pages_shared,
                    "pages_logical": self.alloc.logical_pages,
                    "peak_pages": self.peak_pages,
                    "utilization": self.alloc.utilization,
                    "evictions": self.evictions}
        used = sum(1 for s in self.slots if s.active)
        return {"backend": "dense", "pages_total": self.max_batch,
                "pages_in_use": used, "pages_shared": 0,
                "pages_logical": used, "peak_pages": self.max_batch,
                "utilization": used / self.max_batch, "evictions": 0}

    def can_admit(self, prompt_len: int) -> bool:
        """Admission check against real memory, not just a fixed max_batch."""
        if not self.free_slots():
            return False
        if self.kv_backend == "paged":
            need = max(1, -(-min(prompt_len, self.max_len) // self.page_size))
            return len(self.alloc.free) >= need
        return True

    def can_admit_fork(self, src_slot: int, extra_tokens: int = 0) -> bool:
        """Admission check for the fork path: a free batch row plus enough
        free pages for the tail copy AND the suffix/carry replay
        (extra_tokens). Gating on the full replay need — like `can_admit`
        gates on the full prompt — prevents admit/evict livelock between
        sibling forks under a tight pool."""
        if not self.free_slots():
            return False
        src = self.slots[src_slot]
        total = min(src.ctx_len + extra_tokens, self.max_len)
        full_shared = src.ctx_len // self.page_size
        need = -(-total // self.page_size) - full_shared
        return len(self.alloc.free) >= need

    def can_admit_swap(self, req_id: int) -> bool:
        """Admission check for a demoted request: a free batch row plus
        enough free pages to re-house every swapped page (resident shared
        pages are already held by the hosted entry)."""
        if not self.free_slots():
            return False
        return len(self.alloc.free) >= self.alloc.hosted_pages(req_id)

    def _admit_swapped(self, r: _Resume) -> int:
        """Re-admit a demoted request by promoting its host-tier pages:
        allocate fresh device pages, upload the snapshotted bytes in ONE
        scatter (`promote_slot_paged`, upload width bucketed), rebuild the
        block-table row, and restore the slot so the next step's decode
        continues from the last sampled token. Versus the replay path this
        trades a host->device transfer of the swapped bytes for the whole
        prefill recompute (see docs/serving.md for the crossover)."""
        slot = self.free_slots()[0]
        self._t_admit.setdefault(r.req_id, time.perf_counter())
        self._prune_admit_stamps()
        uploads = self.alloc.promote(r.req_id, slot)    # MemoryError if dry
        chain = self.alloc.owned[slot]
        self.block_table[slot, :] = -1
        self.block_table[slot, :len(chain)] = chain
        self._mark_table_dirty()
        sw = r.swap
        U = _pow2_bucket(max(len(uploads), 1), self.pages_per_seq)
        ids = np.full((U,), self.n_pages, np.int32)     # padding ids drop
        ids[:len(uploads)] = [p for _, p in uploads]
        payloads = []
        for seg in sw["host"]:
            pay = {}
            for k, arr in seg.items():
                buf = np.zeros((arr.shape[0], U) + arr.shape[2:], arr.dtype)
                buf[:, :arr.shape[1]] = arr
                pay[k] = jnp.asarray(buf)
            payloads.append(pay)
        self.cache = self._promote(
            self.cache, jnp.asarray(ids), payloads,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(sw["ctx_len"], jnp.int32))
        self.swap_ins += 1
        self.swap_bytes += sum(a.nbytes for seg in sw["host"]
                               for a in seg.values())
        s = self.slots[slot]
        s.req_id, s.active = r.req_id, True
        s.prompt = list(r.prompt)
        s.tokens, s.logprobs = list(r.carry_tokens), list(r.carry_lps)
        s.max_new, s.generated = r.max_new, len(r.carry_tokens)
        s.ctx_len = sw["ctx_len"]
        s.pending = list(sw["pending"])
        s.prefill_toks = list(sw["prefill_toks"])
        s.fork_src, s.suffix = sw["fork_src"], list(sw["suffix"])
        s.evicted, s.priority = False, r.priority
        s.truncated = sw["truncated"]
        s.arrival = self._arrivals
        self._arrivals += 1
        self._track_peak()
        return slot

    def _latent_pages(self, sp, ends: List[int]) -> None:
        """A latent-pool engine's `latent_pages` span attribute: the pages
        a call streams, summed over rows and layers, for rows whose reads
        end at `ends`."""
        if self.cfg.is_mla:
            sp.attrs["latent_pages"] = self.cfg.n_layers * sum(
                -(-e // self.page_size) for e in ends)

    def _pages_per_step(self, sp, live: int) -> None:
        """A decode's `pages_per_step` span attribute where it reads
        through the paged kernels: the pages one grid step of its read
        streams at the step's static live width."""
        if self.read_path == "pallas" and self.kv_backend == "paged":
            sp.attrs["pages_per_step"] = pda_kernel.decode_pages(
                live, self.cfg.is_mla)

    def _live_pages(self, active: List[int]) -> int:
        """Static read width for this decode step: enough block-table
        columns to cover every active slot's cache plus the token being
        written, bucketed to the next power of two so jit variants stay
        bounded. Trimmed columns are past every slot's valid positions and
        carry exactly-zero attention weight, so any covering width is
        bit-identical — this only stops the read path from paying for
        `max_pages_per_seq` when the batch is short."""
        return self._chunk_live(max(self.slots[i].ctx_len
                                    for i in active) + 1)

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots)
                if not s.active and not s.parked]

    def _alloc_slot_pages(self, slot: int, n_tokens: int):
        """Map a fresh page chain for `n_tokens` into the slot's table row."""
        pages = self.alloc.alloc_for(slot, n_tokens)    # MemoryError if dry
        self._track_peak()
        self.block_table[slot, :] = -1
        self.block_table[slot, :len(pages)] = pages
        self._mark_table_dirty()

    def _chunk_live(self, end: int) -> int:
        """Static covering read width through position `end`, bucketed to
        the next power of two (shared by the decode step and chunk ingest
        so both paths honor one recompile contract). A latent-attention
        engine reads the whole table: its kernels skip a row's pages past
        its live range inside the grid, many pages a step, so a trimmed
        width would save a few grid steps while each width is another
        compiled program of every layer."""
        if self.cfg.is_mla:
            return self.pages_per_seq
        need = -(-min(end, self.max_len) // self.page_size)
        live = 1
        while live < need:
            live *= 2
        return min(live, self.pages_per_seq)

    def _feed_chunk(self, slot: int, chunk: List[int], offset: int):
        """One slot's chunk as a one-row ragged ingest: pad, pick the
        covering live width, write+attend the chunk at `offset`. Returns
        the chunk's last-valid-token logits (1, V)."""
        with trace.span("engine.feed_chunk", engine=self.name,
                        offset=offset, length=len(chunk)):
            padded = np.zeros((1, self.prefill_chunk), np.int32)
            padded[0, :len(chunk)] = chunk
            live = self._chunk_live(offset + len(chunk))
            self._sync_table()
            # numpy rows, as `_run_ingest` builds them: jnp.asarray of a
            # list with a dtype compiles a conversion on first use
            row = [jnp.asarray(np.array([v], np.int32))
                   for v in (slot, offset, len(chunk))]
            logits, self.cache, _ = self._prefill_ragged(
                live, self.params, jnp.asarray(padded), self.cache, *row)
        return logits

    def _first_token(self, slot: int, logits):
        """Sample, read back and commit a slot's first token from (1, V)
        prefill logits: the one draw admission takes."""
        with trace.span("engine.first_token", engine=self.name):
            self.key, sub = jax.random.split(self.key)
            tok = sample(logits, sub, self.sampler)
            lp = token_logprob(logits, tok)
            with trace.span("engine.readback", engine=self.name):
                # repro-analysis: disable=RA103 reason=admission-time first-token draw; one batched readback, off the decode loop
                tok_h, lp_h = jax.device_get((tok, lp))
            self._commit(slot, int(tok_h[0]), float(lp_h[0]))

    def _prefill_into_chunks(self, slot: int, toks: List[int]):
        """Synchronous chunked ingest of a whole prompt (prefill_prefix and
        direct callers outside the step loop); returns final-chunk logits.
        Performs no PRNG splits: only the first-token sample advances the
        key stream. An empty prompt ingests one zero-length chunk so
        callers always get logits (matching the monolithic path's
        zero-padded prefill)."""
        C = self.prefill_chunk
        logits = None
        for start in range(0, max(len(toks), 1), C):
            logits = self._feed_chunk(slot, toks[start:start + C], start)
        return logits

    def _prefill_into(self, slot: int, toks: List[int], padded: np.ndarray):
        """Prefill `toks` into batch row `slot` (either backend); returns
        last-token logits (1, V)."""
        if self.kv_backend == "paged":
            self._alloc_slot_pages(slot, len(toks))
            if self.prefill_chunk:
                return self._prefill_into_chunks(slot, toks)
            self._sync_table()
            logits, self.cache = self._prefill_paged(
                self.params, jnp.asarray(padded), self.cache,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(len(toks), jnp.int32))
        else:
            one_cache = transformer.init_cache(self.cfg, 1, self.max_len)
            logits, one_cache = self._prefill(
                self.params, jnp.asarray(padded), one_cache,
                jnp.asarray([len(toks)], jnp.int32))
            self.cache = self._insert(self.cache, one_cache, slot)
        return logits

    @staticmethod
    def _pad_prompt(full_prompt: List[int], max_len: int):
        """Bucket-pad a prompt, keeping the TAIL when it exceeds max_len
        (generation conditions on the most recent context). Returns
        (kept_tokens, padded, dropped) — `dropped` > 0 surfaces the
        truncation instead of silently shortening the prompt; callers
        record it so an eviction-resume replays the identical truncation."""
        S = min(_bucket(len(full_prompt)), max_len)
        padded = np.zeros((1, S), np.int32)
        toks = full_prompt[-S:]
        padded[0, :len(toks)] = toks
        return toks, padded, len(full_prompt) - len(toks)

    # ------------------------------------------------------------------
    # Prefix sharing (PICE sketch fan-out): prefill the shared (query,
    # sketch) prefix ONCE into a parked slot, then fork N copy-on-write
    # block-table rows off it — full prefix pages are shared refcounted,
    # only the partial tail page is copied per fork.
    # ------------------------------------------------------------------
    def prefill_prefix(self, prefix: List[int]) -> int:
        """Prefill a shared prefix into a parked slot and return its id for
        `add_request(..., share_from=slot)`. The slot holds its pages (and
        is excluded from scheduling) until `release_prefix`."""
        assert self.kv_backend == "paged", \
            "prefix sharing needs the paged backend"
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot")
        # park in the LAST free slot: forks then land on the same batch rows
        # as independent submissions would, keeping the per-row PRNG draws —
        # and therefore sampled outputs — bit-identical to the unshared path
        slot = free[-1]
        with trace.span("engine.prefill_prefix", engine=self.name,
                        tokens=len(prefix)):
            toks, padded, _ = self._pad_prompt(list(prefix), self.max_len)
            logits = self._prefill_into(slot, toks, padded)
        s = self.slots[slot]
        s.req_id, s.active, s.parked = -1, False, True
        s.prompt = list(prefix)
        s.tokens, s.logprobs, s.pending = [], [], []
        s.prefill_toks = []
        s.ctx_len = len(toks)
        self._prefix_logits[slot] = logits
        return slot

    def release_prefix(self, slot: int) -> None:
        """Free a parked prefix slot; pages shared with live forks survive
        via their refcounts."""
        s = self.slots[slot]
        assert s.parked, "release_prefix on a non-parked slot"
        s.parked = False
        self._prefix_logits.pop(slot, None)
        self._release_slot_pages(slot)

    def add_request(self, req_id: int, prompt: List[int], max_new: int,
                    carry_tokens: Optional[List[int]] = None,
                    carry_lps: Optional[List[float]] = None,
                    share_from: Optional[int] = None,
                    suffix: Optional[List[int]] = None,
                    priority: int = 0) -> int:
        """Admit a request. share_from forks a parked prefix slot
        copy-on-write instead of prefilling; `suffix` tokens (the part of
        the logical prompt beyond the shared prefix) are then ingested into
        the cache before sampling starts — as are any carried tokens when a
        preempted fork resumes. `prompt` must be the full logical prompt
        (prefix + suffix) so eviction can always fall back to a monolithic
        resume. `priority` orders eviction: lower-priority slots are
        preempted first (see `_evict_victim`).

        With `cfg.prefill_chunk` set (paged backend), admission only maps
        the prompt's pages and queues its tokens: `step()` then ingests one
        chunk per call interleaved with the decode batch, so a long prompt
        never stalls running decodes for more than one chunk. Fork suffixes
        and resume carries ride the same chunked path (multi-token ingest)
        instead of token-by-token teacher forcing."""
        suffix = list(suffix or [])
        carry_tokens = carry_tokens or []
        carry_lps = carry_lps or []
        if share_from is not None:
            src = self.slots[share_from]
            assert self.kv_backend == "paged", \
                "prefix sharing needs the paged backend"
            assert src.parked and share_from in self._prefix_logits, \
                "share_from must be a parked prefill_prefix slot"
            if src.ctx_len + len(suffix) + len(carry_tokens) > self.max_len:
                share_from = None       # would overflow: prefill monolithically
        free = self.free_slots()
        if not free:
            raise RuntimeError("no free slot")
        slot = free[0]
        self._t_admit.setdefault(req_id, time.perf_counter())
        self._prune_admit_stamps()

        dropped = 0
        ingest: List[int] = []          # chunked path: tokens step() feeds
        logits = None
        if share_from is not None:
            src = self.slots[share_from]
            # MemoryError if the tail copy cannot be allocated
            dst_pages, tail_src, tail_dst = self.alloc.fork(
                share_from, slot, src.ctx_len)
            self._track_peak()
            self.block_table[slot, :] = -1
            self.block_table[slot, :len(dst_pages)] = dst_pages
            self._mark_table_dirty()
            self.cache = self._fork(
                self.cache, jnp.asarray(share_from, jnp.int32),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(tail_src, jnp.int32),
                jnp.asarray(tail_dst, jnp.int32))
            logits = self._prefix_logits[share_from]
            ctx = src.ctx_len
            pending = suffix + carry_tokens
            if self.prefill_chunk and pending:
                # the replay goes through multi-token chunks: map the pages
                # it will write up front (can_admit_fork gated on this need)
                target = -(-min(ctx + len(pending), self.max_len)
                           // self.page_size)
                while len(self.alloc.owned[slot]) < target:
                    p = self.alloc.extend(
                        slot, (len(self.alloc.owned[slot]) + 1)
                        * self.page_size)
                    self.block_table[slot,
                                     len(self.alloc.owned[slot]) - 1] = p
                self._mark_table_dirty()
                self._track_peak()
                ingest, pending = pending, []
        elif self.prefill_chunk:
            full = list(prompt) + carry_tokens
            toks = full[-self.max_len:]
            dropped = len(full) - len(toks)
            self._alloc_slot_pages(slot, len(toks))
            ctx, pending, ingest = 0, [], list(toks)
            if not toks:
                # degenerate empty prompt: ingest one zero-length chunk now
                # so the sample below has logits (the monolithic path
                # likewise prefills a zero-padded buffer and samples)
                logits = self._prefill_into_chunks(slot, toks)
        else:
            toks, padded, dropped = self._pad_prompt(
                list(prompt) + carry_tokens, self.max_len)
            logits = self._prefill_into(slot, toks, padded)
            ctx = len(toks)
            pending = []

        s = self.slots[slot]
        s.req_id, s.active = req_id, True
        s.prompt = list(prompt)
        s.tokens, s.logprobs = list(carry_tokens), list(carry_lps)
        s.max_new, s.generated = max_new, len(carry_tokens)
        s.ctx_len = ctx
        s.pending = list(pending)
        s.prefill_toks = list(ingest)
        s.fork_src = share_from if share_from is not None else -1
        s.suffix = suffix if share_from is not None else []
        s.evicted = False
        s.priority = priority
        s.truncated = dropped > 0
        if dropped:
            # BoundedRecord evicts the oldest entries past the cap
            self.truncations[req_id] = dropped
        s.arrival = self._arrivals
        self._arrivals += 1
        self._track_peak()
        if not s.pending and not s.prefill_toks:
            # sample the first token from (possibly shared) prefill logits
            self._first_token(slot, logits)
        # else: the first sample comes after the last suffix/prompt token
        # is ingested
        return slot

    def _prune_admit_stamps(self):
        """Bound `_t_admit` without losing live requests' TTFT: only stamps
        with NO remaining reference — no active/ingesting slot, nothing in
        the resume queue, nothing a _run loop still drives — are evictable.
        (The old cap popped the OLDEST stamp, which under churn was exactly
        a preempted or still-queued request whose TTFT then silently never
        got recorded.)"""
        if len(self._t_admit) <= self._admit_stamp_cap:
            return
        live = {s.req_id for s in self.slots if s.active}
        live |= {r.req_id for r in self._resume_queue}
        live |= self._inflight
        for rid in list(self._t_admit):
            if len(self._t_admit) <= self._admit_stamp_cap:
                break
            if rid not in live:
                self._t_admit.pop(rid)

    def _commit(self, slot: int, tok: int, lp: float):
        s = self.slots[slot]
        s.tokens.append(tok)
        s.logprobs.append(lp)
        s.generated += 1
        self.tokens_generated += 1
        if s.generated == 1 and s.req_id in self._t_admit:
            # BoundedRecord keeps the most recent window in long-running
            # fleets (insertion order, oldest evicted past the cap)
            self.ttft[s.req_id] = (time.perf_counter()
                                   - self._t_admit.pop(s.req_id))
        # context capacity counts as completion: decoding past max_len would
        # overwrite live cache positions (in either backend), so both
        # backends stop at the same point and stay bit-identical
        if (tok == self.eos_id or s.generated >= s.max_new
                or s.ctx_len >= self.max_len):
            s.active = False
            if self.kv_backend == "paged":
                self._release_slot_pages(slot)

    def _grow_pages(self):
        """Before a decode step, make every active slot's next write target
        safe: copy-on-write any shared page the write would land in, and map
        a fresh page when the slot crosses a page boundary; evict the
        youngest request when the pool is dry. Raises MemoryError only if a
        lone request cannot grow."""
        changed = False
        for i, s in enumerate(self.slots):
            # slots mid-chunked-prefill hold pages for their whole prompt
            # already and are not in the decode batch — nothing to grow
            if not s.active or s.ctx_len >= self.max_len or s.prefill_toks:
                continue
            cow, cow_done = None, False
            while True:
                try:
                    if not cow_done:
                        cow = self.alloc.cow_page(i, s.ctx_len)
                        cow_done = True
                    newp = self.alloc.extend(i, s.ctx_len + 1)
                    break
                except MemoryError:
                    if not self._evict_victim(protect=i):
                        raise
            if cow is not None:
                old, new = cow
                self.block_table[i, s.ctx_len // self.page_size] = new
                # device-side page copy: fork op with src == dst slot
                self.cache = self._fork(
                    self.cache, jnp.asarray(i, jnp.int32),
                    jnp.asarray(i, jnp.int32), jnp.asarray(old, jnp.int32),
                    jnp.asarray(new, jnp.int32))
                changed = True
                self._track_peak()
            if newp is not None:
                n_owned = len(self.alloc.owned[i])
                self.block_table[i, n_owned - 1] = newp
                changed = True
                self._track_peak()
        if changed:
            self._mark_table_dirty()

    def _harvest(self) -> bool:
        """Read back and commit the decode step dispatched LAST step(). One
        `jax.device_get` on the whole (toks, lps) pair replaces the per-slot
        scalar syncs the old loop paid — and because the read happens a full
        step after the dispatch, the host's planning for step N+1 overlapped
        the device's work on step N."""
        if self._pending_decode is None:
            return False
        commits, toks_d, lps_d = self._pending_decode
        self._pending_decode = None
        rows = self._pending_rows
        self._pending_rows = None
        with trace.span("engine.harvest", engine=self.name):
            with trace.span("engine.readback", engine=self.name):
                if rows is None:
                    toks, lps = jax.device_get((toks_d, lps_d))
                else:
                    toks, lps, n = jax.device_get((toks_d, lps_d, rows[0]))
                    rows[1]["expert_rows"] = n.tolist()
            for i in commits:
                # in-engine nothing deactivates a slot between dispatch and
                # harvest; the guard covers direct _evict_victim calls
                # (tests)
                if self.slots[i].active:
                    self._commit(i, int(toks[i]), float(lps[i]))
        return True

    def _plan_decode(self, active_ids: List[int]) -> StepPlan:
        """Build this step's decode plan with numpy only. Token-independent
        slot state advances here (ctx_len, pending-suffix pops) — the
        values the eventual `_commit` termination checks read are exactly
        what the old inline loop saw; only the sampled token itself arrives
        later, at harvest."""
        last = np.zeros((self.max_batch, 1), np.int32)
        mask = np.zeros((self.max_batch,), bool)
        mask[active_ids] = True
        live = self._live_pages(active_ids) \
            if self.kv_backend == "paged" else 0
        commits: List[int] = []
        for i in active_ids:
            s = self.slots[i]
            if s.pending:
                last[i, 0] = s.pending[0]
            elif s.tokens:
                last[i, 0] = s.tokens[-1]
            s.ctx_len = min(s.ctx_len + 1, self.max_len)
            if s.pending:
                s.pending.pop(0)
                if s.pending:
                    continue            # still teacher-forcing the suffix
            commits.append(i)
        return StepPlan(active_ids=active_ids, last=last, mask=mask,
                        live=live, commits=commits)

    def _dispatch_decode(self, plan: StepPlan):
        """The "run" half: ONE fused device call (decode + split + sample +
        logprob), cache donated, readback deferred to the next step's
        harvest. The PRNG key chains through the device so no sync is
        needed to keep `self.key`'s split stream identical to the eager
        loop's. With the tracer on, the open `engine.decode` span
        (`_decode_attrs`) gets the step's `expert_rows` at that harvest."""
        if self.kv_backend == "paged":
            # KV read traffic this step: mapped pages per active slot times
            # per-page pool+scale bytes (repeated-block DMAs past the live
            # range are elided by the kernel's clamped index_map)
            self.kv_bytes_read += self._page_kv_bytes * sum(
                -(-self.slots[i].ctx_len // self.page_size)
                for i in plan.active_ids)
            toks, lps, self.key, self.cache, rows = self._decode_run(
                plan.live, self.params, jnp.asarray(plan.last), self.cache,
                jnp.asarray(plan.mask), self.key)
            self._pending_rows = None if self._decode_attrs is None \
                or rows is None else (rows, self._decode_attrs)
        else:
            toks, lps, self.key, self.cache = self._decode_run(
                self.params, jnp.asarray(plan.last), self.cache,
                jnp.asarray(plan.mask), self.key)
        self._pending_decode = (plan.commits, toks, lps)

    def _run_ingest(self) -> bool:
        """Batched ragged chunk ingest: EVERY ingesting slot's next chunk in
        one `prefill_ragged_paged` dispatch (qo_indptr-style rows of
        (slot, offset, len)). Slots whose final chunk lands here draw their
        first token eagerly, most urgent first, and join the decode batch
        next step."""
        ing = [i for i, s in enumerate(self.slots)
               if s.active and s.prefill_toks]
        if not ing:
            return False
        # finish draws happen in this order: priority first, then
        # admission age
        ing.sort(key=lambda j: (-self.slots[j].priority,
                                self.slots[j].arrival))
        C = self.prefill_chunk
        rows: List[Tuple[int, int, List[int]]] = []
        with trace.span("engine.ingest", engine=self.name) as sp:
            for i in ing:
                s = self.slots[i]
                chunk = s.prefill_toks[:C]
                s.prefill_toks = s.prefill_toks[C:]
                rows.append((i, s.ctx_len, chunk))
                s.ctx_len += len(chunk)
            if sp:
                sp.attrs["rows"] = [(off, len(chunk))
                                    for _, off, chunk in rows]
            R = 1
            while R < len(rows):
                R *= 2                  # bucket rows (lo=1) to bound variants
            toks = np.zeros((R, C), np.int32)
            # padding rows carry the out-of-range slot `max_batch`: their
            # cache scatters drop and their gathers clip to a live row and
            # are discarded
            slots = np.full((R,), self.max_batch, np.int32)
            offs = np.zeros((R,), np.int32)
            lens = np.zeros((R,), np.int32)
            for r, (i, off, chunk) in enumerate(rows):
                toks[r, :len(chunk)] = chunk
                slots[r], offs[r], lens[r] = i, off, len(chunk)
            live = self._chunk_live(max(off + len(chunk)
                                        for _, off, chunk in rows))
            self.kv_bytes_read += self._page_kv_bytes * sum(
                -(-(off + len(chunk)) // self.page_size)
                for _, off, chunk in rows)
            logits, self.cache, n = self._prefill_ragged(
                live, self.params, jnp.asarray(toks), self.cache,
                jnp.asarray(slots), jnp.asarray(offs), jnp.asarray(lens))
            if sp:
                self._latent_pages(sp, [off + len(chunk)
                                        for _, off, chunk in rows])
                if n is not None:
                    # repro-analysis: disable=RA103 reason=read only while the tracer is on
                    sp.attrs["expert_rows"] = jax.device_get(n).tolist()
        # final chunk landed: first token — the same (1, V) sample a
        # monolithic add_request takes (a row of the batched logits is
        # bitwise that row ingested alone)
        done = [(r, i) for r, (i, _, _) in enumerate(rows)
                if self.slots[i].active and not self.slots[i].prefill_toks]
        if not done:
            return True
        with trace.span("engine.first_token", engine=self.name):
            draws: List[Tuple[int, object, object]] = []
            for r, i in done:
                self.key, sub = jax.random.split(self.key)
                tok = sample(logits[r:r + 1], sub, self.sampler)
                lp = token_logprob(logits[r:r + 1], tok)
                draws.append((i, tok, lp))
            with trace.span("engine.readback", engine=self.name):
                # repro-analysis: disable=RA103 reason=one batched readback for every first token finishing this step (was 2 scalar syncs per row)
                flat = jax.device_get([(t, l) for _, t, l in draws])
            for (i, _, _), (tok_h, lp_h) in zip(draws, flat):
                self._commit(i, int(tok_h[0]), float(lp_h[0]))
        return True

    def step(self) -> bool:
        """One engine step, structured plan/run: (0) harvest last step's
        decode readback, (1) host-plan everything — page growth/COW,
        eviction, ragged ingest rows, decode inputs — with numpy, (2) push
        the block table at most once, (3) dispatch at most one batched
        ragged ingest call and one fused decode call, deferring the decode
        readback to the next step. Returns True if work was done (including
        a harvest-only step that drains the last in-flight decode).

        Chunked engines ingest through a single ragged device call a step:
        every ingesting slot advances one chunk, so decode latency between
        steps stays bounded by one chunk of prefill compute and a long
        prompt cannot head-of-line-block the batch. Slots whose final chunk
        lands this step sample their first token eagerly (finish draws,
        then the decode split) and join the decode batch next step.

        Slots with a pending suffix (fork path, monolithic engines) are
        teacher-forced: the step feeds `pending[0]` instead of the last
        sampled token and the sampled output is discarded until the suffix
        is exhausted — the logits after the final suffix token seed the
        first real sample.

        The step's span (`engine.step`, with the active slots at its start)
        holds one child per phase: harvest (with its readback), plan (page
        growth and the decode plan), table push, ingest, first token (with
        its readback) and decode dispatch."""
        with trace.span("engine.step", engine=self.name,
                        max_batch=self.max_batch) as sp:
            if self.step_hook is not None:
                # fault injection point: may stall (straggler), cancel a
                # slot (mid-decode crash), squeeze the page pool, or raise
                # EngineCrash — all before this step's harvest/plan/dispatch
                self.step_hook(self)
            if sp:
                sp.attrs["active"] = sum(1 for s in self.slots if s.active)
            worked = self._harvest()
            if not any(s.active for s in self.slots):
                return worked
            with trace.span("engine.plan", engine=self.name):
                active = [i for i, s in enumerate(self.slots)
                          if s.active and not s.prefill_toks]
                if self.kv_backend == "paged" and active:
                    self._grow_pages()      # may evict, incl. mid-ingest slots
                    active = [i for i, s in enumerate(self.slots)
                              if s.active and not s.prefill_toks]
                plan = self._plan_decode(active) if active else None
            if self.kv_backend == "paged":
                # ONE table push per step, before the first dispatch that reads
                # it. Finish commits below may free rows again; those stale
                # entries ride until the next step's push — decode writes are
                # active-masked, so they cannot touch a COW sibling's pages.
                with trace.span("engine.table_push", engine=self.name):
                    self._sync_table()
            if self.prefill_chunk:
                worked = self._run_ingest() or worked
            if plan is not None:
                with trace.span("engine.decode", engine=self.name) as sp:
                    if sp:
                        sp.attrs["ctx"] = [self.slots[i].ctx_len
                                           for i in plan.active_ids]
                        self._latent_pages(sp, sp.attrs["ctx"])
                        self._pages_per_step(sp, plan.live)
                    self._decode_attrs = sp.attrs if sp else None
                    self._dispatch_decode(plan)
                worked = True
            return worked

    def warmup(self, *, max_context: Optional[int] = None,
               prompt_lens: Tuple[int, ...] = (),
               ingest_rows: Tuple[int, ...] = (1,)) -> int:
        """Precompile the step loop's jit variants on an IDLE engine so the
        first serving window is not dominated by XLA compiles (the paged
        backend's per-live-width variants otherwise all compile inside the
        measured window). Returns the number of variant dispatches made.

        max_context bounds the decode live-width buckets to warm (default
        max_len); prompt_lens warms monolithic prefill buckets (dense and
        non-chunked paged engines); ingest_rows warms batched ragged ingest
        row-bucket variants (chunked paged engines), and the one-row bucket
        that `prefill_prefix` ingests through is always warmed. All warm
        dispatches are state no-ops: all-inactive masks and out-of-range
        slot rows drop every write, and `self.key` is never advanced."""
        assert not any(s.active or s.parked for s in self.slots), \
            "warmup requires an idle engine"
        key0 = jax.random.PRNGKey(0)    # throwaway: self.key stays untouched
        count = 0
        last = np.zeros((self.max_batch, 1), np.int32)
        mask = np.zeros((self.max_batch,), bool)
        if self.kv_backend == "paged":
            lives = sorted({self._chunk_live(end) for end in
                            range(1, min(max_context or self.max_len,
                                         self.max_len) + 1)})
            for live in lives:
                _, _, _, self.cache, _ = self._decode_run(
                    live, self.params, jnp.asarray(last), self.cache,
                    jnp.asarray(mask), key0)
                count += 1
            if self.prefill_chunk:
                rbs = {1}
                for n in ingest_rows:
                    r = 1
                    while r < min(n, self.max_batch):
                        r *= 2
                    rbs.add(r)
                sent = np.full((max(rbs),), self.max_batch, np.int32)
                for rb in sorted(rbs):
                    for live in lives:
                        _, self.cache, _ = self._prefill_ragged(
                            live, self.params,
                            jnp.zeros((rb, self.prefill_chunk), jnp.int32),
                            self.cache, jnp.asarray(sent[:rb]),
                            jnp.zeros((rb,), jnp.int32),
                            jnp.zeros((rb,), jnp.int32))
                        count += 1
            if prompt_lens and not self.prefill_chunk:
                for S in sorted({min(_bucket(n), self.max_len)
                                 for n in prompt_lens}):
                    self._sync_table()
                    _, self.cache = self._prefill_paged(
                        self.params, jnp.zeros((1, S), jnp.int32),
                        self.cache, jnp.asarray(0, jnp.int32),
                        jnp.asarray(0, jnp.int32))
                    count += 1
            # fork/COW page copy: one shape variant total (src == dst is a
            # value no-op on an idle engine)
            self.cache = self._fork(
                self.cache, jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32))
            count += 1
            if self.host_swap:
                # swap-in (promote) variants: one per upload-width bucket.
                # Padding page ids (n_pages) drop every pool write and the
                # out-of-range slot drops the lengths write, so warm
                # promotes are state no-ops.
                for U in sorted({_pow2_bucket(u, self.pages_per_seq)
                                 for u in range(1, self.pages_per_seq + 1)}):
                    payloads = [
                        {k: jnp.zeros((seg[k].shape[0], U)
                                      + seg[k].shape[2:], seg[k].dtype)
                         for k in seg}
                        for seg in self.cache["segments"]
                        if pc.is_pool(seg)]
                    self.cache = self._promote(
                        self.cache,
                        jnp.full((U,), self.n_pages, jnp.int32), payloads,
                        jnp.asarray(self.max_batch, jnp.int32),
                        jnp.asarray(0, jnp.int32))
                    count += 1
        else:
            _, _, _, self.cache = self._decode_run(
                self.params, jnp.asarray(last), self.cache,
                jnp.asarray(mask), key0)
            count += 1
            for S in sorted({min(_bucket(n), self.max_len)
                             for n in prompt_lens}):
                one = transformer.init_cache(self.cfg, 1, self.max_len)
                _, one = self._prefill(self.params,
                                       jnp.zeros((1, S), jnp.int32), one,
                                       jnp.asarray([0], jnp.int32))
                self.cache = self._insert(self.cache, one, 0)
                count += 1
        if prompt_lens:
            # offline scoring shares the serving buckets; warm it alongside
            # so a first score() call does not compile mid-window
            for S in sorted({min(_bucket(n), self.max_len)
                             for n in prompt_lens}):
                self._score(self.params,
                            jnp.full((S,), self.eos_id, jnp.int32))
                count += 1
        return count

    # ------------------------------------------------------------------
    def generate(self, prompts: List[List[int]], max_new: int = 128,
                 priorities: Optional[List[int]] = None,
                 deadline_s: Optional[float] = None
                 ) -> List[Tuple[List[int], List[float]]]:
        """Batch-generate; returns (tokens, logprobs) per prompt.
        `priorities` (optional, per prompt) orders preemption under memory
        pressure — higher survives longer. `deadline_s` (perf_counter
        timestamp) caps the run: once passed, every in-flight request is
        cancelled and returns whatever it generated so far."""
        priorities = priorities or [0] * len(prompts)
        assert len(priorities) == len(prompts), \
            "priorities must match prompts one-to-one"
        pending = [_Resume(req_id=i, prompt=p, max_new=max_new,
                           carry_tokens=[], carry_lps=[], priority=pr)
                   for i, (p, pr) in enumerate(zip(prompts, priorities))]
        return self._run(pending, deadline_s=deadline_s)

    def generate_fanout(self, prefix: List[int],
                        suffixes: List[List[int]], max_new: int = 128,
                        priority: int = 0,
                        deadline_s: Optional[float] = None
                        ) -> List[Tuple[List[int], List[float]]]:
        """Expand one shared prefix N ways (the PICE sketch fan-out: every
        ensemble member / parallel expansion segment repeats the same
        (query, sketch) prefix). The prefix is prefilled ONCE and each
        expansion forks a copy-on-write block-table row off it, so the pool
        holds one prefix instead of N; per-group suffixes are teacher-forced
        before sampling. Falls back to independent submissions on the dense
        backend or a 1-slot engine."""
        if self.kv_backend != "paged" or self.max_batch < 2:
            return self.generate([list(prefix) + list(s) for s in suffixes],
                                 max_new=max_new,
                                 priorities=[priority] * len(suffixes),
                                 deadline_s=deadline_s)
        p_slot = self.prefill_prefix(prefix)
        pending = [_Resume(req_id=i, prompt=list(prefix) + list(sfx),
                           max_new=max_new, carry_tokens=[], carry_lps=[],
                           share_from=p_slot, suffix=list(sfx),
                           priority=priority)
                   for i, sfx in enumerate(suffixes)]
        try:
            return self._run(pending, deadline_s=deadline_s)
        finally:
            self.release_prefix(p_slot)

    def _run(self, pending: List[_Resume],
             deadline_s: Optional[float] = None
             ) -> List[Tuple[List[int], List[float]]]:
        n = len(pending)
        for r in pending:
            # fresh submissions must not inherit a stale admission stamp
            # from an earlier run that reused the same req_id (eviction
            # resumes within THIS run still keep their original stamp)
            self._t_admit.pop(r.req_id, None)
        # register this run's req_ids so admission-stamp pruning never drops
        # a TTFT stamp for work that is merely queued or evicted-and-waiting
        mine = {r.req_id for r in pending}
        self._inflight |= mine
        try:
            return self._run_inner(pending, n, deadline_s)
        finally:
            self._inflight -= mine

    # ------------------------------------------------------------------
    # Request-handle admission API. `try_admit` is ONE admission attempt for
    # a queued (fresh or preempted) request and `drain_resumes` hands back
    # the work eviction preempted — the synchronous `_run` loop below and
    # the async serving front-end (serving/frontend.py) drive the engine
    # through these same two calls, so a multiplexed stream of requests
    # takes exactly the admission path a dedicated run would.
    # ------------------------------------------------------------------
    def try_admit(self, r: _Resume) -> Optional[int]:
        """Attempt to admit `r`. Returns the slot index on success, or None
        when the request must wait for slots/pages to free. Raises
        MemoryError when the engine is IDLE and the request still cannot
        fit: no running work will ever free enough pool.

        May mutate `r`: an injected swap-upload loss (`swap_fault_hook`)
        degrades a host-tier resume to the evict-and-replay path — r.prompt
        and the carried tokens are exactly what a non-swap eviction queued,
        so the replay is the same bit-identical path; a fork resume whose
        parked prefix is gone falls back to a monolithic prompt."""
        if not self.free_slots():
            return None
        if r.swap is not None and self.swap_fault_hook is not None \
                and self.swap_fault_hook(r.req_id):
            self.alloc.drop_hosted(r.req_id)
            r.swap = None
            self.swap_losses += 1
        if r.swap is not None:
            # demoted request: promote its host-tier pages back and
            # re-enter decode directly (no prefill replay)
            if not self.can_admit_swap(r.req_id):
                if not any(s.active for s in self.slots):
                    raise MemoryError(
                        f"request {r.req_id} cannot fit in the page pool")
                return None                      # wait for pages to free
            with trace.span("engine.swap_promote", engine=self.name):
                return self._admit_swapped(r)
        if r.share_from >= 0 and not self.slots[r.share_from].parked:
            r.share_from, r.suffix = -1, []       # prefix gone: monolithic
        if r.share_from >= 0:
            ok = self.can_admit_fork(
                r.share_from, len(r.suffix) + len(r.carry_tokens))
        else:
            ok = self.can_admit(len(r.prompt) + len(r.carry_tokens))
        if not ok:
            if not any(s.active for s in self.slots):
                raise MemoryError(
                    f"request {r.req_id} cannot fit in the page pool")
            return None                          # wait for pages to free
        return self.add_request(
            r.req_id, r.prompt, r.max_new,
            carry_tokens=r.carry_tokens, carry_lps=r.carry_lps,
            share_from=r.share_from if r.share_from >= 0 else None,
            suffix=r.suffix, priority=r.priority)

    def drain_resumes(self) -> List[_Resume]:
        """Take the work eviction preempted, in re-admission order: oldest
        victim first (eviction queued victims youngest-first as it found
        them). Callers put these at the HEAD of their pending queue so
        preempted work re-enters before fresh submissions."""
        out = list(reversed(self._resume_queue))
        self._resume_queue.clear()
        return out

    def _run_inner(self, pending: List[_Resume], n: int,
                   deadline_s: Optional[float] = None
                   ) -> List[Tuple[List[int], List[float]]]:
        results: Dict[int, Tuple[List[int], List[float]]] = {}
        submitted: Dict[int, int] = {}          # req_id -> slot
        while pending or any(s.active for s in self.slots):
            while pending and self.free_slots():
                slot = self.try_admit(pending[0])
                if slot is None:
                    break                        # wait for pages to free
                r = pending.pop(0)
                submitted[r.req_id] = slot
            self.step()
            if deadline_s is not None and time.perf_counter() > deadline_s \
                    and (pending or any(s.active for s in self.slots)):
                # deadline blown: cancel every in-flight request (partial
                # tokens are collected below) and settle never-admitted /
                # evicted work with whatever it carried
                for rid, sl in list(submitted.items()):
                    if self.slots[sl].active:
                        self.cancel(rid)
                        self.deadline_cancels += 1
                pending[:0] = self.drain_resumes()
                for r in pending:
                    if r.swap is not None:
                        self.alloc.drop_hosted(r.req_id)
                    results[r.req_id] = (list(r.carry_tokens),
                                         list(r.carry_lps))
                    self.deadline_cancels += 1
                pending.clear()
            done = [rid for rid, sl in submitted.items()
                    if not self.slots[sl].active]
            for rid in done:
                sl = submitted.pop(rid)
                s = self.slots[sl]
                s.req_id = -1
                if s.evicted:
                    s.evicted = False
                    continue                     # resubmitted via _resume_queue
                results[rid] = (list(s.tokens), list(s.logprobs))
            # preempted work goes to the queue head, oldest first
            pending[:0] = self.drain_resumes()
        return [results[i] for i in range(n)]

    def score(self, tokens: List[int]) -> Tuple[float, np.ndarray]:
        """Mean token logprob of a sequence under this model (perplexity).

        The scoring buffer is clamped to max_len: the unbounded power-of-two
        bucket used to compile (and OOM) arbitrarily large variants for one
        long input. Sequences beyond max_len are scored on their TAIL — the
        same most-recent-context convention `_pad_prompt` applies."""
        S = min(_bucket(len(tokens)), self.max_len)
        toks = tokens[-S:]
        arr = np.full((S,), self.eos_id, np.int32)
        arr[:len(toks)] = toks
        _, gold_d = self._score(self.params, jnp.asarray(arr))
        # repro-analysis: disable=RA103 reason=offline scoring API; the readback is the result, not on the step loop
        gold = jax.device_get(gold_d)[:max(len(toks) - 1, 1)]
        return float(np.mean(gold)), gold
