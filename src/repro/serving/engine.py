"""Continuous-batching inference engine over a paged KV cache (the
real-compute rollout backend).

One engine = one rollout instance (or one local seeding engine on the
training cluster).  Global-attention KV lives in a shared page pool with
per-request block tables (``repro.models.kv_cache.PagedKVAllocator``);
per-slot state (ring buffers, SSM states, sampling buffers) is bounded by
``max_batch`` decode slots.  The scheduler:

  * decodes ``horizon`` tokens per dispatch inside ONE jitted
    ``jax.lax.scan`` — sampling, EOS/max_total stopping, and the
    token-feedback loop all run on device, and the host syncs once per
    horizon instead of once per token;
  * keeps scheduler state device-resident: the last-token / sampling-key /
    active / max-total buffers and the block table live on device and are
    re-uploaded only when the host mutates them (admission, completion,
    migration, page allocation) — steady-state decode transfers nothing
    host->device;
  * batches prefill across waiting requests in fixed token-budget chunks,
    interleaved with decode steps (one chunk per request per ``step()``;
    long prompts on all-global models are split across steps);
  * shares GRPO group prompts: ``add_group`` prefill's the common prompt
    ONCE, ref-counts its pages, and forks the block table copy-on-write to
    every sibling — group rollout does 1 prompt prefill instead of G;
  * admits by capacity (``AdmissionError``), not by a slab-length assert:
    responses may grow past any fixed slab because the pool allocates (and,
    if needed, grows) pages on demand;
  * attends through the ragged paged Pallas kernels by default
    (``use_pallas``; interpret mode off-TPU, so CPU CI runs the identical
    kernel): decode streams only each slot's live pages (lengths = the
    device-resident ``pos`` buffer) and chunked prefill streams only live
    prefix pages + the causal chunk — the dense ``gather_pages`` oracle
    path survives for parity testing only.

Horizon contract: before each fused dispatch the host reserves the whole
write window [ctx_len, ctx_len + H) per active slot in one allocator call
(``PagedKVAllocator.reserve_decode``: capacity + all COW copies up front),
so no allocator interaction can interrupt the loop.  Rows that finish
mid-horizon freeze their ``pos``, park their token buffer at
``TOKEN_SENTINEL``, and route subsequent KV writes to the garbage page via
the in-loop active mask.  ``swap_weights`` and migration happen between
``step()`` calls, i.e. at horizon boundaries — ``weight_version`` is
constant within a horizon by construction.

Token-level semantics needed by RLBoost:
  * every generated token (and its behavior logprob) is emitted to the caller
    as it is produced — the rollout manager collects at token granularity;
  * ``add_request`` accepts prompt+partial tokens, so migrated requests
    continue with a single prefill (paper §4.2);
  * sampling keys are (request, position)-addressed => migration is bit-exact
    (and H > 1 is bit-exact vs. H = 1 by construction: the scan body IS the
    single-step decode computation).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.tokenizer import EOS, PAD
from repro.models import kv_cache as kvc
from repro.models.kv_cache import GARBAGE_PAGE, OutOfPages, PagedKVAllocator
from repro.models.transformer import (CPU_RT, forward, logits_from_hidden)
from repro.obs.tracer import NULL_TRACER
from repro.rl.sampler import sample_token

_JIT_CACHE: Dict = {}
_JIT_STATS = {"compiles": 0, "padded_reuse": 0, "chunk_pad_reuse": 0}

# prefill chunks are right-padded up to a multiple of the kernel query tile,
# so the ragged prefill kernel always lands on a compiled [*, C] grid (and
# the closure-cache holds a handful of C values instead of every power of 2)
PREFILL_TILE = 128


def _serve_pallas_default() -> bool:
    """Serving hot-path default: the ragged Pallas kernels (interpret mode
    off-TPU).  ``RLBOOST_SERVE_PALLAS=0`` forces the dense gather_pages
    oracle path (parity tests / debugging)."""
    return os.environ.get("RLBOOST_SERVE_PALLAS", "1") != "0"

# parked in the device token buffer for empty / finished rows — a finished
# row's stale last token must never leak into a reused batch row
TOKEN_SENTINEL = PAD


class AdmissionError(RuntimeError):
    """Request rejected at admission (engine full / over capacity)."""


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _tile_bucket(n: int, tile: int = PREFILL_TILE) -> int:
    """Round ``n`` up to a multiple of ``tile`` (kernel-grid friendly)."""
    return max(tile, -(-n // tile) * tile)


def jit_cache_stats() -> Dict[str, int]:
    """Compile-churn counters (regression-tested): total closures compiled,
    block-table-width lookups served by a wider already-compiled one
    (``padded_reuse``), and prefill dispatches whose 128-tile-bucketed
    chunk width reused an existing closure (``chunk_pad_reuse``)."""
    return dict(_JIT_STATS, entries=len(_JIT_CACHE))


def _padded_width(family: Tuple, needed: int) -> Optional[int]:
    """Smallest already-compiled block-table width >= ``needed`` for this
    closure family.  Block tables pad with the garbage page, so any wider
    compiled closure computes the identical result — reusing it avoids
    compiling every power-of-two width as requests grow and shrink."""
    best = None
    for k in _JIT_CACHE:
        if k[:-1] == family and k[-1] >= needed:
            if best is None or k[-1] < best:
                best = k[-1]
    return best


# --------------------------------------------------------------------------- #
# jitted stages (cache keyed on the temperature VALUE — two engines with
# different positive temperatures must not share compiled closures)
# --------------------------------------------------------------------------- #
def _prefill_family(cfg: ModelConfig, n: int, C: int,
                    use_pallas: bool) -> Tuple:
    return ("prefill", cfg.name, cfg.d_model, n, C, use_pallas)


def _get_prefill_fn(cfg: ModelConfig, rt, n: int, C: int, nb: int):
    """Batched chunk prefill: n rows of C tokens against paged prefixes."""
    key = _prefill_family(cfg, n, C, rt.use_pallas) + (nb,)
    if key not in _JIT_CACHE:
        def engine_prefill(params, cache, slot_idx, tokens, mask, offsets,
                           bt):
            rows = kvc.gather_rows(cache, slot_idx)
            out = forward(params, cfg, rt, tokens=tokens, seq_mask=mask,
                          cache=rows, mode="prefill",
                          paged={"block_tables": bt, "q_offsets": offsets})
            cache = kvc.scatter_rows(cache, out["cache"], slot_idx)
            lens = mask.astype(jnp.int32).sum(-1)
            last = jnp.clip(lens - 1, 0)
            hidden_last = jnp.take_along_axis(
                out["hidden"], last[:, None, None], axis=1)[:, 0]
            logits = logits_from_hidden(params, cfg, hidden_last)  # [n, V]
            return cache, logits
        _JIT_STATS["compiles"] += 1
        _JIT_CACHE[key] = jax.jit(engine_prefill, donate_argnums=(1,))
    return _JIT_CACHE[key]


def _decode_family(cfg: ModelConfig, temperature: float, horizon: int,
                   use_pallas: bool = True) -> Tuple:
    return ("decode", cfg.name, cfg.d_model, temperature, horizon,
            use_pallas)


def _get_decode_fn(cfg: ModelConfig, rt, nb: int, temperature: float,
                   horizon: int):
    """Fused decode horizon: ``horizon`` tokens per dispatch in one scan.

    Carry = (cache, last_tokens [B], active [B]).  Each step is exactly the
    single-step decode computation (forward, logits, (request, position)-
    keyed sampling, logprob), so H > 1 is bit-exact vs. H = 1.  Rows that
    hit EOS or max_total drop out of the active mask: their ``pos``
    freezes, their block-table row is masked to the garbage page (all
    subsequent KV writes land there), and their carried token parks at
    ``TOKEN_SENTINEL``.  Outputs are [B, H] token / logprob matrices plus
    the [B, H] emission mask (row was active at that step).
    """
    key = _decode_family(cfg, temperature, horizon, rt.use_pallas) + (nb,)
    if key not in _JIT_CACHE:
        t = temperature if temperature > 0 else 1.0

        def engine_decode(params, cache, tokens, rkeys, active, max_total,
                          bt):
            def body(carry, _):
                cache, tokens, active = carry
                old_pos = cache["pos"]
                bt_step = jnp.where(active[:, None], bt,
                                    jnp.int32(GARBAGE_PAGE))
                out = forward(params, cfg, rt, tokens=tokens,
                              cache=cache, mode="decode",
                              paged={"block_tables": bt_step})
                logits = logits_from_hidden(params, cfg, out["hidden"][:, 0])
                nxt = sample_token(logits, rkeys, old_pos, temperature)
                lse = jax.nn.logsumexp(logits / t, axis=-1)
                lp = jnp.take_along_axis(
                    logits / t, nxt[:, None], axis=-1)[:, 0] - lse
                cache = out["cache"]
                cache["pos"] = jnp.where(active, cache["pos"], old_pos)
                # host-side done condition, verbatim: after appending this
                # token the request holds old_pos + 2 tokens (old_pos KV'd
                # + the input token + this sample)
                done = (nxt == EOS) | (old_pos + 2 >= max_total)
                new_active = active & ~done
                new_tokens = jnp.where(new_active, nxt,
                                       jnp.int32(TOKEN_SENTINEL))
                return (cache, new_tokens, new_active), (nxt, lp, active)

            (cache, tokens, active), (toks, lps, em) = jax.lax.scan(
                body, (cache, tokens, active), None, length=horizon)
            return cache, tokens, active, toks.T, lps.T, em.T

        _JIT_STATS["compiles"] += 1
        _JIT_CACHE[key] = jax.jit(engine_decode, donate_argnums=(1, 2, 4))
    return _JIT_CACHE[key]


def _get_batch_sample_fn(temperature: float, m: int):
    """First-token sampling for ``m`` prefill-completed rows in ONE call
    (was one jit dispatch per GRPO group member)."""
    key = ("sample", temperature, m)
    if key not in _JIT_CACHE:
        def engine_sample(logits, key_data, pos):
            t = temperature if temperature > 0 else 1.0
            nxt = sample_token(logits, key_data, pos, temperature)
            lse = jax.nn.logsumexp(logits / t, axis=-1)
            lp = jnp.take_along_axis(
                logits / t, nxt[:, None], axis=-1)[:, 0] - lse
            return nxt, lp
        _JIT_STATS["compiles"] += 1
        _JIT_CACHE[key] = jax.jit(engine_sample)
    return _JIT_CACHE[key]


def _get_copy_fn(cfg: ModelConfig, m: int):
    key = ("copy", cfg.name, cfg.d_model, m)
    if key not in _JIT_CACHE:
        def engine_copy(cache, src, dst):
            return kvc.copy_pool_pages(cache, src, dst)
        _JIT_STATS["compiles"] += 1
        _JIT_CACHE[key] = jax.jit(engine_copy, donate_argnums=(0,))
    return _JIT_CACHE[key]


# --------------------------------------------------------------------------- #
@dataclass
class SlotState:
    req_id: int
    key_data: np.ndarray            # [2] uint32 raw key
    tokens: List[int]               # prompt + generated (absolute history)
    n_prompt: int
    max_total: int
    last_token: int
    table: List[int]                # block table (page ids)
    ctx_len: int                    # tokens whose KV is in the pool


@dataclass
class _WaitRow:
    """One prefill context: a request's prompt+partial, or a GRPO group's
    shared prompt.  ``members`` are the requests that will consume it."""
    token_ids: List[int]
    table: List[int]
    members: List[Tuple[int, np.ndarray, int, int, int]]
    # (req_id, key_data, max_total, n_prompt, slot)
    queued: object                  # ``engine.queued``: admission -> first token
    done: int = 0                   # tokens already prefilled (chunking)


@dataclass
class StepEvent:
    req_id: int
    token: int
    logprob: float
    finished: bool
    weight_version: int = 0     # weights that produced this token


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 slab_len: int = 256, temperature: float = 1.0,
                 weight_version: int = 0, page_size: int = 16,
                 prefill_chunk: int = 256, max_context: Optional[int] = None,
                 horizon: int = 1, use_pallas: Optional[bool] = None,
                 max_pool_pages: Optional[int] = None, tracer=None):
        """``slab_len`` sizes the initial pool (max_batch * slab_len tokens)
        and the local-attention ring width; unlike the old dense slab it is
        NOT a hard length cap — pages are allocated (and the pool grown) on
        demand, bounded only by ``max_context`` when set.

        ``horizon`` is the number of tokens one ``step()`` decodes per
        active request inside a single fused dispatch (H = 1 reproduces
        per-token stepping bit-exactly; larger H amortizes the per-dispatch
        host<->device cost over H tokens).

        ``use_pallas`` selects the attention hot path: True (the default,
        overridable via ``RLBOOST_SERVE_PALLAS=0``) runs the ragged paged
        Pallas kernels — decode and chunked prefill both read only live KV
        pages, in interpret mode off-TPU; False keeps the dense
        gather_pages oracle path (bit-parity testing)."""
        self.cfg = cfg
        self.params = params
        # flight recorder: engines run REAL compute, so their tracer (if
        # any) must be on a wall clock — the sim's event-clock tracer paces
        # the modeled time, not this work
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.trace_lane = "engine"
        if use_pallas is None:
            use_pallas = _serve_pallas_default()
        self.use_pallas = bool(use_pallas)
        self.rt = dataclasses.replace(CPU_RT, use_pallas=self.use_pallas)
        self.weight_version = weight_version
        self.max_batch = max_batch
        self.slab_len = slab_len
        self.page_size = page_size
        self.prefill_chunk = prefill_chunk
        self.temperature = temperature
        self.max_context = max_context
        self.horizon = max(int(horizon), 1)
        mixers = cfg.layer_mixers()
        # chunked (multi-step) prompt prefill needs stateless-across-chunks
        # layers; models with SSM/ring state prefill each context in one chunk
        self._chunkable = all(m == "global" for m in mixers)
        num_pages = max(2 * (max_batch * slab_len) // page_size, 8) + 1
        if max_pool_pages is not None:
            num_pages = max(min(num_pages, int(max_pool_pages)), 2)
        self.max_pool_pages = max_pool_pages
        self.alloc = PagedKVAllocator(num_pages, page_size,
                                      max_pages=max_pool_pages)
        self.cache = kvc.init_paged_cache(cfg, max_batch, num_pages,
                                          page_size, ring_len=slab_len,
                                          dtype=jnp.float32)
        self.slots: List[Optional[SlotState]] = [None] * max_batch
        self._reserved: Dict[int, int] = {}     # req_id -> slot (waiting)
        self.waiting: List[_WaitRow] = []
        # host mirrors of the device-resident decode state (authoritative
        # only while ``_state_dirty``; re-uploaded once, then the fused
        # loop's carried outputs ARE the state)
        self.tokens_buf = np.full((max_batch,), TOKEN_SENTINEL, np.int32)
        self.keys_buf = np.zeros((max_batch, 2), np.uint32)
        self.maxtot_buf = np.zeros((max_batch,), np.int32)
        self._dev_tokens = None
        self._dev_keys = None
        self._dev_active = None
        self._dev_maxtot = None
        self._state_dirty = True
        self._bt_dev = None                     # cached device block table
        self._bt_width = 0
        self._bt_dirty = True
        # perf counters (prefix-sharing / dedup / transfer visibility)
        self.n_prefills = 0                     # context prefills (rows)
        self.n_prefill_tokens = 0
        self.n_shared_prompt_tokens = 0         # tokens NOT re-prefilled
        self.n_decode_dispatches = 0            # fused horizon launches
        self.n_state_uploads = 0                # host->device state syncs
        self.n_bt_uploads = 0                   # host->device block tables
        self.n_kv_import_tokens = 0             # context resumed w/o prefill

    # ------------------------------------------------------------------ #
    def swap_weights(self, params, version: int):
        """Install a new weight version between scheduler steps (i.e. at a
        horizon boundary — never inside a fused decode dispatch, so every
        token of a horizon carries the same ``weight_version``).

        In-flight requests are NOT dropped: their KV pages stay valid (KV
        was computed under older weights — that is the staleness the
        version stamps expose) and decoding continues under the new params
        from the next ``step()``.  Tokens emitted after the swap carry
        ``weight_version == version`` in their StepEvents.
        """
        self.params = params
        self.weight_version = version
        self.tracer.event("engine.swap_weights", self.trace_lane,
                          version=version)

    def load_weights(self, params, version: int):
        self.swap_weights(params, version)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def supports_prefix_sharing(self) -> bool:
        """Group prompt sharing needs per-slot state to be limited to the
        paged pools (all-global attention) — SSM/ring rows are not forked."""
        return self._chunkable

    def free_slots(self) -> int:
        return self.max_batch - self.n_active - len(self._reserved)

    # ------------------------------------------------------------------ #
    # admission
    # ------------------------------------------------------------------ #
    def _check_admission(self, L: int, max_total: int, need_slots: int = 1):
        if self.free_slots() < need_slots:
            raise AdmissionError(
                f"engine full: need {need_slots} slots, "
                f"{self.free_slots()} free")
        if self.max_context is not None:
            if max(L, max_total) > self.max_context:
                raise AdmissionError(
                    f"context {max(L, max_total)} exceeds max_context "
                    f"{self.max_context}")
        if self.max_pool_pages is not None:
            # commitment-based admission (the watermark a bounded pool
            # needs): every resident request reserves its WORST-CASE page
            # count up front, so decode can always reserve its write
            # window without growing past the cap.  Conservative — shared
            # group prompts are counted per sibling — which is the point:
            # admission may under-fill, decode must never die.
            usable = self.max_pool_pages - 1          # page 0 = garbage
            need = need_slots * self.alloc.pages_for(max_total)
            if self._committed_pages() + need > usable:
                raise AdmissionError(
                    f"page pool cap: need {need} pages for "
                    f"{need_slots} slot(s), "
                    f"{usable - self._committed_pages()} uncommitted of "
                    f"{usable} (max_pool_pages={self.max_pool_pages})")

    def _committed_pages(self) -> int:
        """Worst-case pages promised to resident requests (active slots +
        waiting prefill rows), each counted to its ``max_total``."""
        pages = 0
        for slot, s in enumerate(self.slots):
            if s is not None:
                pages += self.alloc.pages_for(int(self.maxtot_buf[slot]))
        for row in self.waiting:
            for (_rid, _key, max_total, _np, _slot) in row.members:
                pages += self.alloc.pages_for(max_total)
        return pages

    def _alloc_table(self, n_tokens: int) -> List[int]:
        while True:
            try:
                return self.alloc.alloc_table(n_tokens)
            except OutOfPages:
                self._grow_pool()

    def _reserve_decode(self, table: List[int], start: int, n: int
                        ) -> List[Tuple[int, int]]:
        """Pre-reserve the horizon write window [start, start + n): all
        capacity and COW copies happen HERE, before the fused dispatch
        (``reserve_decode`` is atomic, so growing the pool and retrying
        never loses copies)."""
        n0 = len(table)
        while True:
            try:
                copies = self.alloc.reserve_decode(table, start, n)
                break
            except OutOfPages:
                self._grow_pool()
        if copies or len(table) != n0:
            self._bt_dirty = True
        return copies

    def _grow_pool(self):
        """Double the page pool, bounded by ``max_pool_pages``.  At the
        cap the engine stops growing and surfaces ``AdmissionError``
        backpressure instead of doubling without bound (the real-engine
        host-OOM failure mode): callers keep the request pending and
        admission recovers once completions free pages."""
        try:
            new_num = self.alloc.grow(2 * self.alloc.num_pages)
        except OutOfPages as e:
            raise AdmissionError(str(e)) from e
        self.cache = kvc.grow_pool(self.cache, new_num)

    def _free_slot(self, slot: int):
        st = self.slots[slot]
        if st is not None and st.table:
            self.alloc.free_table(st.table)
        self.slots[slot] = None
        self.tokens_buf[slot] = TOKEN_SENTINEL
        self.maxtot_buf[slot] = 0

    def _reserve_slot(self, req_id: int) -> int:
        taken = set(self._reserved.values())
        slot = next(i for i, s in enumerate(self.slots)
                    if s is None and i not in taken)
        self._reserved[req_id] = slot
        return slot

    # ------------------------------------------------------------------ #
    # request intake
    # ------------------------------------------------------------------ #
    def add_request(self, req_id: int, token_ids: List[int], key,
                    max_total: int, n_prompt: int) -> int:
        """Queue prompt(+partial) for batched prefill; returns the reserved
        slot.  The first emitted token arrives from the next ``step()``.
        ``token_ids`` may include previously generated tokens (migration
        continuation).

        Kept as the single-request alias of :meth:`add_group`: a size-1
        group takes the identical admission / commitment / backpressure
        path (one ``_check_admission``, pages before the slot), so a
        capped pool exercises ONE code path whichever door work arrives
        through."""
        return self.add_group([(req_id, key, max_total)], token_ids,
                              n_prompt)[0]

    def add_group(self, members: List[Tuple[int, object, int]],
                  token_ids: List[int], n_prompt: int) -> List[int]:
        """Queue a group of requests sharing one prefill — THE admission
        path (``add_request`` delegates here with a size-1 group).

        members: [(req_id, key, max_total)] — all members sample from the
        same ``token_ids`` context.  For a GRPO group that is the shared
        prompt: it is prefilled once and its pages are ref-counted and
        shared copy-on-write across the G block tables.  For a size-1
        group ``token_ids`` may be prompt+partial (migration
        continuation).  Returns the reserved slots (one per member).

        Admission is commitment-based (``_check_admission`` with the
        group's worst-case ``max_total``) and pages are allocated BEFORE
        any slot is reserved: a capped pool rejecting here must not leak
        slot reservations.
        """
        L = len(token_ids)
        max_tot = max(m[2] for m in members)
        self._check_admission(L, max_tot, need_slots=len(members))
        table = self._alloc_table(L)
        queued = self.tracer.begin("engine.queued", self.trace_lane,
                                   annotate=False,
                                   req=[m[0] for m in members], tokens=L)
        row = _WaitRow(token_ids=list(token_ids), table=table, members=[],
                       queued=queued)
        slots = []
        for req_id, key, max_total in members:
            slot = self._reserve_slot(req_id)
            key_data = np.asarray(jax.random.key_data(key), np.uint32)
            row.members.append((req_id, key_data, max_total, n_prompt, slot))
            slots.append(slot)
        self.waiting.append(row)
        self.n_shared_prompt_tokens += L * (len(members) - 1)
        return slots

    # ------------------------------------------------------------------ #
    # scheduler step: decode phase, then prefill phase (token budget)
    # ------------------------------------------------------------------ #
    def step(self) -> List[StepEvent]:
        tr = self.tracer
        if not tr.enabled:                      # no per-step counters when off
            events = self._decode_phase()
            events.extend(self._prefill_phase())
            return events
        # counters are read before any reservation; ``new_program`` says
        # whether the phase built a jitted closure (a compile inside a step).
        # Attrs pass through begin/end only, never through the span handle:
        # a tracer may be any object with the Tracer's methods
        lane = self.trace_lane
        ctx = [s.ctx_len + 1 for s in self.slots if s is not None]
        compiles = _JIT_STATS["compiles"]
        span = tr.begin("engine.decode", lane, horizon=self.horizon,
                        rows=len(ctx), ctx=ctx,
                        pages_used=self.alloc.num_pages - 1 - self.alloc.n_free,
                        pages_committed=self._committed_pages())
        try:
            events = self._decode_phase(span)
        finally:
            tr.end(span, new_program=_JIT_STATS["compiles"] > compiles)
        compiles = _JIT_STATS["compiles"]
        rows: List[Tuple[int, int, bool]] = []
        span = tr.begin("engine.prefill", lane, n_waiting=len(self.waiting))
        try:
            events.extend(self._prefill_phase(span, rows))
        finally:
            tr.end(span, new_program=_JIT_STATS["compiles"] > compiles,
                   rows=rows)
        return events

    # ---------------- device-resident state ---------------- #
    def _sync_device_state(self):
        """Upload the decode-state buffers iff the host mutated them since
        the last dispatch (admission / migration / drop).  Rows finishing
        inside a horizon need NO re-upload: the device transitions them
        itself and the host mirrors track it."""
        if self._state_dirty or self._dev_tokens is None:
            active = np.array([s is not None for s in self.slots])
            self._dev_tokens = jnp.asarray(self.tokens_buf)
            self._dev_keys = jnp.asarray(self.keys_buf)
            self._dev_active = jnp.asarray(active)
            self._dev_maxtot = jnp.asarray(self.maxtot_buf)
            self._state_dirty = False
            self.n_state_uploads += 1

    def _device_block_tables(self):
        """Cached device block table, rebuilt only when some table changed
        (admission, COW, page append, free, migration).  The width is the
        smallest already-compiled closure width that fits (pad up) so width
        jitter from requests growing/finishing doesn't recompile."""
        needed = max((len(s.table) for s in self.slots if s is not None),
                     default=1)
        if self._bt_dirty or self._bt_dev is None or self._bt_width < needed:
            family = _decode_family(self.cfg, self.temperature, self.horizon,
                                    self.use_pallas)
            nb = _padded_width(family, needed)
            if nb is None:
                nb = _bucket(needed, minimum=8)
            else:
                _JIT_STATS["padded_reuse"] += 1
            bt = np.full((self.max_batch, nb), GARBAGE_PAGE, np.int32)
            for i, st in enumerate(self.slots):
                if st is not None:
                    bt[i, :len(st.table)] = st.table
            self._bt_dev = jnp.asarray(bt)
            self._bt_width = nb
            self._bt_dirty = False
            self.n_bt_uploads += 1
        return self._bt_dev

    # ---------------- decode ---------------- #
    def _decode_phase(self, span=None) -> List[StepEvent]:
        if self.n_active == 0:
            return []
        tr, lane, H = self.tracer, self.trace_lane, self.horizon
        with tr.span("engine.decode.host", lane, parent=span):
            # host-side page bookkeeping, ONCE per horizon: reserve the
            # whole write window (capacity + COW) for every active slot
            copies: List[Tuple[int, int]] = []
            for st in self.slots:
                if st is None:
                    continue
                copies.extend(self._reserve_decode(st.table, st.ctx_len, H))
            if copies:
                m = _bucket(len(copies), minimum=1)
                src = np.full((m,), GARBAGE_PAGE, np.int32)
                dst = np.full((m,), GARBAGE_PAGE, np.int32)
                src[:len(copies)] = [c[0] for c in copies]
                dst[:len(copies)] = [c[1] for c in copies]
                fn = _get_copy_fn(self.cfg, m)
                self.cache = fn(self.cache, jnp.asarray(src),
                                jnp.asarray(dst))
            bt = self._device_block_tables()
            self._sync_device_state()
            fn = _get_decode_fn(self.cfg, self.rt, bt.shape[1],
                                self.temperature, H)
            (self.cache, self._dev_tokens, self._dev_active,
             toks, lps, em) = fn(self.params, self.cache, self._dev_tokens,
                                 self._dev_keys, self._dev_active,
                                 self._dev_maxtot, bt)
            self.n_decode_dispatches += 1
        # ONE device->host sync per horizon: unpack [B, H] matrices into the
        # per-token StepEvent stream the rollout manager consumes
        with tr.span("engine.decode.wait", lane, parent=span):
            toks = np.asarray(toks)
            lps = np.asarray(lps)
            em = np.asarray(em)
        with tr.span("engine.decode.unpack", lane, parent=span):
            events: List[StepEvent] = []
            for h in range(H):
                for i, st in enumerate(self.slots):
                    if st is None or not em[i, h]:
                        continue
                    t = int(toks[i, h])
                    st.tokens.append(t)
                    st.last_token = t
                    st.ctx_len += 1
                    self.tokens_buf[i] = t
                    done = (t == EOS) or (len(st.tokens) >= st.max_total)
                    events.append(StepEvent(
                        req_id=st.req_id, token=t, logprob=float(lps[i, h]),
                        finished=done, weight_version=self.weight_version))
                    if done:
                        # mirrors the device transition (active->False,
                        # token parked at the sentinel), so no state
                        # re-upload is needed; the freed pages stay masked
                        # by the active mask until any table changes and
                        # the bt rebuilds
                        self._free_slot(i)
        return events

    # ---------------- prefill ---------------- #
    def _prefill_phase(self, span=None,
                       rows: Optional[List[Tuple[int, int, bool]]] = None
                       ) -> List[StepEvent]:
        """``rows``, when given, receives each chosen row as ``(offset,
        take, last)``."""
        if not self.waiting:
            return []
        tr, lane = self.tracer, self.trace_lane
        with tr.span("engine.prefill.host", lane, parent=span):
            budget = max(self.prefill_chunk, 1)
            chosen: List[Tuple[_WaitRow, int, int]] = []  # (row, start, take)
            for row in self.waiting:
                if budget <= 0:
                    break
                rem = len(row.token_ids) - row.done
                take = min(rem, budget) if self._chunkable else rem
                chosen.append((row, row.done, take))
                budget -= take
            n_rows = len(chosen)
            n = _bucket(n_rows, minimum=1)
            # chunk width buckets to kernel-tile multiples (128): the ragged
            # prefill kernel always hits a compiled [n, C] grid, and short
            # chunks of many widths reuse ONE closure (counted below)
            max_take = max(take for _, _, take in chosen)
            C = _tile_bucket(max_take)
            toks = np.zeros((n, C), np.int32)
            mask = np.zeros((n, C), np.float32)
            offsets = np.zeros((n,), np.int32)
            slot_idx = np.full((n,), self.max_batch, np.int32)  # OOB: dropped
            widths = [len(row.table) for row, _, _ in chosen]
            needed = max(widths)
            family = _prefill_family(self.cfg, n, C, self.use_pallas)
            nb = _padded_width(family, needed)
            if nb is None:
                nb = _bucket(needed, minimum=8)
            else:
                _JIT_STATS["padded_reuse"] += 1
            if C > max_take and family + (nb,) in _JIT_CACHE:
                _JIT_STATS["chunk_pad_reuse"] += 1
            bt = np.full((n, nb), GARBAGE_PAGE, np.int32)
            for i, (row, start, take) in enumerate(chosen):
                toks[i, :take] = row.token_ids[start:start + take]
                mask[i, :take] = 1.0
                offsets[i] = start
                slot_idx[i] = row.members[0][4]     # owner slot's state rows
                bt[i, :len(row.table)] = row.table
            fn = _get_prefill_fn(self.cfg, self.rt, n, C, nb)
            self.cache, logits = fn(self.params, self.cache,
                                    jnp.asarray(slot_idx), jnp.asarray(toks),
                                    jnp.asarray(mask), jnp.asarray(offsets),
                                    jnp.asarray(bt))
        with tr.span("engine.prefill.wait", lane, parent=span):
            logits = np.asarray(logits)

        events: List[StepEvent] = []
        completed: List[Tuple[int, _WaitRow]] = []
        for i, (row, start, take) in enumerate(chosen):
            row.done += take
            self.n_prefill_tokens += take
            if row.done < len(row.token_ids):
                continue                         # more chunks to go
            self.waiting.remove(row)
            self.n_prefills += 1
            completed.append((i, row))
        if rows is not None:
            rows.extend((start, take, start + take == len(row.token_ids))
                        for row, start, take in chosen)
        if not completed:
            return events

        # ONE batched first-token sampling call over every member of every
        # completed row (was one jit dispatch per GRPO group member)
        with tr.span("engine.sample", lane, parent=span) as sample:
            M = sum(len(row.members) for _, row in completed)
            m = _bucket(M, minimum=1)
            sel = np.zeros((m,), np.int32)
            keys = np.zeros((m, 2), np.uint32)
            pos = np.zeros((m,), np.int32)
            e = 0
            for i, row in completed:
                L = len(row.token_ids)
                for (_, key_data, _, _, _) in row.members:
                    sel[e] = i
                    keys[e] = key_data
                    pos[e] = L - 1
                    e += 1
            sfn = _get_batch_sample_fn(self.temperature, m)
            nxts, first_lps = sfn(jnp.asarray(logits[sel]),
                                  jnp.asarray(keys), jnp.asarray(pos))
            with tr.span("engine.sample.wait", lane, parent=sample):
                nxts = np.asarray(nxts)
                first_lps = np.asarray(first_lps)

        pos_fix: List[Tuple[int, int]] = []     # sibling slots need pos = L
        e = 0
        for i, row in completed:
            L = len(row.token_ids)
            # fork every sibling table BEFORE emitting any events: the owner
            # may finish (EOS / max_total) immediately, and freeing its table
            # must not strip pages later siblings still need
            tables = [row.table] + [self.alloc.fork(row.table)
                                    for _ in row.members[1:]]
            for j, (req_id, key_data, max_total, n_prompt, slot) in \
                    enumerate(row.members):
                table = tables[j]
                nxt = int(nxts[e])
                lp = float(first_lps[e])
                e += 1
                st = SlotState(req_id=req_id, key_data=key_data,
                               tokens=list(row.token_ids) + [nxt],
                               n_prompt=n_prompt, max_total=max_total,
                               last_token=nxt, table=table, ctx_len=L)
                del self._reserved[req_id]
                self.slots[slot] = st
                self.tokens_buf[slot] = nxt
                self.keys_buf[slot] = key_data
                self.maxtot_buf[slot] = max_total
                if j > 0:
                    pos_fix.append((slot, L))
                done = (nxt == EOS) or (len(st.tokens) >= st.max_total)
                events.append(StepEvent(req_id=req_id, token=nxt,
                                        logprob=lp, finished=done,
                                        weight_version=self.weight_version))
                if done:
                    self._free_slot(slot)
            tr.end(row.queued, outcome="served")
        # admission mutated the decode state + tables: re-upload next decode
        self._state_dirty = True
        self._bt_dirty = True
        if pos_fix:
            # the prefill scatter set pos only on the owner's slot row;
            # group siblings share the same context length
            idx = jnp.asarray([s for s, _ in pos_fix], jnp.int32)
            val = jnp.asarray([v for _, v in pos_fix], jnp.int32)
            self.cache["pos"] = self.cache["pos"].at[idx].set(val)
        return events

    # ------------------------------------------------------------------ #
    # KV-page migration (zero-recompute, paper §4.2 over the chunk plane)
    # ------------------------------------------------------------------ #
    def exportable_request_ids(self) -> List[int]:
        """Requests whose KV state can be exported: decode-resident slots.
        Requests still waiting for (chunked) prefill migrate by token
        history as before — they have no complete KV to ship."""
        return [s.req_id for s in self.slots if s is not None]

    def export_request_state(self, req_ids: List[int]) -> Dict:
        """Export the full generation state of ``req_ids`` as host arrays.

        The export is GRPO-aware: pages shared between exported siblings
        (COW prompt sharing) appear ONCE in the unique-page payload, and
        each request's table is a list of indices into it.  Ring-buffer /
        SSM per-slot rows ride along under ``slot_state``.  Only pages
        covering ``ctx_len`` ship — horizon-reserved tail pages past the
        context are re-reserved by the destination.  The source state is
        untouched; callers drop the requests after a successful export.
        """
        by_id = {s.req_id: (i, s) for i, s in enumerate(self.slots)
                 if s is not None}
        unique: List[int] = []
        uidx: Dict[int, int] = {}
        requests: List[Dict] = []
        slot_state: Dict[int, Dict] = {}
        for rid in req_ids:
            if rid not in by_id:
                raise KeyError(f"request {rid} has no decode-resident state")
            slot, st = by_id[rid]
            idxs = []
            for p in st.table[:self.alloc.pages_for(st.ctx_len)]:
                if p not in uidx:
                    uidx[p] = len(unique)
                    unique.append(p)
                idxs.append(uidx[p])
            requests.append(dict(
                req_id=rid, tokens=list(st.tokens), n_prompt=st.n_prompt,
                max_total=st.max_total, last_token=st.last_token,
                ctx_len=st.ctx_len,
                key_data=np.array(st.key_data, np.uint32),
                page_idx=idxs))
            if not self._chunkable:         # ring / SSM state exists
                slot_state[rid] = kvc.gather_slot_rows(self.cache, slot)
        span = self.tracer.begin("engine.kv_export", self.trace_lane,
                                 n_reqs=len(req_ids), n_pages=len(unique))
        pages = (kvc.gather_pages(self.cache, unique) if unique else {})
        self.tracer.end(span)
        return dict(page_size=self.page_size, n_pages=len(unique),
                    pages=pages, requests=requests, slot_state=slot_state)

    def import_request_state(self, state: Dict,
                             only: Optional[List[int]] = None) -> List[int]:
        """Adopt exported KV state: requests resume decoding at
        ``pos = len(prompt) + len(partial)`` with ZERO prefill.

        Pages are allocated once per unique page actually referenced by the
        imported requests and written from the payload; tables referencing
        the same page (migrated GRPO siblings' shared prompt) adopt it by
        refcount — identical COW semantics to ``add_group``.  ``only``
        restricts the import to a subset of the exported requests (partial
        group landing); unreferenced pages are neither allocated nor
        written.  Raises :class:`AdmissionError` when slots are short.
        """
        if state["page_size"] != self.page_size:
            raise AdmissionError(
                f"page_size mismatch: export {state['page_size']} vs "
                f"engine {self.page_size}")
        reqs = [r for r in state["requests"]
                if only is None or r["req_id"] in only]
        if not reqs:
            return []
        self._check_admission(
            max(r["ctx_len"] for r in reqs),
            max(r["max_total"] for r in reqs), need_slots=len(reqs))
        span = self.tracer.begin("engine.kv_import", self.trace_lane,
                                 n_reqs=len(reqs))
        # allocate each referenced unique page once
        used = sorted({i for r in reqs for i in r["page_idx"]})
        while True:
            try:
                fresh = self.alloc.alloc(len(used))
                break
            except OutOfPages:
                try:
                    self._grow_pool()
                except AdmissionError:
                    self.tracer.end(span, outcome="rejected")
                    raise
        page_map = dict(zip(used, fresh))
        if used:
            # select the referenced pages from the payload (group-stacked
            # pools carry a leading G axis -> page axis is ndim-4 either way)
            sel = {k: np.take(np.asarray(v), used, axis=v.ndim - 4)
                   for k, v in state["pages"].items()}
            self.cache = kvc.scatter_pages(self.cache, sel, fresh)
        slots = []
        referenced: Dict[int, int] = {}
        for r in reqs:
            rid = r["req_id"]
            slot = self._reserve_slot(rid)
            del self._reserved[rid]
            table = []
            for i in r["page_idx"]:
                p = page_map[i]
                if p in referenced:
                    self.alloc.incref(p)     # shared-page adoption
                else:
                    referenced[p] = rid      # first table keeps alloc's ref
                table.append(p)
            st = SlotState(req_id=rid, key_data=np.array(r["key_data"],
                                                         np.uint32),
                           tokens=list(r["tokens"]), n_prompt=r["n_prompt"],
                           max_total=r["max_total"],
                           last_token=r["last_token"], table=table,
                           ctx_len=r["ctx_len"])
            self.slots[slot] = st
            self.tokens_buf[slot] = r["last_token"]
            self.keys_buf[slot] = st.key_data
            self.maxtot_buf[slot] = r["max_total"]
            if rid in state["slot_state"]:
                self.cache = kvc.scatter_slot_rows(
                    self.cache, state["slot_state"][rid], slot)
            slots.append(slot)
            self.n_kv_import_tokens += r["ctx_len"]
        idx = jnp.asarray(slots, jnp.int32)
        val = jnp.asarray([r["ctx_len"] for r in reqs], jnp.int32)
        self.cache["pos"] = self.cache["pos"].at[idx].set(val)
        self._state_dirty = True
        self._bt_dirty = True
        self.tracer.end(span, n_pages=len(used))
        return slots

    # ------------------------------------------------------------------ #
    def drop_request(self, req_id: int) -> Optional[List[int]]:
        """Remove a request (migration away); returns its token history.
        Legal only between ``step()`` calls — i.e. at horizon boundaries."""
        for i, st in enumerate(self.slots):
            if st is not None and st.req_id == req_id:
                toks = list(st.tokens)
                self._free_slot(i)
                self._state_dirty = True
                self._bt_dirty = True
                return toks
        for row in self.waiting:
            for m in row.members:
                if m[0] == req_id:
                    row.members.remove(m)
                    self._reserved.pop(req_id, None)
                    toks = list(row.token_ids)
                    if not row.members:
                        self.alloc.free_table(row.table)
                        self.waiting.remove(row)
                        self.tracer.end(row.queued, outcome="dropped")
                    return toks
        return None

    def active_request_ids(self) -> List[int]:
        ids = [s.req_id for s in self.slots if s is not None]
        ids.extend(m[0] for row in self.waiting for m in row.members)
        return ids
