"""Continuous-batching serve engine over the PVQ-packed artifact.

The fixed-batch ``serve.generate`` loop decodes a lockstep batch: every
sequence starts together, ends together, and a short request pays for the
longest one.  This engine serves a fixed pool of ``n_slots`` decode slots
that sequences join and leave **mid-flight**, with the PVQ-compressed KV
cache paged through a shared physical pool:

admission -> batcher -> page table -> prefill/decode steps

* **Admission** — an asyncio feeder releases :class:`Request`s into the
  pending queue at their (Poisson) arrival times; :meth:`PVQEngine.run`'s
  loop admits from the queue head whenever a slot AND the prompt's full
  pages are available (backpressure is simply "the queue waits").
* **Paged KV** — each attention layer's cache is a
  :class:`core.packed.PagedKV`: PVQ-encoded blocks live in a pool of
  physical pages with **page size = kv block size**, so a page is exactly
  one PVQ encode unit and stays packed at rest (int8 pulse planes +
  per-group rho; an allocator move is an int8 byte move, never a
  re-encode).  The host-side :class:`PageAllocator` owns the free list;
  the device sees only the ``page_table``/``write_page`` arrays refreshed
  every step.
* **Prefill/decode disaggregation** — prompts run through a separately
  compiled prefill step (``model.prefill_bucketed``, prompt length padded
  to a page-multiple bucket so compile count is bounded by buckets, and
  with a DENSE cache via ``kv_quant_scope(None)``), then the prefilled KV
  is **grafted** into the slot pool: complete blocks are PVQ-encoded
  straight into allocator-assigned pages (bit-identical to the
  ``PackedKV.from_dense`` encode the fixed-batch path uses) and the exact
  partial tail block lands in the slot's f32 tail ring.  Decode then runs
  one engine-static compiled step over the whole slot pool with per-slot
  positions.
* **Eviction** — when a decode step needs more pages than the pool has
  free, the youngest active sequence is evicted: its pages return to the
  pool and the request is requeued at the queue head with its
  prompt + generated-so-far as the new prefill context (generated tokens
  are kept; re-admission re-prefills them teacher-forced).
* **Per-sequence stopping** — each slot retires on its own EOS or
  ``max_new_tokens``; a finished slot frees its pages and stops consuming
  batch capacity immediately.

The decode step is **engine-static**: shapes depend only on
``(n_slots, n_pages, max_pages)``, never on which sequences are resident,
so the whole run compiles ONE decode step (plus one prefill/graft pair per
prompt bucket).  ``trace_counts`` records actual traces for the
compile-count regression tests.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packed import is_paged_kv
from repro.core.quantize import default_kv_quant, kv_quant_scope
from repro.runtime import obs
from repro.runtime.telemetry import Histogram


def bucket_len(n: int, multiple: int) -> int:
    """Round ``n`` up to a positive multiple — the static-shape buckets
    that keep XLA compile counts bounded (shared by the engine's prefill
    and by ``serve.generate``'s cache-length bucketing)."""
    m = max(int(multiple), 1)
    return max(m, -(-int(n) // m) * m)


# ---------------------------------------------------------------------------
# Host-side page allocator
# ---------------------------------------------------------------------------


class PageAllocator:
    """Refcounted free-list allocator over the physical KV page pool, with
    a prompt-prefix hash index for shared-prefix page reuse.

    Page ids are ``0 .. n_pages-1``; id ``n_pages`` is the device-side
    *trash page* (masked scatter target / unallocated page-table entries)
    and is never handed out.  Double frees and trash frees raise — the
    tests lean on this to prove no page is ever freed out from under a
    sequence.

    **Refcounts** — ``alloc`` hands a page out at refcount 1; the prefix
    cache maps an already-written page into another slot's page table via
    ``share`` (refcount += 1).  ``free`` decrements, and only a page
    reaching refcount 0 actually leaves the used set, so evicting or
    retiring one sharer never frees pages a co-sharer still reads.  Pages
    are immutable once written (appends and chunk grafts only ever target
    freshly-allocated pages), which makes the sharing copy-on-write by
    construction: extending a shared prefix writes NEW pages, never the
    shared ones.

    **Prefix index** — ``register`` binds a page to the chain hash of its
    prompt-block content (hash covers every block from position 0, so a
    key encodes content AND absolute position — exactly the condition for
    a packed KV page to be causally valid in another sequence).  A
    registered page whose refcount drops to 0 parks in a *cached* LRU
    pool instead of the free list: still resident, instantly shareable by
    the next request with the same prefix, and reclaimed LRU-first when
    the free list runs dry (``available`` counts both).
    """

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"need at least one page, got {n_pages}")
        self.n_pages = int(n_pages)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self._refs: Dict[int, int] = {}
        self._cached: "OrderedDict[int, str]" = OrderedDict()  # pid -> key, LRU order
        self._prefix: Dict[str, int] = {}  # chain hash -> pid
        self._keys: Dict[int, str] = {}  # pid -> registered chain hash

    @property
    def trash(self) -> int:
        return self.n_pages

    @property
    def available(self) -> int:
        """Pages allocatable right now: the free list plus the cached pool
        (cached pages are reclaimed LRU-first when the free list is dry)."""
        return len(self._free) + len(self._cached)

    @property
    def used(self) -> int:
        """Pages with a live owner (refcount >= 1)."""
        return len(self._refs)

    @property
    def cached(self) -> int:
        """Refcount-0 pages parked for prefix reuse."""
        return len(self._cached)

    def refcount(self, pid: int) -> int:
        return self._refs.get(int(pid), 0)

    def alloc(self) -> Optional[int]:
        if self._free:
            pid = self._free.pop()
        elif self._cached:
            # reclaim the least-recently-parked prefix page; its index
            # entry dies with it (the content is about to be overwritten)
            pid, key = self._cached.popitem(last=False)
            self._prefix.pop(key, None)
            self._keys.pop(pid, None)
        else:
            return None
        self._refs[pid] = 1
        return pid

    def alloc_many(self, n: int) -> Optional[List[int]]:
        if self.available < n:
            return None
        return [self.alloc() for _ in range(n)]

    def free(self, ids: Sequence[int]) -> None:
        for pid in ids:
            pid = int(pid)
            if pid == self.trash:
                raise ValueError("freeing the trash page")
            rc = self._refs.get(pid)
            if rc is None:
                raise ValueError(f"double free of page {pid}")
            if rc > 1:
                self._refs[pid] = rc - 1
                continue
            del self._refs[pid]
            key = self._keys.get(pid)
            if key is not None and self._prefix.get(key) == pid:
                self._cached[pid] = key  # park for prefix reuse
            else:
                self._free.append(pid)

    # ------------------------------------------------------- prefix index

    def register(self, pid: int, key: str) -> None:
        """Bind a live page to its prompt-block chain hash.  First writer
        wins: a key already mapped to a different page stays put (both
        pages hold identical content; the duplicate just frees normally)."""
        pid = int(pid)
        if pid == self.trash or pid not in self._refs:
            return
        if key in self._prefix and self._prefix[key] != pid:
            return
        old = self._keys.get(pid)
        if old is not None and old != key:
            self._prefix.pop(old, None)
        self._prefix[key] = pid
        self._keys[pid] = key

    def lookup(self, key: str) -> Optional[int]:
        return self._prefix.get(key)

    def share(self, pid: int) -> bool:
        """Take a reference on an indexed page (live or cached).  Returns
        False if the page was reclaimed in the meantime."""
        pid = int(pid)
        if pid in self._refs:
            self._refs[pid] += 1
            return True
        if pid in self._cached:
            del self._cached[pid]
            self._refs[pid] = 1
            return True
        return False


# ---------------------------------------------------------------------------
# Requests and traces
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One generation request plus its engine-owned progress/timing state.

    After an eviction ``generated`` keeps everything produced so far; the
    re-admission prefills ``prompt + generated[:-1]`` and resumes decoding
    with ``generated[-1]`` as the pending input token, so eviction never
    loses or re-samples a token."""

    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    arrival: float = 0.0  # seconds offset within the trace
    generated: List[int] = dataclasses.field(default_factory=list)
    submit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    evictions: int = 0
    # admission-blocked duration: total seconds spent waiting in the
    # pending queue (initial wait + every post-eviction re-wait)
    queue_wait_s: float = 0.0
    # eviction latency cost: seconds from each eviction to the end of the
    # re-admission (re-queue wait + teacher-forced re-prefill), summed
    evict_cost_s: float = 0.0
    evict_t: Optional[float] = None  # in-flight eviction timestamp
    # TTFT decomposition (queue_wait_s + prefill_compute_s + chunk_wait_s
    # ~= first_token_t - submit_t): device time actually spent in this
    # request's prefill/graft/chunk calls, and the between-chunk gaps
    # where the scheduler ran decode steps for other slots instead
    admit_t: Optional[float] = None
    prefill_compute_s: float = 0.0
    chunk_wait_s: float = 0.0
    # pages mapped from the shared-prefix cache (zero prefill recompute)
    prefix_hit_pages: int = 0

    @property
    def done(self) -> bool:
        if len(self.generated) >= self.max_new_tokens:
            return True
        return (
            bool(self.generated)
            and self.eos_id is not None
            and self.generated[-1] == self.eos_id
        )


def poisson_trace(
    n_requests: int,
    *,
    rate: float,
    vocab: int,
    prompt_lens: Tuple[int, int] = (8, 24),
    max_new: int = 16,
    eos_id: Optional[int] = None,
    seed: int = 0,
    shared_prefix: int = 0,
) -> List[Request]:
    """Poisson request trace: exponential inter-arrival gaps at ``rate``
    requests/second and uniformly random prompt lengths in
    ``prompt_lens = (lo, hi)``.  ``rate=inf`` (or 0) puts every arrival at
    t=0 — the saturate-then-drain pattern the CI smoke uses.

    ``shared_prefix > 0`` prepends one common random token prefix of that
    length to every prompt (the shared-system-prompt traffic shape the
    prefix page cache is built for); the per-request suffix still draws
    its length from ``prompt_lens``."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    prefix = (
        [int(x) for x in rng.integers(0, vocab, int(shared_prefix))]
        if shared_prefix
        else []
    )
    t = 0.0
    out = []
    for rid in range(n_requests):
        if rate and np.isfinite(rate) and rate > 0:
            t += float(rng.exponential(1.0 / rate))
        plen = int(rng.integers(lo, hi + 1))
        out.append(
            Request(
                rid=rid,
                prompt=prefix + [int(x) for x in rng.integers(0, vocab, plen)],
                max_new_tokens=int(max_new),
                eos_id=eos_id,
                arrival=t,
            )
        )
    return out


def prefill_in_chunks(model, params, prompt: Sequence[int], chunk_tokens: int):
    """Last-position logits ``(1, 1, vocab)`` of ``prompt`` prefilled alone
    through ``Model.prefill_chunk``, ``chunk_tokens`` (a page multiple) at a
    time from position 0, on a private one-slot page pool whose page ``b``
    holds block ``b``.  Needs an active ``KVQuant`` (page = its block).

    This is what chunked prefill computes, for checks against
    ``Model.prefill`` over the whole prompt: one chunk agrees to float
    rounding, and later chunks differ only by reading the earlier ones
    through PVQ-packed pages."""
    page = int(default_kv_quant().block)
    plen = len(prompt)
    n_full = plen // page
    n_pages = -(-plen // page)  # the trash page is n_pages
    blocks = np.arange(n_pages)
    table = jnp.asarray(np.where(blocks < n_full, blocks, n_pages)[None], jnp.int32)
    no_write = jnp.full((1,), n_pages, jnp.int32)
    cache = jax.tree.map(
        lambda c: c.with_tables(table, no_write) if is_paged_kv(c) else c,
        model.init_paged_cache(1, n_pages, n_pages),
        is_leaf=is_paged_kv,
    )
    step = jax.jit(model.prefill_chunk)
    for start in range(0, plen, chunk_tokens):
        end = min(start + chunk_tokens, plen)
        toks = np.zeros((1, chunk_tokens), np.int32)
        toks[0, : end - start] = np.asarray(prompt[start:end], np.int32)
        ids = start // page + np.arange(chunk_tokens // page)
        ids = np.where(ids < n_full, ids, n_pages).astype(np.int32)
        logits, cache = step(
            params, cache, toks, np.int32(0), np.int32(start), ids, np.int32(plen)
        )
    return logits


@dataclasses.dataclass
class _Slot:
    req: Request
    length: int  # cache rows currently filled for this slot
    pages: List[int]  # physical pages owned/shared (in logical-block order)
    admit_order: int
    # chunked-prefill state machine: a slot admitted via the chunked path
    # starts in phase "prefill" (its prompt streams in C tokens per engine
    # step, interleaved with other slots' decode steps) and flips to
    # "decode" when chunk_pos reaches len(ctx)
    phase: str = "decode"
    ctx: Optional[List[int]] = None  # admission context being prefilled
    chunk_pos: int = 0  # next absolute position to compute
    block_keys: Optional[List[str]] = None  # prefix chain hash per full block


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class PVQEngine:
    """Continuous-batching decode over a paged, PVQ-compressed KV cache.

    Requires an active process-wide ``KVQuant`` default (pages ARE the PVQ
    kv blocks) — the same switch the fixed-batch ``serve --kv-pvq`` path
    uses, so both paths share kernels, encode, and autotune entries.

    Slot invariant: an active slot holds ``length`` cache rows
    (= prompt + all generated tokens except the newest), and the next
    decode step feeds ``req.generated[-1]`` at position ``length``.
    """

    def __init__(
        self,
        model,
        params,
        *,
        n_slots: int = 4,
        max_len: int = 128,
        n_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        prefill_batch: int = 1,
        prefix_cache: bool = True,
    ):
        kvq = default_kv_quant()
        if kvq is None:
            raise ValueError(
                "PVQEngine pages the PVQ-compressed cache: set a process-wide "
                "KVQuant first (set_default_kv_quant / kv_quant_scope)"
            )
        self.page = int(kvq.block)
        if self.page < 2:
            raise ValueError("page (= kv block) must be >= 2")
        self.model = model
        self.params = params
        self.n_slots = int(n_slots)
        self.max_pages = bucket_len(max_len, self.page) // self.page
        full = self.n_slots * self.max_pages
        self.n_pages = int(n_pages) if n_pages else full
        if self.n_pages < self.max_pages:
            # a lone sequence must always be able to run to max_len, or
            # eviction could never free enough pages to make progress
            raise ValueError(
                f"n_pages={self.n_pages} < max_pages={self.max_pages}: "
                "one full-length sequence must fit the pool"
            )
        # chunked prefill: long prompts stream in C = prefill_chunk * page
        # tokens per engine step (page-multiple chunks -> every chunk start
        # is page-aligned), interleaved with decode steps so active slots'
        # inter-token latency stays bounded during long-prompt admission
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.chunk_tokens = (self.prefill_chunk or 0) * self.page
        # batched admission: up to prefill_batch same-bucket waiting
        # requests prefill through ONE multi-row compile per step
        self.prefill_batch = max(int(prefill_batch), 1)
        # the shared-prefix page cache needs the chunk machinery to resume
        # a prompt from a page-aligned hit boundary
        self.prefix_cache = bool(prefix_cache) and self.prefill_chunk is not None
        self.alloc = PageAllocator(self.n_pages)
        self.cache = model.init_paged_cache(self.n_slots, self.n_pages, self.max_pages)
        self.slots: List[Optional[_Slot]] = [None] * self.n_slots
        self._page_table = np.full(
            (self.n_slots, self.max_pages), self.alloc.trash, np.int32
        )
        self._admit_seq = 0
        self.pending: deque = deque()
        self.finished: List[Request] = []
        self.trace_counts: Dict[str, int] = {
            "decode": 0, "prefill": 0, "graft": 0, "chunk": 0,
        }
        self.stats: Dict[str, int] = {
            "steps": 0, "active_slot_steps": 0, "evictions": 0, "decode_tokens": 0,
            "prefill_batches": 0, "prefill_rows": 0, "chunks": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_pages_shared": 0,
        }
        self._decode = jax.jit(self._decode_fn)
        self._prefill = jax.jit(self._prefill_fn)
        self._graft = jax.jit(self._graft_fn)
        self._chunk = jax.jit(self._chunk_fn)
        # decode-interference samples: inter-token gaps of steps that
        # shared their scheduler iteration with prefill/chunk work vs
        # pure-decode iterations (the p99 spread IS the head-of-line cost)
        self._itl_decode_s: List[float] = []
        self._itl_with_prefill_s: List[float] = []
        # sampled KV quality probes: the graft's in-graph encode cannot
        # probe itself (traced), so the first few admissions re-encode one
        # prefilled page eagerly when the registry is on
        self._kv_probe_budget = 8

    # ------------------------------------------------------------- capacity

    @property
    def capacity_tokens(self) -> int:
        return self.max_pages * self.page

    def validate(self, req: Request) -> None:
        need = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if need > self.capacity_tokens:
            raise ValueError(
                f"request {req.rid}: prompt+max_new={need} exceeds per-slot "
                f"capacity {self.capacity_tokens} (= max_pages * page)"
            )

    # --------------------------------------------------------- device steps

    def _decode_fn(self, params, cache, tokens, pos, page_table, write_page):
        # trace-time side effect: counts actual XLA traces, not calls
        self.trace_counts["decode"] += 1
        cache = jax.tree.map(
            lambda c: c.with_tables(page_table, write_page) if is_paged_kv(c) else c,
            cache,
            is_leaf=is_paged_kv,
        )
        logits, cache = self.model.decode_step(params, cache, tokens, pos)
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), cache

    def _prefill_fn(self, params, tokens, real_len):
        self.trace_counts["prefill"] += 1
        logits, caches = self.model.prefill_bucketed(
            params, {"tokens": tokens}, real_len
        )
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), caches

    def _graft_fn(self, cache, pre, slots, page_ids, real_len):
        """Batched graft: row ``i`` of the prefill batch lands in slot
        ``slots[i]``.  The row count is STATIC (``prefill_batch``; short
        batches duplicate row 0, and the duplicate grafts re-write
        identical bytes to identical destinations), so one trace serves
        every admission batch of a bucket."""
        self.trace_counts["graft"] += 1
        nrows = int(page_ids.shape[0])

        def row(leaf, i):
            # prefill cache leaves are (..., B, L_b, n_kv, hd): the batch
            # axis sits at -4 whether or not a layer-stack axis leads
            return leaf[..., i : i + 1, :, :, :]

        def walk(c, p):
            if is_paged_kv(c):
                for i in range(nrows):
                    c = c.graft(
                        row(p["k"], i), row(p["v"], i),
                        slots[i], page_ids[i], real_len[i],
                    )
                return c
            if isinstance(c, dict):
                return {key: walk(v, p[key]) for key, v in c.items()}
            return c

        return walk(cache, pre)

    def _chunk_fn(self, params, cache, tokens, slot, start, page_ids, real_len, page_table):
        """One chunked-prefill step: C tokens of one slot's context, read
        against its already-packed pages through ``page_table`` and
        grafted into ``page_ids``.  C is static, so the whole run
        compiles this exactly ONCE regardless of prompt lengths."""
        self.trace_counts["chunk"] += 1
        wp = jnp.full((self.n_slots,), self.alloc.trash, jnp.int32)
        cache = jax.tree.map(
            lambda c: c.with_tables(page_table, wp) if is_paged_kv(c) else c,
            cache,
            is_leaf=is_paged_kv,
        )
        logits, cache = self.model.prefill_chunk(
            params, cache, tokens, slot, start, page_ids, real_len
        )
        return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32), cache

    # ------------------------------------------------------------ admission

    def _free_slot(self, exclude: Optional[set] = None) -> Optional[int]:
        for s, st in enumerate(self.slots):
            if st is None and (exclude is None or s not in exclude):
                return s
        return None

    @staticmethod
    def _ctx_tokens(req: Request) -> List[int]:
        if req.generated:
            # re-admission after eviction: the last generated token is the
            # pending decode input, everything before it is prefill context
            return list(req.prompt) + req.generated[:-1]
        return list(req.prompt)

    def _prefix_keys(self, ctx: Sequence[int]) -> List[str]:
        """Chain hash per full page of the context, from position 0.  The
        running digest makes key ``b`` cover blocks ``0..b``, so a match
        certifies the whole prefix up to and including that page — content
        AND absolute position, the causal-validity condition for mapping a
        packed page into another sequence."""
        h = hashlib.blake2b(digest_size=16)
        out = []
        page = self.page
        for b in range(len(ctx) // page):
            h.update(np.asarray(ctx[b * page : (b + 1) * page], np.int64).tobytes())
            out.append(h.hexdigest())
        return out

    def _start_timing(self, req: Request, t_now: Optional[float]) -> float:
        t_adm = time.perf_counter()
        if req.submit_t is None:
            req.submit_t = t_adm if t_now is None else t_now
        # queue wait: submitted (or evicted) -> admission actually starting
        base = req.evict_t if req.evict_t is not None else req.submit_t
        req.queue_wait_s += max(t_adm - base, 0.0)
        req.admit_t = t_adm
        return t_adm

    def _chunk_routed(self, ctx: List[int]) -> bool:
        """A context takes the chunked path when it is longer than one
        chunk, or when the prefix cache can hand it packed pages (the
        continuation has to resume from a page-aligned boundary, which is
        exactly what the chunk step does)."""
        if self.prefill_chunk is None:
            return False
        if len(ctx) > self.chunk_tokens:
            return True
        if not self.prefix_cache or (len(ctx) - 1) // self.page < 1:
            return False
        keys = self._prefix_keys(ctx)
        return bool(keys) and self.alloc.lookup(keys[0]) is not None

    def admit_pending(self, t_now: Optional[float] = None) -> int:
        """Admit from the queue head until blocked (FIFO — no request can
        starve behind a later, smaller one).  Short same-bucket prompts
        are batch-claimed up to ``prefill_batch`` and prefilled through
        one multi-row compile; long or prefix-hitting prompts enter the
        chunked state machine instead (their prefill streams through
        :meth:`_prefill_step`, interleaved with decode steps)."""
        admitted = 0
        while self.pending:
            req = self.pending[0]
            self.validate(req)
            ctx = self._ctx_tokens(req)
            if self._chunk_routed(ctx):
                n = self._admit_chunked(req, ctx, t_now)
            else:
                n = self._admit_batch(t_now)
            if not n:
                break
            admitted += n
        return admitted

    # ------------------------------------------------- chunked admission

    def _admit_chunked(self, req: Request, ctx: List[int], t_now) -> int:
        """Claim a slot + ALL the context's full-block pages up front
        (prefill then never waits on the pool mid-stream, which rules out
        prefill/decode page deadlock), map any shared-prefix pages into
        the page table, and park the slot in phase "prefill"."""
        plen = len(ctx)
        n_full = plen // self.page
        slot = self._free_slot()
        if slot is None:
            return 0
        keys = self._prefix_keys(ctx) if self.prefix_cache else []
        # never map the block containing the LAST context token: its
        # logits must be recomputed to produce the first generated token
        max_hit = (plen - 1) // self.page
        hits: List[int] = []
        for key in keys[:max_hit]:
            pid = self.alloc.lookup(key)
            if pid is None or not self.alloc.share(pid):
                break
            hits.append(pid)
        ids = self.alloc.alloc_many(n_full - len(hits))
        if ids is None:
            if hits:
                self.alloc.free(hits)  # roll the shares back; try later
            return 0
        self._start_timing(req, t_now)
        req.prefix_hit_pages += len(hits)
        st = _Slot(
            req=req, length=0, pages=hits + ids, admit_order=self._admit_seq,
            phase="prefill", ctx=ctx, chunk_pos=len(hits) * self.page,
            block_keys=keys or None,
        )
        self._admit_seq += 1
        self.slots[slot] = st
        self._page_table[slot, :] = self.alloc.trash
        self._page_table[slot, :n_full] = st.pages
        self.pending.popleft()
        self.stats["prefix_hits"] += len(hits)
        self.stats["prefix_pages_shared"] += len(hits)
        if self.prefix_cache and len(hits) < max_hit:
            self.stats["prefix_misses"] += 1
        if obs.enabled():
            obs.counter("engine.admissions").inc()
            if hits:
                obs.counter("prefix_cache.hit").add(len(hits))
                obs.counter("prefix_cache.pages_shared").add(len(hits))
            if self.prefix_cache and len(hits) < max_hit:
                obs.counter("prefix_cache.miss").inc()
            obs.event("engine/admit", args={
                "rid": req.rid, "ctx": plen, "chunked": 1,
                "prefix_pages": len(hits),
            })
        return 1

    # ------------------------------------------------- batched admission

    def _admit_batch(self, t_now) -> int:
        """Batch-claim slots/pages FIFO from the queue head: every
        consecutive request sharing the head's length bucket joins, up to
        ``prefill_batch`` rows, then ONE bucketed multi-row prefill + one
        batched graft admit them all.  A request that needs the chunked
        path (or a different bucket, or for which resources run out)
        stops the batch — FIFO order is never reordered around."""
        page = self.page
        lb = bucket_len(len(self._ctx_tokens(self.pending[0])), page)
        rows: List[Tuple[Request, List[int], int, List[int]]] = []
        claimed: set = set()
        while self.pending and len(rows) < self.prefill_batch:
            req = self.pending[0]
            self.validate(req)
            ctx = self._ctx_tokens(req)
            if bucket_len(len(ctx), page) != lb or self._chunk_routed(ctx):
                break
            slot = self._free_slot(exclude=claimed)
            if slot is None:
                break
            ids = self.alloc.alloc_many(len(ctx) // page)
            if ids is None:
                break
            claimed.add(slot)
            rows.append((req, ctx, slot, ids))
            self.pending.popleft()
        if not rows:
            return 0
        self._run_batch_prefill(rows, lb, t_now)
        return len(rows)

    def _run_batch_prefill(self, rows, lb: int, t_now) -> None:
        page = self.page
        bsz = self.prefill_batch
        toks = np.zeros((bsz, lb), np.int32)
        real = np.ones((bsz,), np.int32)
        slots_arr = np.zeros((bsz,), np.int32)
        ids_arr = np.full((bsz, lb // page), self.alloc.trash, np.int32)
        for i, (req, ctx, slot, ids) in enumerate(rows):
            toks[i, : len(ctx)] = np.asarray(ctx, np.int32)
            real[i] = len(ctx)
            slots_arr[i] = slot
            ids_arr[i, : len(ids)] = ids
            self._start_timing(req, t_now)
        for i in range(len(rows), bsz):
            # pad rows duplicate row 0: the duplicate graft re-writes the
            # same bytes to the same pages/slot, so padding is idempotent
            # and the compile count stays one per bucket
            toks[i] = toks[0]
            real[i] = real[0]
            slots_arr[i] = slots_arr[0]
            ids_arr[i] = ids_arr[0]
        t0 = time.perf_counter()
        with kv_quant_scope(None), obs.span(
            "engine/prefill",
            args={"bucket": lb, "rows": len(rows), "batch": bsz},
        ):
            tok0, pre = self._prefill(self.params, toks, real)
        if obs.enabled() and self._kv_probe_budget > 0 and int(real[0]) >= page:
            self._kv_probe_budget -= 1
            self._probe_kv_quality(pre)
        with obs.span(
            "engine/graft",
            args={"rows": len(rows), "pages": int((real // page).sum())},
        ):
            self.cache = self._graft(self.cache, pre, slots_arr, ids_arr, real)
        tok_host = np.asarray(jax.device_get(tok0))
        dt = time.perf_counter() - t0
        self.stats["prefill_batches"] += 1
        self.stats["prefill_rows"] += len(rows)
        for i, (req, ctx, slot, ids) in enumerate(rows):
            # each row experienced the whole batch call as its latency
            req.prefill_compute_s += dt
            if req.evict_t is not None:
                # the eviction's full latency cost lands at re-admission:
                # the re-queue wait plus the teacher-forced re-prefill
                req.evict_cost_s += max(time.perf_counter() - req.evict_t, 0.0)
                req.evict_t = None
            if self.prefix_cache:
                for b, key in enumerate(self._prefix_keys(ctx)):
                    self.alloc.register(ids[b], key)
            if not req.generated:
                req.generated.append(int(tok_host[i]))
                req.first_token_t = time.perf_counter()
            if req.done:
                # prefill alone satisfied the request (max_new == 1 /
                # instant EOS): never occupies a slot.  Registered pages
                # park in the allocator's cached pool, still shareable.
                self.alloc.free(ids)
                self._finish(req)
                continue
            self.slots[slot] = _Slot(
                req=req, length=len(ctx), pages=list(ids),
                admit_order=self._admit_seq,
            )
            self._admit_seq += 1
            self._page_table[slot, :] = self.alloc.trash
            self._page_table[slot, : len(ids)] = ids
        if obs.enabled():
            obs.counter("engine.admissions").add(len(rows))
            for req, ctx, _, _ in rows:
                obs.event("engine/admit", args={"rid": req.rid, "ctx": len(ctx)})

    # --------------------------------------------------- chunked prefill

    def _prefill_step(self) -> int:
        """Run the per-step prefill token budget: ONE chunk (C tokens) for
        the oldest slot still in phase "prefill".  Interleaving exactly
        one chunk between decode steps bounds how long any active slot
        waits on admission work — the p99 inter-token latency guarantee
        monolithic prefill cannot make.  Returns tokens of chunk work
        done (0 when no slot is prefilling)."""
        cand = [
            (s, st) for s, st in enumerate(self.slots)
            if st is not None and st.phase == "prefill"
        ]
        if not cand:
            return 0
        s, st = min(cand, key=lambda t: t[1].admit_order)
        req, ctx = st.req, st.ctx
        assert ctx is not None
        plen = len(ctx)
        n_full = plen // self.page
        ctk = self.chunk_tokens
        start = st.chunk_pos
        end = min(start + ctk, plen)
        toks = np.zeros((1, ctk), np.int32)
        toks[0, : end - start] = np.asarray(ctx[start:end], np.int32)
        page_ids = np.full((ctk // self.page,), self.alloc.trash, np.int32)
        b0 = start // self.page
        for j in range(ctk // self.page):
            if b0 + j < n_full:
                page_ids[j] = st.pages[b0 + j]
        t0 = time.perf_counter()
        with obs.span("engine/prefill_chunk", args={
            "rid": req.rid, "start": start, "end": end, "ctx": plen,
        }):
            tok0, self.cache = self._chunk(
                self.params, self.cache, toks, np.int32(s), np.int32(start),
                page_ids, np.int32(plen), self._page_table.copy(),
            )
            tok0.block_until_ready()
        req.prefill_compute_s += time.perf_counter() - t0
        self.stats["chunks"] += 1
        if self.prefix_cache and st.block_keys:
            for b in range(b0, min(end // self.page, n_full)):
                self.alloc.register(st.pages[b], st.block_keys[b])
        st.chunk_pos = end
        if end < plen:
            return end - start
        # final chunk: transition prefill -> decode
        if not req.generated:
            req.generated.append(int(np.asarray(jax.device_get(tok0))[0]))
            req.first_token_t = time.perf_counter()
            if req.admit_t is not None:
                req.chunk_wait_s += max(
                    req.first_token_t - req.admit_t - req.prefill_compute_s, 0.0
                )
        if req.evict_t is not None:
            req.evict_cost_s += max(time.perf_counter() - req.evict_t, 0.0)
            req.evict_t = None
        st.phase = "decode"
        st.ctx = None
        st.length = plen
        if req.done:
            self._retire(s)
        return end - start

    def _probe_kv_quality(self, pre) -> None:
        """Host-side KV quality probe: eagerly re-encode the first page of
        one prefilled layer with the engine's KVQuant so the eager-only
        probe inside ``_kv_encode_planes`` fires (records SNR/clamp/scale
        metrics).  Sampled — never on the per-token path."""
        from repro.core.packed import _kv_encode_planes

        kvq = default_kv_quant()

        def find(c):
            if isinstance(c, dict):
                if "k" in c and "v" in c:
                    return c
                for v in c.values():
                    hit = find(v)
                    if hit is not None:
                        return hit
            return None

        kv = find(pre)
        if kv is None or kvq is None:
            return
        k = np.asarray(jax.device_get(kv["k"]), np.float32)
        if k.ndim < 2:
            return
        k = k[:, : self.page]
        g, hd = kvq.group, k.shape[-1]
        while g > 1 and hd % g:  # same power-of-two fit the cache init uses
            g //= 2
        _kv_encode_planes(jnp.asarray(k), g, kvq.k)

    # ----------------------------------------------------- retire and evict

    def _finish(self, req: Request) -> None:
        req.finish_t = time.perf_counter()
        self.finished.append(req)
        if obs.enabled():
            obs.counter("engine.requests_finished").inc()
            if req.submit_t is not None:
                obs.histogram("engine.request_latency_s").record(
                    req.finish_t - req.submit_t
                )
                if req.first_token_t is not None:
                    obs.histogram("engine.ttft_s").record(
                        req.first_token_t - req.submit_t
                    )
            obs.histogram("engine.queue_wait_s").record(req.queue_wait_s)
            # TTFT decomposition: queue_wait + prefill_compute + chunk_wait
            # ~= first_token_t - submit_t (the residual is host overhead)
            obs.histogram("engine.prefill_compute_s").record(req.prefill_compute_s)
            obs.histogram("engine.chunk_wait_s").record(req.chunk_wait_s)
            if req.evictions:
                obs.histogram("engine.evict_cost_s").record(req.evict_cost_s)
            obs.event("engine/retire", args={"rid": req.rid})

    def _release(self, s: int) -> _Slot:
        st = self.slots[s]
        assert st is not None
        if st.pages:
            self.alloc.free(st.pages)
        self._page_table[s, :] = self.alloc.trash
        self.slots[s] = None
        return st

    def _retire(self, s: int) -> None:
        self._finish(self._release(s).req)

    def _evict(self, s: int) -> None:
        st = self._release(s)
        st.req.evictions += 1
        st.req.evict_t = time.perf_counter()
        self.stats["evictions"] += 1
        if obs.enabled():
            obs.counter("engine.evictions").inc()
            obs.event(
                "engine/evict",
                args={"rid": st.req.rid, "kept_tokens": len(st.req.generated)},
            )
        # queue head: the victim resumes as soon as pages free up
        self.pending.appendleft(st.req)

    # ----------------------------------------------------------- decode step

    def step(self) -> int:
        """One decode step over every active slot.  Returns the number of
        tokens generated (0 when idle).

        Slots completing a PVQ block this step get their destination page
        pre-assigned (``write_page``); if the pool can't cover every
        completing slot, the youngest active sequence is evicted until it
        can (guaranteed to terminate: a lone sequence never needs more
        than ``max_pages`` <= ``n_pages``).  Slots still in phase
        "prefill" neither decode nor get evicted — their pages were fully
        reserved at admission, so they always make progress."""
        while True:
            active = [
                (s, st) for s, st in enumerate(self.slots)
                if st is not None and st.phase == "decode"
            ]
            if not active:
                return 0
            needed = sum(
                1 for _, st in active if (st.length + 1) % self.page == 0
            )
            if needed <= self.alloc.available:
                break
            victim = max(active, key=lambda t: t[1].admit_order)[0]
            self._evict(victim)

        tokens = np.zeros((self.n_slots, 1), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        write_page = np.full((self.n_slots,), self.alloc.trash, np.int32)
        for s, st in active:
            tokens[s, 0] = st.req.generated[-1]
            pos[s] = st.length
            if (st.length + 1) % self.page == 0:
                pid = self.alloc.alloc()
                assert pid is not None  # reserved above
                st.pages.append(pid)
                self._page_table[s, st.length // self.page] = pid
                write_page[s] = pid

        # obs.NOOP when disabled: no span object, no args dict — the
        # telemetry hook adds zero allocations to the disabled decode step
        span = obs.NOOP
        if obs.enabled():
            span = obs.span("engine/decode_step", args={
                "active": len(active), "queue": len(self.pending),
                "free_pages": self.alloc.available,
            })
        with span:
            tok_ids, self.cache = self._decode(
                self.params, self.cache, tokens, pos,
                self._page_table.copy(), write_page,
            )
            tok_host = np.asarray(jax.device_get(tok_ids))
        self.stats["steps"] += 1
        self.stats["active_slot_steps"] += len(active)
        self.stats["decode_tokens"] += len(active)
        if obs.enabled():
            obs.counter("engine.decode_steps").inc()
            obs.counter("engine.decode_tokens").add(len(active))
            obs.gauge("engine.queue_depth").set(len(self.pending))
            obs.gauge("engine.page_pool_free").set(self.alloc.available)
            obs.gauge("engine.active_slots").set(len(active))
            # counter-track events: perfetto renders these as time series
            obs.trace_counter("engine.queue_depth", len(self.pending))
            obs.trace_counter("engine.page_pool_free", self.alloc.available)
            obs.trace_counter("engine.active_slots", len(active))
        for s, st in active:
            st.length += 1
            st.req.generated.append(int(tok_host[s]))
            if st.req.done:
                self._retire(s)
        return len(active)

    # --------------------------------------------------------------- warmup

    def warmup(self, prompt_lens: Sequence[int] = ()) -> None:
        """Compile the decode step, every prefill/graft bucket (at the
        engine's static prefill batch), and — when chunking is enabled —
        the single chunk shape, before the timed run (slots must be idle;
        the dummy writes all target the trash page / a tail ring the real
        graft overwrites).  Prompts longer than one chunk take the
        chunked path at runtime, so their buckets are skipped."""
        assert all(st is None for st in self.slots), "warmup needs an idle engine"
        buckets = {bucket_len(max(int(p), 1), self.page) for p in prompt_lens}
        if self.prefill_chunk is not None:
            buckets = {lb for lb in buckets if lb <= self.chunk_tokens}
        bsz = self.prefill_batch
        for lb in sorted(buckets):
            toks = np.zeros((bsz, lb), np.int32)
            with kv_quant_scope(None):
                _, pre = self._prefill(self.params, toks, np.ones((bsz,), np.int32))
            ids = np.full((bsz, lb // self.page), self.alloc.trash, np.int32)
            self.cache = self._graft(
                self.cache, pre, np.zeros((bsz,), np.int32), ids,
                np.ones((bsz,), np.int32),
            )
        if self.prefill_chunk is not None:
            ctk = self.chunk_tokens
            toks = np.zeros((1, ctk), np.int32)
            ids = np.full((ctk // self.page,), self.alloc.trash, np.int32)
            _, self.cache = self._chunk(
                self.params, self.cache, toks, np.int32(0), np.int32(0),
                ids, np.int32(1), self._page_table.copy(),
            )
        tokens = np.zeros((self.n_slots, 1), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        wp = np.full((self.n_slots,), self.alloc.trash, np.int32)
        _, self.cache = self._decode(
            self.params, self.cache, tokens, pos, self._page_table.copy(), wp
        )

    def decode_hlo(self) -> str:
        """StableHLO text of the decode step at the engine's static shapes,
        lowered and not compiled.  Each Pallas kernel that lowers to Mosaic
        appears as a ``tpu_custom_call``; an interpreted kernel does not."""
        tokens = np.zeros((self.n_slots, 1), np.int32)
        pos = np.zeros((self.n_slots,), np.int32)
        wp = np.full((self.n_slots,), self.alloc.trash, np.int32)
        return self._decode.lower(
            self.params, self.cache, tokens, pos, self._page_table.copy(), wp
        ).as_text()

    # ------------------------------------------------------------- run loop

    async def _feed(self, trace: List[Request], t0: float, time_scale: float):
        loop = asyncio.get_running_loop()
        for req in sorted(trace, key=lambda r: r.arrival):
            delay = (t0 + req.arrival * time_scale) - loop.time()
            if delay > 0:
                await asyncio.sleep(delay)
            req.submit_t = time.perf_counter()
            self.pending.append(req)

    async def _run_async(self, trace: List[Request], time_scale: float):
        for req in trace:
            self.validate(req)
        t_start = time.perf_counter()
        loop = asyncio.get_running_loop()
        feeder = asyncio.create_task(self._feed(trace, loop.time(), time_scale))
        last_step_end: Optional[float] = None
        try:
            while True:
                pb0 = self.stats["prefill_batches"]
                self.admit_pending()
                chunked = self._prefill_step()
                n = self.step()
                if n:
                    now = time.perf_counter()
                    if last_step_end is not None:
                        # decode-interference sample: the gap between two
                        # consecutive decode steps, split by whether
                        # prefill work (a chunk or a batch admission) ran
                        # inside it
                        gap = now - last_step_end
                        if chunked or self.stats["prefill_batches"] > pb0:
                            self._itl_with_prefill_s.append(gap)
                        else:
                            self._itl_decode_s.append(gap)
                    last_step_end = now
                prefilling = any(
                    st is not None and st.phase == "prefill" for st in self.slots
                )
                if n or chunked:
                    await asyncio.sleep(0)  # yield to the arrival feeder
                elif feeder.done() and not self.pending and not prefilling:
                    break
                else:
                    last_step_end = None  # idle: gaps are not ITL samples
                    await asyncio.sleep(0.0005)  # wait for arrivals
        finally:
            await feeder
        return self.report(time.perf_counter() - t_start)

    def run(self, trace: Sequence[Request], *, time_scale: float = 1.0) -> Dict[str, Any]:
        """Serve a trace to completion; returns the metrics report.
        ``time_scale`` compresses/stretches the trace's arrival times."""
        return asyncio.run(self._run_async(list(trace), time_scale))

    # -------------------------------------------------------------- metrics

    def report(self, wall_s: float) -> Dict[str, Any]:
        done = self.finished
        toks = sum(len(r.generated) for r in done)
        lat = [
            r.finish_t - r.submit_t
            for r in done
            if r.finish_t is not None and r.submit_t is not None
        ]
        ttft = [
            r.first_token_t - r.submit_t
            for r in done
            if r.first_token_t is not None and r.submit_t is not None
        ]
        # the telemetry histogram IS the percentile implementation — one
        # type shared with the benchmarks instead of inline pct() copies
        lat_h = Histogram.from_values(lat)
        ttft_h = Histogram.from_values(ttft)
        qwait_h = Histogram.from_values(r.queue_wait_s for r in done)
        # TTFT decomposition: queue_wait (scheduler) + prefill_compute
        # (device) + chunk_wait (interleaved-decode delay, chunked only)
        pcomp_h = Histogram.from_values(r.prefill_compute_s for r in done)
        cwait_h = Histogram.from_values(r.chunk_wait_s for r in done)
        evict_costs = [r.evict_cost_s for r in done if r.evictions]
        evict_h = Histogram.from_values(evict_costs)
        itl_h = Histogram.from_values(self._itl_decode_s)
        itl_pf_h = Histogram.from_values(self._itl_with_prefill_s)

        if obs.enabled():
            # trace-count watcher as a first-class metric (one gauge per
            # jitted fn; report() may run repeatedly, so not a counter)
            for fn, n in self.trace_counts.items():
                obs.gauge("engine.trace_count", {"fn": fn}).set(n)
            obs.gauge("engine.itl_p99_s").set(itl_h.percentile(99))
            obs.gauge("engine.itl_with_prefill_p99_s").set(itl_pf_h.percentile(99))

        steps = max(self.stats["steps"], 1)
        return {
            "requests": len(done),
            "generated_tokens": toks,
            "wall_s": round(wall_s, 4),
            "tokens_per_s": round(toks / max(wall_s, 1e-9), 2),
            "latency_p50_s": round(lat_h.percentile(50), 4),
            "latency_p99_s": round(lat_h.percentile(99), 4),
            "ttft_p50_s": round(ttft_h.percentile(50), 4),
            "ttft_p99_s": round(ttft_h.percentile(99), 4),
            "queue_wait_p50_s": round(qwait_h.percentile(50), 4),
            "queue_wait_p99_s": round(qwait_h.percentile(99), 4),
            "prefill_compute_p50_s": round(pcomp_h.percentile(50), 4),
            "prefill_compute_p99_s": round(pcomp_h.percentile(99), 4),
            "chunk_wait_p50_s": round(cwait_h.percentile(50), 4),
            "chunk_wait_p99_s": round(cwait_h.percentile(99), 4),
            "itl_p99_s": round(itl_h.percentile(99), 6),
            "itl_with_prefill_p99_s": round(itl_pf_h.percentile(99), 6),
            "itl_samples": len(self._itl_decode_s),
            "itl_with_prefill_samples": len(self._itl_with_prefill_s),
            "prefill_batches": self.stats["prefill_batches"],
            "prefill_rows": self.stats["prefill_rows"],
            "chunks": self.stats["chunks"],
            "prefix_hits": self.stats["prefix_hits"],
            "prefix_misses": self.stats["prefix_misses"],
            "prefix_pages_shared": self.stats["prefix_pages_shared"],
            "eviction_cost_total_s": round(evict_h.total, 4),
            "eviction_cost_p50_s": round(evict_h.percentile(50), 4),
            "slot_utilization": round(
                self.stats["active_slot_steps"] / (steps * self.n_slots), 4
            ),
            "evictions": self.stats["evictions"],
            "decode_steps": self.stats["steps"],
            "n_slots": self.n_slots,
            "n_pages": self.n_pages,
            "page": self.page,
            "trace_counts": dict(self.trace_counts),
            "outputs": {r.rid: list(r.generated) for r in done},
        }
