"""Real-model disaggregated serving engines (jitted JAX, CPU-testable).

``PrefillEngine`` runs the prompt pass and emits a per-request KV/state
cache bundle.  It keeps a **block-granular prefix cache** keyed by the same
chained ``block_hashes`` the router/indexer use: when a new prompt shares a
cached prefix (and the model supports resumable prefill — attention-only
stacks), the prompt pass *resumes* from the matched block boundary instead
of recomputing the prefix, so a cache-warm routing decision actually skips
real jitted compute.  Per-call and cumulative stats (reused blocks,
computed suffix tokens, estimated FLOPs, wall time) back the
``benchmarks/bench_backend_parity.py`` warm-vs-cold measurement.

``DecodeEngine`` holds a fixed-slot continuous batch whose per-slot lengths
advance independently (ragged decode with masked cache writes).  Finished
slots are released **inside** :meth:`DecodeEngine.step` — the returned-slot
contract: a ``done=True`` tuple means the slot is already free and
re-admittable in the same tick.  The engine also tracks which KV blocks are
resident (admitted and not yet evicted by the bounded LRU), so the
prefill→decode ``transfer()`` hop can be charged per *non-resident* block.
Every engine sits on the default device, so the hop is an in-process copy
on one device; the per-block charge is what reintroduces the KV-movement
cost the routing game is about.  A hop between chips does not exist yet.
"""
from __future__ import annotations

import functools
import time
from collections import Counter, OrderedDict
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.radix import BLOCK_SIZE, block_hashes
from repro.models.model import Model
from repro.serving.paging import PageAllocator


@dataclass
class PrefillStats:
    """Cumulative prefix-cache + batching accounting (one per engine)."""
    requests: int = 0
    total_blocks: int = 0        # full blocks across all prompts
    reused_blocks: int = 0       # blocks resumed from the prefix cache
    total_tokens: int = 0        # prompt tokens across all prompts
    computed_tokens: int = 0     # suffix tokens actually run through compute
    flops: float = 0.0           # ≈ 2·N_active·computed_tokens
    wall_s: float = 0.0          # jitted prompt-pass wall time
    batches: int = 0             # jitted prompt passes issued (any width)
    batched_requests: int = 0    # requests served by a width>1 pass
    padded_tokens: int = 0       # pad tokens run through compute (overhead)

    def as_dict(self) -> dict:
        return dict(requests=self.requests, total_blocks=self.total_blocks,
                    reused_blocks=self.reused_blocks,
                    total_tokens=self.total_tokens,
                    computed_tokens=self.computed_tokens,
                    flops=self.flops, wall_s=self.wall_s,
                    batches=self.batches,
                    batched_requests=self.batched_requests,
                    padded_tokens=self.padded_tokens)


class PrefillEngine:
    def __init__(self, model: Model, params, max_len: int,
                 cache_entries: int = 16, block_size: int = BLOCK_SIZE,
                 max_batch: int = 8):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.block_size = block_size
        self.cache_entries = cache_entries
        # batched prompt passes: cold prompts bucket into one right-padded
        # ragged pass (lengths vector), resumes group by (start, suffix).
        # Batch widths are padded to powers of two so the jit shape set
        # stays O(log max_batch) per length bucket.
        self.max_batch = max(1, max_batch)
        self._prefill = jax.jit(
            lambda p, batch: model.prefill(p, batch, max_len=max_len))
        self._prefill_batched = jax.jit(
            lambda p, toks, lens: model.prefill_batched(p, toks, lens,
                                                        max_len=max_len))
        # start is traced (one compile per suffix length, not per offset)
        self._resume = jax.jit(model.prefill_resume)
        # prefix cache: full hash chain of a completed prompt pass → its
        # cache bundle (K/V valid for every position of that prompt).  A
        # lookup matches the longest common *prefix* of chains — chained
        # hashes commit to the whole prefix, so chain equality at depth m
        # means token equality over the first m blocks.
        self._cache: "OrderedDict[Tuple[int, ...], object]" = OrderedDict()
        self.stats = PrefillStats()
        # per-token FLOPs estimate: 2·N_active (inference forward pass)
        self._flops_per_token = 2.0 * model.cfg.active_param_count()

    # ------------------------------------------------------ prefix cache ----

    def _best_match(self, hashes: Sequence[int]):
        """One walk over the cache: ``(depth, entry)`` of the deepest
        common-prefix chain (most recently used wins ties); the winner's
        LRU position is refreshed.  Chained hashes commit to their whole
        prefix, so chain equality at depth m means token equality over the
        first m blocks — any entry matching m blocks is a valid K/V donor
        for every resume point inside them."""
        best, donor, key = 0, None, None
        for chain in reversed(self._cache):   # most recent first
            m = _shared_depth(chain, hashes)
            if m > best:
                best, donor, key = m, self._cache[chain], chain
        if key is not None:
            self._cache.move_to_end(key)
        return best, donor

    def _resume_start(self, n: int, m: int) -> int:
        """Resume point of an ``n``-token prompt whose first ``m`` blocks
        are cached.  At least one suffix token is kept so the pass emits
        this prompt's logits; the donor matched ``m`` full blocks, which
        covers every position below any start ≤ m·block_size (including a
        non-boundary start inside the donor's last matched block).  0
        means a cold pass."""
        return max(0, min(m * self.block_size, n - 1))

    def resume_suffixes(self, prompts: Sequence[Sequence[int]]) -> List[int]:
        """Suffix lengths a prefix-cache resume can run over a stream of
        ``prompts`` — the warmup pre-compile set.  A prompt resumes from
        the full-block prefix it shares with a cached earlier prompt (an
        identical one included), so only the pairwise shared depths of
        the stream's distinct prompts can occur."""
        if not (self.model.supports_prefill_resume and self.cache_entries > 0):
            return []
        counts = Counter(tuple(p) for p in prompts)
        chains = {p: block_hashes(p, self.block_size) for p in counts}
        suffixes = set()
        for a, ha in chains.items():
            for b, hb in chains.items():
                if a == b and counts[a] < 2:
                    continue
                start = self._resume_start(len(a), _shared_depth(ha, hb))
                if start > 0:
                    suffixes.add(len(a) - start)
        return sorted(suffixes)

    def _store(self, hashes: Sequence[int], caches) -> None:
        if not hashes or self.cache_entries <= 0:
            return
        key = tuple(hashes)
        self._cache[key] = caches
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_entries:
            self._cache.popitem(last=False)

    def clear_cache(self) -> None:
        self._cache.clear()

    def dummy_caches(self, prompt_len: int):
        """A throwaway cache bundle from a zero-token prompt pass of
        ``prompt_len`` — for warmup flows that need a structurally valid
        bundle to drive admit/step compilation, without touching the
        prefix cache or the stats (and without callers reaching into the
        engine's jitted internals)."""
        batch = {"tokens": jnp.zeros((1, prompt_len), jnp.int32)}
        _, caches = self._prefill(self.params, batch)
        return caches

    def _padded_len(self, n: int) -> int:
        """Cold-bucket sequence length: next block multiple when the model
        tolerates right-padding, the exact length otherwise."""
        if self.model.supports_padded_prefill:
            return -(-n // self.block_size) * self.block_size
        return n

    def _width(self, n: int) -> int:
        """Batch width for ``n`` group members: next power of two, capped
        at ``max_batch`` — bounds the jitted shape set to O(log max_batch)
        widths per length bucket."""
        w = 1
        while w < min(n, self.max_batch):
            w *= 2
        return w

    def warmup(self, prompt_lengths: Sequence[int],
               suffix_lengths: Sequence[int] = (),
               batch_sizes: Sequence[int] = (1,)) -> None:
        """Pre-compile the jitted prompt passes for the given prompt (and
        resume-suffix) lengths, without touching the prefix cache or the
        stats — so measured runs and the saturation detector never see
        multi-second XLA compile walls as TTFT.

        ``batch_sizes`` lists the batched-pass widths to pre-compile (each
        rounded to its power-of-two width); the cold ragged pass compiles
        per (width, padded length) and resumes per (width, suffix length).

        Resume compilation is keyed on the suffix length alone (cache
        shapes are fixed at ``max_len`` and ``start`` is traced), so each
        suffix compiles once against one donor instead of once per
        (prompt, suffix) pair."""
        lengths = sorted(set(int(x) for x in prompt_lengths))
        caches = None
        for n in lengths:
            batch = {"tokens": jnp.zeros((1, n), jnp.int32)}
            _, caches = self._prefill(self.params, batch)
        widths = sorted({self._width(max(1, int(b))) for b in batch_sizes})
        for n in sorted({self._padded_len(x) for x in lengths}):
            for w in widths:
                self._prefill_batched(self.params,
                                      jnp.zeros((w, n), jnp.int32),
                                      jnp.ones((w,), jnp.int32))
        if caches is None or not self.model.supports_prefill_resume:
            return
        n_max = lengths[-1]
        suffixes = [s for s in sorted(set(int(x) for x in suffix_lengths))
                    if 0 < s < n_max]
        for w in widths:
            donor = caches if w == 1 else jax.tree.map(
                lambda a, w=w: jnp.concatenate([a] * w, axis=1), caches)
            for s in suffixes:
                self._resume(self.params, donor,
                             jnp.zeros((w, s), jnp.int32),
                             jnp.int32(n_max - s))

    # ----------------------------------------------------------- prefill ----

    def prefill(self, tokens: Sequence[int], extras: Optional[dict] = None,
                hashes: Optional[Sequence[int]] = None):
        """Single-request prompt pass → (last_logits (V,), cache bundle).

        Resumes from the longest cached block prefix when possible; a miss
        (or a model without resumable prefill, or multimodal ``extras``)
        pays the full jitted pass.  Always recomputes at least the last
        token so the returned logits are exact for *this* prompt."""
        resumable = (self.model.supports_prefill_resume and not extras
                     and self.cache_entries > 0)
        if hashes is None and resumable:
            hashes = block_hashes(tokens, self.block_size)
        hashes = tuple(hashes or ())
        start = 0
        donor = None
        if resumable and hashes:
            m, donor = self._best_match(hashes)
            start = self._resume_start(len(tokens), m)
            if start == 0:
                donor = None
        t0 = time.perf_counter()
        if start > 0:
            suffix = jnp.asarray(tokens[start:], jnp.int32)[None, :]
            logits, caches = self._resume(self.params, donor, suffix,
                                          jnp.int32(start))
        else:
            batch = {"tokens": jnp.asarray(tokens, jnp.int32)[None, :]}
            if extras:
                batch.update({k: jnp.asarray(v)[None]
                              for k, v in extras.items()})
            logits, caches = self._prefill(self.params, batch)
        logits = np.asarray(logits[0])
        wall = time.perf_counter() - t0
        st = self.stats
        st.requests += 1
        st.total_blocks += len(hashes)
        st.reused_blocks += start // self.block_size
        st.total_tokens += len(tokens)
        st.computed_tokens += len(tokens) - start
        st.flops += self._flops_per_token * (len(tokens) - start)
        st.wall_s += wall
        if resumable:
            self._store(hashes, caches)
        return logits, caches

    # --------------------------------------------------- batched prefill ----

    def prefill_many(self, requests: Sequence[Tuple[Sequence[int],
                                                    Optional[dict],
                                                    Optional[Sequence[int]]]]
                     ) -> List[Tuple[np.ndarray, object, int]]:
        """Batched prompt passes across queued requests.

        ``requests``: ``(tokens, extras, hashes)`` triples (``hashes`` may
        be None).  Returns a list aligned with the input order of
        ``(last_logits (V,), cache_bundle, row)`` — ``cache_bundle`` is
        the (possibly shared) batch bundle and ``row`` the request's batch
        row, consumable by :meth:`DecodeEngine.admit` via ``src_row``.

        Grouping: multimodal requests (``extras``) fall back to the
        single-request path; prefix-cache hits group by (resume start,
        suffix length) and run one stacked-donor resume pass; cold prompts
        bucket by padded length (block multiple for models that tolerate
        right-padding, exact length otherwise) and run one right-padded
        ragged pass over the per-row lengths vector.  Identical prompts
        inside one call collapse onto a single batch row.  Every grouped
        pass is pinned logit-comparable to the sequential path by
        ``tests/test_engine_batching.py``."""
        n = len(requests)
        results: List[Optional[Tuple[np.ndarray, object, int]]] = [None] * n
        st = self.stats
        can_resume = self.model.supports_prefill_resume and \
            self.cache_entries > 0
        # --- resolve: dedupe identical prompts, match prefix cache once ---
        cold: dict = {}     # padded_len -> [(idx, tokens, hashes)]
        resume: dict = {}   # (start, plen) -> [(idx, tokens, hashes, donor)]
        alias: List[Tuple[int, int]] = []   # (dup idx, primary idx)
        seen: dict = {}     # tokens tuple -> primary idx
        for i, (tokens, extras, hashes) in enumerate(requests):
            if extras:
                # multimodal inputs carry per-request arrays; keep them on
                # the exact single-request path
                logits, caches = self.prefill(tokens, extras, hashes=hashes)
                results[i] = (logits, caches, 0)
                continue
            key = tuple(tokens)
            if key in seen:
                alias.append((i, seen[key]))
                continue
            seen[key] = i
            resumable = can_resume
            if hashes is None and resumable:
                hashes = block_hashes(tokens, self.block_size)
            hashes = tuple(hashes or ())
            start, donor = 0, None
            if resumable and hashes:
                m, donor = self._best_match(hashes)
                start = self._resume_start(len(tokens), m)
                if start == 0:
                    donor = None
            if donor is not None:
                resume.setdefault((start, len(tokens)), []).append(
                    (i, tokens, hashes, donor))
            else:
                cold.setdefault(self._padded_len(len(tokens)), []).append(
                    (i, tokens, hashes))
        # --- cold buckets: one ragged right-padded pass per chunk ---------
        for plen, group in cold.items():
            for c0 in range(0, len(group), self.max_batch):
                self._run_cold_chunk(plen, group[c0:c0 + self.max_batch],
                                     results)
        # --- resume groups: one stacked-donor pass per chunk --------------
        for (start, _), group in resume.items():
            for c0 in range(0, len(group), self.max_batch):
                self._run_resume_chunk(start, group[c0:c0 + self.max_batch],
                                       results)
        for i, j in alias:
            results[i] = results[j]
            st.requests += 1
            st.total_blocks += len(tuple(requests[i][2] or ()))
            st.total_tokens += len(requests[i][0])
        return results  # fully populated: every request hit exactly one path

    def _run_cold_chunk(self, plen: int, group, results) -> None:
        w = self._width(len(group))
        toks = np.zeros((w, plen), np.int32)
        lens = np.ones((w,), np.int32)
        for r, (_, tokens, _) in enumerate(group):
            toks[r, :len(tokens)] = tokens
            lens[r] = len(tokens)
        t0 = time.perf_counter()
        logits, caches = self._prefill_batched(
            self.params, jnp.asarray(toks), jnp.asarray(lens))
        logits = np.asarray(logits)
        wall = time.perf_counter() - t0
        st = self.stats
        st.batches += 1
        st.wall_s += wall
        if len(group) > 1:
            st.batched_requests += len(group)
        # pad overhead: right-padding inside rows + power-of-two pad rows
        st.padded_tokens += int(np.sum(plen - lens[:len(group)])) \
            + (w - len(group)) * plen
        for r, (i, tokens, hashes) in enumerate(group):
            st.requests += 1
            st.total_blocks += len(hashes)
            st.total_tokens += len(tokens)
            st.computed_tokens += len(tokens)
            st.flops += self._flops_per_token * len(tokens)
            results[i] = (logits[r], caches, r)
            if hashes and self.model.supports_prefill_resume \
                    and self.cache_entries > 0:
                self._store(hashes, jax.tree.map(
                    lambda a, r=r: a[:, r:r + 1], caches))

    def _run_resume_chunk(self, start: int, group, results) -> None:
        w = self._width(len(group))
        suffixes = np.stack(
            [np.asarray(tokens[start:], np.int32) for _, tokens, _, _ in group]
            + [np.asarray(group[0][1][start:], np.int32)] * (w - len(group)))
        donors = [d for *_, d in group] + [group[0][3]] * (w - len(group))
        stacked = donors[0] if w == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1), *donors)
        t0 = time.perf_counter()
        logits, caches = self._resume(self.params, stacked,
                                      jnp.asarray(suffixes), jnp.int32(start))
        logits = np.asarray(logits)
        wall = time.perf_counter() - t0
        st = self.stats
        st.batches += 1
        st.wall_s += wall
        if len(group) > 1:
            st.batched_requests += len(group)
        st.padded_tokens += (w - len(group)) * suffixes.shape[1]
        for r, (i, tokens, hashes, _) in enumerate(group):
            st.requests += 1
            st.total_blocks += len(hashes)
            st.reused_blocks += start // self.block_size
            st.total_tokens += len(tokens)
            st.computed_tokens += len(tokens) - start
            st.flops += self._flops_per_token * (len(tokens) - start)
            results[i] = (logits[r], caches, r)
            if hashes:
                self._store(hashes, jax.tree.map(
                    lambda a, r=r: a[:, r:r + 1], caches))


def _shared_depth(a: Sequence[int], b: Sequence[int]) -> int:
    """Leading positions on which two block-hash chains agree."""
    m = 0
    for x, y in zip(a, b):
        if x != y:
            break
        m += 1
    return m


@dataclass
class Slot:
    active: bool = False
    request_id: Optional[str] = None
    length: int = 0
    generated: List[int] = field(default_factory=list)
    max_new: int = 0


PAGED_IMPLS = ("paged", "paged_sdpa")


class DecodeEngine:
    """Fixed-slot continuous batcher around the jitted ragged decode step.

    ``decode_impl`` selects the cached-attention step: ``"pallas"``
    (default) streams the KV cache through the ragged Pallas decode kernel
    on the per-slot lengths vector (TPU-compiled, interpret mode on CPU);
    ``"sdpa"`` keeps the XLA einsum reference path — the two are pinned
    token-stream identical by ``tests/test_engine_batching.py``.

    The paged impls swap the dense per-slot ``max_len`` KV layout for a
    global page pool of ``num_pages`` KV blocks plus a per-slot page table:
    ``"paged"`` runs the Pallas paged-attention kernel (page-table-
    indirected block loads), ``"paged_sdpa"`` gathers the slot's pages into
    a dense view and reuses the XLA causal path.  Admission is then gated
    on *free pages* (:meth:`can_admit`) instead of free slots alone, the
    jitted step grows a slot's table when generation crosses a block
    boundary, and :meth:`release` returns the pages to the free list — so
    the same KV HBM budget sustains many more concurrent short/medium
    requests.  ``num_pages=None`` sizes the pool to the dense worst case
    ``num_slots * ceil(max_len / block)``, where the page gate can never
    bind and the admission stream is identical to the dense layout's."""

    def __init__(self, model: Model, params, num_slots: int, max_len: int,
                 worker_id: int = 0, resident_blocks: int = 4096,
                 decode_impl: str = "pallas",
                 num_pages: Optional[int] = None,
                 page_block: int = BLOCK_SIZE):
        if decode_impl not in ("pallas", "sdpa") + PAGED_IMPLS:
            raise ValueError(f"unknown decode_impl {decode_impl!r}")
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.worker_id = worker_id
        self.decode_impl = decode_impl
        self.paged = decode_impl in PAGED_IMPLS
        self.slots = [Slot() for _ in range(num_slots)]
        self.tokens = np.zeros((num_slots, 1), np.int32)
        if self.paged:
            if not model.supports_paged_decode:
                raise ValueError(
                    f"{model.cfg.name} has non-attention mixers; paged KV "
                    "needs a pure causal-attention stack")
            self.page_block = page_block
            self.max_pages_per_slot = -(-max_len // page_block)
            if num_pages is None:
                num_pages = num_slots * self.max_pages_per_slot
            self.allocator = PageAllocator(num_pages, page_block)
            self.caches = model.paged_cache_init(num_pages, page_block)
            # page table starts one page wide and widens along the
            # power-of-two ladder as slots grow (each width is one jit
            # specialization of the decode step; warmup can pre-compile
            # the ladder).  Unmapped entries stay 0 — the trash page.
            self.page_table = np.zeros((num_slots, 1), np.int32)
            self._adopt = jax.jit(
                functools.partial(adopt_prefill_pages, block=page_block),
                donate_argnums=0)
        else:
            self.allocator = None
            self.caches = model.cache_init(num_slots, max_len)
        self._decode = jax.jit(
            functools.partial(model.decode, decode_impl=decode_impl),
            donate_argnums=1)
        # KV-block residency (the worker's G1 view): bounded LRU over the
        # block hashes this worker has admitted.  The transfer() hop is
        # charged only for blocks NOT in this set — a cache-warm routing
        # decision ships less KV.
        self.resident_cap = resident_blocks
        self._resident: "OrderedDict[int, None]" = OrderedDict()
        self.transferred_blocks = 0      # cumulative non-resident blocks

    # -------------------------------------------------------------- admit ---

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if not s.active:
                return i
        return None

    def _touch_blocks(self, hashes: Sequence[int]) -> int:
        """Mark ``hashes`` resident (LRU refresh); returns the number of
        blocks that were NOT already resident — the transfer() payload."""
        new = 0
        for h in hashes:
            if h in self._resident:
                self._resident.move_to_end(h)
            else:
                self._resident[h] = None
                new += 1
        while len(self._resident) > self.resident_cap:
            self._resident.popitem(last=False)
        return new

    # ------------------------------------------------------------- paging ---

    def pages_for_request(self, prompt_len: int, max_new: int) -> int:
        """Worst-case page count of a request: prompt + every generated
        token + the admission first-token write, capped by the engine's
        ``max_len`` stop condition."""
        total = min(prompt_len + max_new + 1, self.max_len)
        return self.allocator.pages_for(total)

    def pages_for_prompt(self, prompt_len: int) -> int:
        """Pages mapped at admit time: the prompt plus one position for the
        first generated token's KV write."""
        return self.allocator.pages_for(min(prompt_len + 1, self.max_len))

    def can_admit(self, prompt_len: int, max_new: int) -> bool:
        """Admission gate: dense layouts admit on slots alone; the paged
        layout additionally requires the request's worst-case page count to
        be coverable by pages not promised to already-scheduled slots."""
        if not self.paged:
            return True
        return self.allocator.can_admit(
            self.pages_for_request(prompt_len, max_new))

    def _table_width(self, n_pages: int) -> int:
        """Page-table width holding ``n_pages``: next power of two, capped
        at the ``max_len`` worst case — keeps the jitted decode shape set
        O(log max_pages_per_slot)."""
        w = 1
        while w < n_pages:
            w *= 2
        return min(w, self.max_pages_per_slot)

    def width_ladder(self, total_tokens: Optional[int] = None,
                     min_prompt: int = 0) -> List[int]:
        """Every page-table width a run can emit, widest bounded by
        ``total_tokens`` (prompt + generated; None = the ``max_len`` worst
        case) — the warmup pre-compile set for the decode step.  The table
        only widens, and admission already maps a prompt's pages, so widths
        below the shortest prompt's (``min_prompt`` tokens) never step."""
        top = self.max_pages_per_slot if total_tokens is None else \
            self._table_width(self.allocator.pages_for(
                min(total_tokens, self.max_len)))
        w = self._table_width(self.pages_for_prompt(min_prompt)) \
            if min_prompt > 0 else 1
        ladder = []
        while w < top:
            ladder.append(w)
            w = self._table_width(2 * w)
        ladder.append(top)
        return ladder

    def _widen_table(self, width: int) -> None:
        if width > self.page_table.shape[1]:
            pad = width - self.page_table.shape[1]
            self.page_table = np.pad(self.page_table, ((0, 0), (0, pad)))

    def kv_bytes_held(self) -> int:
        """KV HBM bytes currently committed to requests: dense layouts
        commit every slot's full ``max_len`` rows up front; the paged pool
        commits only mapped pages."""
        if self.paged:
            tokens = self.allocator.used_pages * self.page_block
        else:
            tokens = self.num_slots * self.max_len
        return tokens * kv_token_bytes(self.model)

    def pool_utilization(self) -> float:
        """Fraction of the page pool currently mapped to live slots
        (dense layouts are always fully committed)."""
        if not self.paged:
            return 1.0
        return self.allocator.used_pages / max(1, self.allocator.num_pages)

    # -------------------------------------------------------------- admit ---

    def reserve(self, slot: int, request_id: str,
                prompt_len: Optional[int] = None,
                max_new: int = 0) -> None:
        """Claim ``slot`` for ``request_id`` before its (batched) prefill
        has produced a cache bundle, so a scheduler placing several
        requests in one tick sees consistent ``free_slot`` accounting.
        A reserved-but-unadmitted slot holds no cache state: :meth:`step`
        skips it until :meth:`admit` lands (or :meth:`release` frees
        it).

        On a paged engine, passing ``prompt_len`` also reserves the
        request's worst-case page count, so several reservations in one
        scheduling tick cannot double-count the same free pages (gate with
        :meth:`can_admit` first)."""
        s = self.slots[slot]
        assert not s.active, (slot, s.request_id)
        if self.paged and prompt_len is not None:
            ok = self.allocator.reserve(
                slot, self.pages_for_request(prompt_len, max_new))
            assert ok, (slot, "reserve() without a can_admit() gate")
        s.active = True
        s.request_id = request_id

    def admit(self, slot: int, request_id: str, prefill_caches,
              first_token: int, prompt_len: int, max_new: int,
              hashes: Sequence[int] = (), src_row: int = 0) -> int:
        """Transfer a prefill cache bundle into ``slot`` (the NIXL hop).

        ``src_row`` selects the bundle's batch row (batched prefill hands
        every request of a group the same shared bundle).

        Returns the number of *non-resident* blocks the transfer had to
        move — the per-block charge of the prefill→decode hop.  Blocks
        already resident (an earlier request of the same template landed
        here) ride for free; that asymmetry is the cache-affinity
        externality on the real path.

        Paged engines map the prompt's pages from the free list (plus one
        position for the first token's KV write) and scatter the prefill
        KV into them at block granularity; the rest of the request's
        worst case stays reserved for mid-generation :meth:`step` growth.
        Callers that skipped :meth:`reserve` must gate on
        :meth:`can_admit` — an ungated paged admit raises."""
        if self.paged:
            n_map = self.pages_for_prompt(prompt_len)
            pages = self.allocator.admit(
                slot, n_map, self.pages_for_request(prompt_len, max_new))
            if pages is None:
                raise RuntimeError(
                    f"page pool exhausted admitting {request_id!r} to slot "
                    f"{slot}: gate admission on can_admit()")
            self._widen_table(self._table_width(len(pages)))
            self.page_table[slot, :] = 0
            self.page_table[slot, :len(pages)] = pages
            row = jax.tree.map(
                lambda a: jax.lax.slice_in_dim(a, src_row, src_row + 1,
                                               axis=1), prefill_caches)
            self.caches = self._adopt(self.caches, row,
                                      jnp.asarray(pages, jnp.int32))
        else:
            self.caches = _insert_cache(self.caches, prefill_caches, slot,
                                        src_row)
        s = self.slots[slot]
        s.active = True
        s.request_id = request_id
        s.length = prompt_len
        s.generated = [int(first_token)]
        s.max_new = max_new
        self.tokens[slot, 0] = first_token
        moved = self._touch_blocks(hashes)
        self.transferred_blocks += moved
        return moved

    def release(self, slot: int):
        if self.paged:
            self.allocator.release(slot)
            self.page_table[slot, :] = 0
        self.slots[slot] = Slot()
        self.tokens[slot, 0] = 0

    @property
    def active_count(self) -> int:
        return sum(s.active for s in self.slots)

    def warmup(self, table_widths: Optional[Sequence[int]] = None) -> None:
        """Pre-compile the jitted decode step (slots all inactive; whatever
        the pass writes is fully overwritten on the next ``admit``).

        On a paged engine, ``table_widths`` lists the page-table widths to
        pre-compile (each width is its own decode-step shape — the
        page-growth recompile points; see :meth:`width_ladder`); None
        compiles the live table's current width.  The live table keeps its
        current width; pre-compiled shapes are hit when admission or growth
        widens it later."""
        lengths = jnp.zeros((self.num_slots,), jnp.int32)
        if not self.paged:
            _, self.caches = self._decode(self.params, self.caches,
                                          jnp.asarray(self.tokens), lengths)
            return
        widths = sorted({int(w) for w in (table_widths
                                          or (self.page_table.shape[1],))})
        for w in widths:
            table = jnp.zeros((self.num_slots, w), jnp.int32)
            _, self.caches = self._decode(self.params, self.caches,
                                          jnp.asarray(self.tokens), lengths,
                                          page_table=table)

    # --------------------------------------------------------------- step ---

    def step(self) -> List[Tuple[str, int, bool]]:
        """One batched decode tick. Returns [(request_id, token, done)].

        Returned-slot contract: when ``done`` is True the slot has already
        been released inside this step — it is free for admission in the
        same tick, and callers must NOT call :meth:`release` again."""
        if not any(s.active and s.generated for s in self.slots):
            return []
        # reserved-but-unadmitted slots (active, no first token yet) carry
        # no valid cache state: they decode as length-0 rows and their
        # output is skipped below
        lengths = jnp.asarray([s.length if s.active else 0
                               for s in self.slots], jnp.int32)
        if self.paged:
            # growth pre-pass: this tick writes each admitted slot's KV at
            # position s.length — if that crosses into an unmapped block,
            # map one page from the slot's reservation (and widen the
            # table to the next ladder width when the row is full).
            for i, s in enumerate(self.slots):
                if not s.active or not s.generated:
                    continue
                j = s.length // self.page_block
                if j >= len(self.allocator.owned[i]):
                    page = self.allocator.grow(i)
                    self._widen_table(self._table_width(j + 1))
                    self.page_table[i, j] = page
            logits, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(self.tokens), lengths,
                page_table=jnp.asarray(self.page_table))
        else:
            logits, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(self.tokens), lengths)
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        out = []
        for i, s in enumerate(self.slots):
            if not s.active or not s.generated:
                continue
            tok = int(nxt[i])
            s.generated.append(tok)
            s.length += 1
            self.tokens[i, 0] = tok
            done = (len(s.generated) >= s.max_new + 1
                    or s.length >= self.max_len - 1)
            out.append((s.request_id, tok, done))
            if done:
                self.release(i)   # slot is re-admittable this same tick
        return out


def kv_token_bytes(model: Model) -> int:
    """KV HBM bytes per cached token position (all layers, K and V)."""
    cfg = model.cfg
    n_attn = sum(d.mixer == "attn" for d in model.descs) * model.n_periods
    itemsize = jnp.dtype(jnp.bfloat16).itemsize
    return 2 * n_attn * cfg.num_kv_heads * cfg.resolved_head_dim * itemsize


def adopt_prefill_pages(pool, row_bundle, page_ids, *, block: int):
    """Scatter one prefill cache row into freshly mapped pool pages.

    ``pool``: paged cache pytree (leaves ``(P, N, block, K, hd)``);
    ``row_bundle``: a single-row prefill bundle (leaves ``(P, 1, S, K, hd)``
    — callers slice ``src_row`` out first so the jit specializes on the
    page count, not the prefill batch width); ``page_ids``: (n,) int32
    destination pages.  The row's first ``n * block`` positions land in the
    pages in order (right-padded with zeros when the prefill sequence axis
    is shorter; positions past the prompt are masked by length and
    overwritten by decode before any query reaches them)."""
    n = page_ids.shape[0]
    def leaf(d, s):
        src = s[:, 0]                                     # (P, S, ...)
        need = n * block
        if src.shape[1] < need:
            pads = [(0, 0), (0, need - src.shape[1])]
            pads += [(0, 0)] * (src.ndim - 2)
            src = jnp.pad(src, pads)
        blocks = src[:, :need].reshape(
            (src.shape[0], n, block) + src.shape[2:])
        return d.at[:, page_ids].set(blocks.astype(d.dtype))
    return jax.tree.map(leaf, pool, row_bundle)


@functools.partial(jax.jit, donate_argnums=0)
def _insert_cache(dst, src, slot, src_row):
    """Write row ``src_row`` of a prefill cache bundle into decode slot
    ``slot`` (batched prefill emits multi-row bundles; the sequential path
    keeps row 0).  ``dst`` is donated, so the decoder cache is updated in
    place, and ``slot``/``src_row`` are traced: one compile per bundle
    shape serves every slot and row."""
    def leaf(d, s):
        # d: (P, B, ...); s: (P, W, ...) — prefill cache may have a shorter
        # sequence axis than the decode cache; pad on the right.
        if s.shape[2:] != d.shape[2:]:
            pads = [(0, 0), (0, 0)]
            for ds, ss in zip(d.shape[2:], s.shape[2:]):
                pads.append((0, ds - ss))
            s = jnp.pad(s, pads)
        row = jax.lax.dynamic_index_in_dim(s, src_row, axis=1,
                                           keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(d, row.astype(d.dtype),
                                                   slot, axis=1)
    return jax.tree.map(leaf, dst, src)
