"""Paged KV cache: device pages + host-side page allocator.

vLLM-style paging re-designed for XLA's static-shape world (SURVEY.md §7
"the hard parts"): the device holds a fixed pool of KV pages per layer,
(L, num_pages, page_size, Hkv*D; each model module's ``init_paged_cache``) — heads folded into the minor axis for
lane-aligned page DMA (ops/paged_attention.py). The allocator is plain
host Python: slots own ordered page lists, pages are allocated at
prefill admission and lazily when decode crosses a page boundary, and
freeing a slot returns its pages to the pool. The jitted step functions
only ever see dense int32 arrays (page table, flat write indices), so no
recompilation happens as requests come and go.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import numpy as np


class OutOfPagesError(RuntimeError):
    """KV page pressure. ``recoverable`` distinguishes pool exhaustion
    (freeing other slots' pages would help — the scheduler may preempt
    instead of failing, ISSUE 7) from a per-slot structural limit that
    no amount of preemption can satisfy. ``slot`` is tagged by the
    engine so failures attribute to one request, not the whole batch."""

    def __init__(self, msg: str = "KV page pool exhausted", *, needed: int = 0,
                 free: int = 0, recoverable: bool = True) -> None:
        super().__init__(msg)
        self.needed = needed
        self.free = free
        self.recoverable = recoverable
        self.slot: int | None = None


@dataclass
class PagedCacheConfig:
    page_size: int = 32
    num_pages: int = 0  # 0 = full reservation: max_slots * max_seq_len / page_size
    max_slots: int = 8
    max_seq_len: int = 512
    # Rows of the pool a context of n tokens holds, where that is not n
    # (``models/family.Family.cached_rows``, ISSUE 61: it may FALL as n grows,
    # so what a slot holds pages for is the most it has held on the way: ``peak_rows``).
    rows: Callable[[Any], Any] | None = None

    def __post_init__(self) -> None:
        self._peak = None if self.rows is None else np.maximum.accumulate(
            np.asarray(self.rows(np.arange(self.max_seq_len + 1)), np.int64))

    def peak_rows(self, n_tokens: int) -> int:
        """The most rows a slot has held by the time its context is ``n_tokens``
        (past ``max_seq_len``: more than a slot's pages hold)."""
        if self._peak is None:
            return n_tokens
        if n_tokens <= self.max_seq_len:
            return int(self._peak[n_tokens])
        return self.max_pages_per_slot * self.page_size + n_tokens - self.max_seq_len

    def resolve_num_pages(self) -> int:
        if self.num_pages:
            return self.num_pages
        return self.max_slots * self.max_pages_per_slot

    @property
    def max_pages_per_slot(self) -> int:
        return (self.peak_rows(self.max_seq_len) + self.page_size - 1) // self.page_size


class PageAllocator:
    """Host-side page bookkeeping with refcounts (shared prefix pages);
    not thread-safe (engine holds the lock)."""

    def __init__(self, cfg: PagedCacheConfig):
        self.cfg = cfg
        self.num_pages = cfg.resolve_num_pages()
        self._free: list[int] = list(range(self.num_pages))
        # Pool high-water mark (ISSUE 19): most pages ever simultaneously
        # out of the free list — the /debug/hbm KV pane's sizing signal.
        self.pages_high_water = 0
        self._refs: dict[int, int] = {}
        self._slot_pages: dict[int, list[int]] = {}
        # Dense page table handed to jit; row per slot, padded with
        # num_pages (an out-of-range page the kernels never dereference
        # because lengths bound the walk).
        self._table = np.zeros((cfg.max_slots, self.cfg.max_pages_per_slot), np.int32)

    def free_page_count(self) -> int:
        return len(self._free)

    def pages_of(self, slot: int) -> list[int]:
        return self._slot_pages.get(slot, [])

    def incref(self, page: int) -> None:
        self._refs[page] = self._refs.get(page, 0) + 1

    def decref(self, page: int) -> None:
        self._refs[page] -= 1
        if self._refs[page] == 0:
            del self._refs[page]
            self._free.append(page)

    def adopt_pages(self, slot: int, pages: list[int]) -> None:
        """Start a slot's page list from shared (already-ref'd) pages."""
        assert slot not in self._slot_pages or not self._slot_pages[slot]
        self._slot_pages[slot] = list(pages)
        for i, p in enumerate(pages):
            self._table[slot, i] = p

    def ensure_capacity(self, slot: int, n_tokens: int) -> None:
        """Grow the slot's page list to cover n_tokens total tokens."""
        pages = self._slot_pages.setdefault(slot, [])
        needed = (self.cfg.peak_rows(n_tokens) + self.cfg.page_size - 1) // self.cfg.page_size
        if needed > self.cfg.max_pages_per_slot:
            raise OutOfPagesError(
                f"slot {slot} needs {needed} pages > per-slot max",
                needed=needed, free=len(self._free), recoverable=False)
        while len(pages) < needed:
            if not self._free:
                raise OutOfPagesError(
                    "KV page pool exhausted",
                    needed=needed - len(pages), free=0)
            page = self._free.pop()
            self._refs[page] = 1
            self._table[slot, len(pages)] = page
            pages.append(page)
        in_use = self.num_pages - len(self._free)
        if in_use > self.pages_high_water:
            self.pages_high_water = in_use

    def release(self, slot: int) -> None:
        pages = self._slot_pages.pop(slot, [])
        for p in pages:
            self.decref(p)
        self._table[slot, :] = 0

    def page_table(self) -> np.ndarray:
        return self._table

    def flat_write_indices(self, slot: int, start: int, count: int) -> np.ndarray:
        """Flat (page*page_size + offset) cache positions for tokens
        [start, start+count) of this slot."""
        ps = self.cfg.page_size
        pages = self._slot_pages.get(slot, [])
        if self.cfg.rows is not None:   # a token's row is not its position (ISSUE 61)
            t = np.asarray(self.cfg.rows(np.arange(start + 1, start + count + 1)), np.int64) - 1
            return np.asarray(pages, np.int64)[t // ps] * ps + t % ps
        out = np.empty((count,), np.int64)
        for i in range(count):
            t = start + i
            out[i] = pages[t // ps] * ps + (t % ps)
        return out


class PrefixCache:
    """Automatic prefix caching over full KV pages.

    Requests sharing a prompt prefix (system prompts, few-shot headers)
    reuse the prefix's KV pages instead of recomputing them: pages are
    read-only once full, so sharing needs no copy-on-write — new tokens
    always land in later pages. Entries are chain-digested per page with
    blake2b (digest_i = H(digest_{i-1} || page_tokens_i)) AND store the
    page's tokens, which are compared exactly on match — a digest
    collision can therefore never attach another request's KV pages to a
    new prompt (the weakness that moved vLLM's prefix cache to SHA-256).
    Evicted LRU when the pool runs low. TTFT for cached prefixes drops to
    the cost of the tail.
    """

    def __init__(self, allocator: PageAllocator, max_cached_pages: int | None = None,
                 decline_hits: bool = False, successor: bool = False):
        from collections import OrderedDict

        # A model whose next-token module caches, a position, a row that depends on
        # the token BEHIND it (ISSUE 56): a page is the same only where the token
        # behind its last is too, so an entry's tokens are the page's and that one.
        self.successor = int(successor)

        # A model that keeps a recurrent state per slot (models/phi4flash.py)
        # has nothing to hand over with the pages: the cache still learns the
        # prefixes, holds no page for them, declines every hit and counts it.
        self.decline_hits = decline_hits
        self.declined = 0

        self.allocator = allocator
        self.page_size = allocator.cfg.page_size
        self.max_cached_pages = max_cached_pages or max(allocator.num_pages // 2, 1)
        # chain_digest -> (page index, page tokens); ordered for LRU.
        self._entries: "OrderedDict[bytes, tuple[int, tuple[int, ...]]]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def _chain(prev: bytes, tokens: tuple[int, ...]) -> bytes:
        import hashlib

        h = hashlib.blake2b(prev, digest_size=16)
        h.update(np.asarray(tokens, np.int64).tobytes())
        return h.digest()

    def match(self, prompt: list[int]) -> tuple[list[int], int]:
        """Longest cached page-aligned prefix: (shared pages incref'd,
        matched token count). Always leaves ≥1 token to prefill so the
        request samples from a real forward pass."""
        ps = self.page_size
        pages: list[int] = []
        matched = 0
        chain = b""
        n_full = (len(prompt) - 1) // ps  # last token never comes from cache
        for i in range(n_full):
            chunk = tuple(prompt[i * ps:(i + 1) * ps + self.successor])
            chain = self._chain(chain, chunk)
            entry = self._entries.get(chain)
            if entry is None or entry[1] != chunk:  # exact-token guard
                break
            self._entries.move_to_end(chain)
            pages.append(entry[0])
            matched += ps
        if self.decline_hits:
            self.declined += bool(pages)
            self.misses += 1
            return [], 0
        for p in pages:
            self.allocator.incref(p)
        if pages:
            self.hits += 1
        else:
            self.misses += 1
        return pages, matched

    def insert(self, prompt: list[int], slot_pages: list[int]) -> None:
        """Register the request's full prefix pages for reuse."""
        ps = self.page_size
        chain = b""
        n_full = min((len(prompt) - self.successor) // ps, len(slot_pages))
        for i in range(n_full):
            chunk = tuple(prompt[i * ps:(i + 1) * ps + self.successor])
            chain = self._chain(chain, chunk)
            if chain in self._entries:
                self._entries.move_to_end(chain)
                continue
            if len(self._entries) >= self.max_cached_pages:
                self._evict_one()
                if len(self._entries) >= self.max_cached_pages:
                    return
            page = slot_pages[i]
            if not self.decline_hits:
                self.allocator.incref(page)  # cache's own hold
            self._entries[chain] = (page, chunk)

    def _evict_one(self) -> None:
        if not self._entries:
            return
        _, (page, _tokens) = self._entries.popitem(last=False)
        if not self.decline_hits:
            self.allocator.decref(page)

    def evict_for_pressure(self, min_free: int) -> None:
        while self.allocator.free_page_count() < min_free and self._entries:
            self._evict_one()

    def stats(self) -> dict:
        out = {"cached_pages": len(self._entries), "hits": self.hits, "misses": self.misses}
        return {**out, "declined": self.declined} if self.decline_hits else out

