"""Prefix-aware KV-cache subsystem (ISSUE 5 tentpole).

``bigdl_tpu/llm/kvcache`` owns the page pool that used to be embedded in
``LLMServer`` and adds prefix reuse on top of it:

- :mod:`~bigdl_tpu.llm.kvcache.pool` — refcounted page pool with
  copy-on-write fork semantics and the admission-budget ledger;
- :mod:`~bigdl_tpu.llm.kvcache.radix` — radix prefix index keyed on
  page-size token chunks, leaf-first LRU eviction;
- :mod:`~bigdl_tpu.llm.kvcache.prefill` — the closures every family's
  ragged in-place prefill and decode step share (attention over the
  pool where it sits, the COW tail fork, the suffix write) and the
  mixed / speculative steps composed from a family's two programs;
- :class:`KVCacheManager` (here) — the engine-facing façade: admission
  lookup + suffix-only budget charging, adoption refcounts/pins,
  chain insertion at prefill and EOS, on-demand LRU eviction (the
  ``kvcache.evict`` fault site), and hit/miss/evict accounting.

``bigdl.llm.kvcache.enabled=false`` (the default) keeps the manager as
a pure pool wrapper: no radix index is constructed, no
``bigdl_kvcache_*`` series are declared, every admission charges the
full worst case, and page ids flow in the seed engine's exact order —
the engine is bit-identical to the pre-kvcache one (asserted in
tests/test_kvcache.py).

See docs/KVCACHE.md for the page lifecycle and the invariants.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from bigdl_tpu.llm.kvcache.pool import PagePool, PagePoolError
from bigdl_tpu.llm.kvcache.radix import PrefixMatch, RadixIndex


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class Admission:
    """One admitted request's cache grant, held per engine slot.

    ``charge`` is the suffix-only budget reservation (released wholesale
    at EOS); ``shared_pages`` the adopted full-prefix pages (one pool
    ref + a possibly-shared pin each); ``tail_src`` the COW fork source
    page when the match ended mid-page (a transient ref/pin dropped as
    soon as the partial prefill is dispatched).

    Host-tier extension (ISSUE 6): when part of the matched prefix is
    resident in the host arena, ``fetch`` names its ``(key, slot)``
    chunks, ``fetch_job`` the in-flight migration uploading them, and
    ``fetch_reserved`` the budget pre-charged for their future pool
    pages. ``matched_len`` already INCLUDES the host chunks; if the
    fetch fails, :meth:`KVCacheManager.degrade` rolls it back to
    ``device_matched`` and converts the pre-charge into plain suffix
    budget — a host miss, never a stall."""

    __slots__ = ("matched_len", "shared_pages", "tail_src", "tail_len",
                 "charge", "fetch", "fetch_job", "fetch_reserved",
                 "device_matched")

    def __init__(self, matched_len: int = 0,
                 shared_pages: Optional[List[int]] = None,
                 tail_src: Optional[int] = None, tail_len: int = 0,
                 charge: int = 0):
        self.matched_len = matched_len
        self.shared_pages = shared_pages or []
        self.tail_src = tail_src
        self.tail_len = tail_len
        self.charge = charge
        self.fetch: List[Any] = []
        self.fetch_job = None
        self.fetch_reserved = 0
        self.device_matched = matched_len


class KVCacheManager:
    """Engine-facing façade over the pool + radix index.

    Thread-safe (its own RLock): the engine thread admits/releases under
    the engine lock, while ``submit`` peeks suffix costs from client
    threads for shed diagnostics."""

    def __init__(self, num_pages: int, page_size: int,
                 enabled: bool = False, keeps_tokens: bool = True):
        self.pool = PagePool(num_pages, page_size)
        # False for a family with no class that keeps every token (its
        # cache is state a slot holds): no request needs a page, so
        # every admission fits and nothing is ever granted
        self.keeps_tokens = keeps_tokens
        self.page = page_size
        self.enabled = bool(enabled)
        self.index: Optional[RadixIndex] = (
            RadixIndex(self.pool) if self.enabled else None)
        # host tier (ISSUE 6): attached by the engine when
        # bigdl.llm.kvtier.enabled — None means every tier branch below
        # is structurally absent (the PR 5 manager exactly)
        self.tier = None
        self._read_page = None     # engine: pid -> (k_dev, v_dev) gather
        self._write_pages = None   # engine: (pids, k_devs, v_devs) scatter
        self._lock = threading.RLock()
        # always-on plain accounting (tools/microbench_prefix.py and
        # GET /debug/kvcache read these; metric series mirror them only
        # when observability is enabled)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.prefix_tokens_reused = 0
        self._ins: Optional[Dict[str, Any]] = None

    # -- observability -------------------------------------------------------
    def _instruments(self):
        from bigdl_tpu import observability as obs
        if not (self.enabled and obs.enabled()):
            return None
        if self._ins is None:
            self._ins = {
                "hits": obs.counter(
                    "bigdl_kvcache_hits_total",
                    "Admissions that reused a cached prefix"),
                "misses": obs.counter(
                    "bigdl_kvcache_misses_total",
                    "Admissions with no cached prefix"),
                "evictions": obs.counter(
                    "bigdl_kvcache_evictions_total",
                    "Pages evicted from the prefix index under pool "
                    "pressure"),
                "reused": obs.counter(
                    "bigdl_kvcache_prefix_tokens_reused_total",
                    "Prompt tokens served from cached prefixes instead "
                    "of prefill"),
                "indexed": obs.gauge(
                    "bigdl_kvcache_indexed_pages",
                    "Pages currently referenced by the prefix index"),
                "shared": obs.gauge(
                    "bigdl_kvcache_shared_pages",
                    "Pages with more than one reference (index + live "
                    "requests)"),
                "occupancy": obs.gauge(
                    "bigdl_kvcache_pool_occupancy",
                    "Fraction of the usable page pool allocated "
                    "(live + indexed)"),
            }
        return self._ins

    def record_gauges(self):
        ins = self._instruments()
        if ins is None:
            return
        ins["indexed"].set(self.index.indexed_pages())
        ins["shared"].set(self.pool.shared_pages())
        ins["occupancy"].set(
            self.pool.allocated() / max(self.pool.num_pages - 1, 1))
        if self.tier is not None:
            self.tier.record_gauges()

    # -- host tier (ISSUE 6) -------------------------------------------------
    def attach_tier(self, tier, reader, writer):
        """Arm the host spill tier. ``reader(pid)`` must DISPATCH a
        per-page gather of the engine's pools and return the standalone
        device arrays (engine thread only — eviction runs under the
        engine lock, and engine-thread dispatch order is what keeps the
        gather ahead of any reuse of the page id). ``writer(pids,
        k_devs, v_devs)`` scatters fetched pages into the pools."""
        if not self.enabled:
            raise ValueError(
                "the host tier extends the prefix cache: enable "
                "bigdl.llm.kvcache first")
        self.tier = tier
        self._read_page = reader
        self._write_pages = writer

    def _spill(self, token_path, pid: int):
        """Eviction hook: capture the page into the host arena before
        its id is freed. Best-effort by contract — any failure here
        (arena saturated, injected ``kvtier.spill``) leaves the
        eviction a plain drop."""
        if len(token_path) % self.page:
            return              # partial tails re-prefill on miss
        try:
            slot = self.tier.arena.reserve(tuple(token_path))
            if slot is None:
                return          # every slot pinned: skip this spill
            k_dev, v_dev = self._read_page(pid)
            self.tier.migrator.submit_spill(tuple(token_path), slot,
                                            k_dev, v_dev)
            self.tier.count_spill()
        except Exception:
            pass

    def materialize(self, adm: Admission, k_devs, v_devs):
        """Land a completed fetch: allocate pool pages (pre-evicting if
        needed — may raise the injected ``kvcache.evict``, in which
        case the caller retries, nothing committed), scatter the
        uploaded pages in, index the chunks, and convert the admission
        pre-charge into ordinary pinned-shared adoption. After this the
        admission is indistinguishable from a device prefix hit."""
        with self._lock:
            n = len(adm.fetch)
            if n == 0:
                return
            self.ensure_free(n)             # retryable injected raise
            pids = [self.pool.take_free() for _ in range(n)]
            self._write_pages(pids, k_devs, v_devs)
            # index under the chain identity: the device-matched chunks
            # already have nodes (kept as-is), the fetched chunks take
            # one index ref each. A chunk some concurrent request
            # indexed meanwhile keeps ITS page; ours then stays a
            # request-private ref that frees at EOS.
            chain = list(adm.fetch[-1][0])
            self.index.insert(chain, list(adm.shared_pages) + pids)
            for pid in pids:
                # take_free's ref becomes the request's adoption ref;
                # the pin consumes the admission-time pre-charge
                self.pool.pin_precharged(pid)
            adm.shared_pages.extend(pids)
            adm.fetch_reserved = 0
            adm.fetch = []
            adm.fetch_job = None
            host_tokens = n * self.page
            self.prefix_tokens_reused += host_tokens
            self._count("reused", host_tokens)
            self.tier.count_fetch(n)
            self.record_gauges()

    def degrade(self, adm: Admission):
        """A failed / timed-out / cancelled fetch becomes a plain cache
        miss: the matched prefix rolls back to the device-resident part
        and the fetch pre-charge converts 1:1 into the suffix budget
        the extra prefill pages need (the arena pins are the migration
        worker's to release)."""
        with self._lock:
            if not adm.fetch:
                return
            if adm.fetch_job is not None:
                adm.fetch_job.cancelled = True
            adm.charge += adm.fetch_reserved
            adm.fetch_reserved = 0
            adm.fetch = []
            adm.fetch_job = None
            adm.matched_len = adm.device_matched
            adm.tail_src, adm.tail_len = None, 0
            self.tier.count_fetch_failure()

    def _count(self, name: str, n: int = 1):
        ins = self._instruments()
        if ins is not None:
            ins[name].inc(n)

    # -- admission -----------------------------------------------------------
    def suffix_budget(self, prompt_len: int, max_new: int,
                      matched_len: int) -> int:
        """Worst-case pages the request may still need to OWN: every
        page from the first non-fully-shared one through the last
        decode token. The COW fork target (a mid-page match's page) is
        inside this range, so forks are pre-reserved too."""
        if not self.keeps_tokens:
            return 0
        full = _ceil_div(prompt_len + max_new, self.page)
        return full - matched_len // self.page

    def peek(self, prompt_ids, max_new: int) -> Dict[str, int]:
        """Lock-held read-only suffix cost for shed/reject diagnostics:
        no refs taken, no LRU touch, no counters."""
        with self._lock:
            matched = 0
            matched_total = 0
            if self.enabled:
                m = self.index.lookup(prompt_ids, touch=False)
                matched = min(m.matched_len, len(prompt_ids) - 1)
                matched_total = matched
                if self.tier is not None:
                    # host-resident chunks reduce prefill, not budget:
                    # each fetched page still pre-charges one page, so
                    # pages_needed stays the device-matched suffix cost
                    base = len(m.full_pages) * self.page
                    host = self.tier.arena.lookup_chunks(
                        prompt_ids, base, len(prompt_ids) - 1,
                        touch=False)
                    if host:
                        matched = base
                        matched_total = base + len(host) * self.page
            return {
                "pages_needed": self.suffix_budget(
                    len(prompt_ids), max_new, matched),
                "pages_free": self.pool.budget_avail,
                "matched_tokens": matched_total,
                # the device-only match (host chunks excluded) — the
                # engine's chunked-admission decision needs the suffix
                # it would actually chunk (ISSUE 14)
                "matched_device": matched,
            }

    def admit(self, prompt_ids, max_new: int,
              chunk_pages: Optional[int] = None) -> Optional[Admission]:
        """Look up the longest cached prefix, charge the suffix-only
        budget (+ pins for newly-adopted shared pages), take adoption
        refs, and pre-evict enough free pages for the prompt's own
        pages. Returns None when the budget cannot cover it (the
        engine's head-of-line wait). Raises only from the seeded
        ``kvcache.evict`` fault site, with NOTHING charged or adopted —
        the engine retries the whole admission.

        ``chunk_pages`` (ISSUE 14, chunked admission): charge only that
        many pages — the FIRST prefill chunk's — instead of the whole
        worst case; later chunks extend the ledger incrementally via
        :meth:`charge_chunk` and the final chunk tops up the decode
        budget. The host tier is bypassed in this mode (the engine
        routes arena-extending admissions through the unchunked path),
        and pre-eviction covers only the first chunk's own pages."""
        T = len(prompt_ids)
        with self._lock:
            if not self.enabled:
                charge = (chunk_pages if chunk_pages is not None
                          else self.suffix_budget(T, max_new, 0))
                if charge > self.pool.budget_avail:
                    return None
                self.pool.charge(charge)
                return Admission(charge=charge)
            m = self.index.lookup(prompt_ids)
            # host-tier extension (ISSUE 6): consecutive arena-resident
            # chunks past the device full-page boundary extend the
            # match; a host chunk always beats a device tail (>= one
            # full page vs < one), so the tail is dropped un-adopted
            host_chunks = []
            if self.tier is not None and chunk_pages is None:
                base = len(m.full_pages) * self.page
                host_chunks = self.tier.arena.lookup_chunks(
                    prompt_ids, base, T - 1)
                if host_chunks:
                    m.matched_len = base + len(host_chunks) * self.page
                    m.tail_src, m.tail_len = None, 0
            # a fully-cached prompt still runs >= 1 suffix token — the
            # engine needs its logits to start decoding
            if m.matched_len > T - 1:
                m.matched_len = T - 1
                if m.tail_len > 1:
                    m.tail_len -= 1
                elif m.tail_len == 1:
                    m.tail_src, m.tail_len = None, 0
                else:
                    # pure full-page match: the last page turns into a
                    # COW tail source missing its final slot
                    m.tail_src = m.full_pages.pop()
                    m.tail_len = self.page - 1
            if not m.tail_len:
                m.tail_src = None
            n_fetch = len(host_chunks)
            charge = (chunk_pages if chunk_pages is not None
                      else self.suffix_budget(T, max_new, m.matched_len))
            adopt = list(m.full_pages)
            if m.tail_src is not None:
                adopt.append(m.tail_src)
            # each fetched chunk pre-charges the pool page it will
            # occupy, so materialization can never overdraft — and a
            # degraded fetch converts the pre-charge 1:1 into the
            # suffix budget those extra prefill pages need
            need = charge + n_fetch + self.pool.pin_cost(adopt)
            if need > self.pool.budget_avail:
                return None
            self.pool.charge(charge + n_fetch)
            for pid in adopt:
                self.pool.incref(pid)
                self.pool.pin(pid)
            adm = Admission(m.matched_len, m.full_pages, m.tail_src,
                            m.tail_len, charge)
            adm.fetch_reserved = n_fetch
            adm.device_matched = (len(m.full_pages) * self.page
                                  if host_chunks else m.matched_len)
            try:
                own_prompt = (chunk_pages if chunk_pages is not None
                              else _ceil_div(T, self.page)
                              - m.matched_len // self.page)
                self.ensure_free(own_prompt)
            except BaseException:
                self.cancel(adm)
                raise
            # arm the fetch LAST: nothing below can raise, so cancel()
            # never races the migration worker's arena unpins
            if host_chunks:
                for _key, slot in host_chunks:
                    self.tier.arena.pin(slot)
                adm.fetch = host_chunks
                adm.fetch_job = self.tier.migrator.submit_fetch(
                    host_chunks)
            if m.matched_len:
                # host tokens count toward ``reused`` only once their
                # fetch materializes — a degraded fetch must not have
                # inflated the savings tally
                dev_reused = adm.device_matched
                self.hits += 1
                self.prefix_tokens_reused += dev_reused
                self._count("hits")
                if dev_reused:
                    self._count("reused", dev_reused)
            else:
                self.misses += 1
                self._count("misses")
            return adm

    def cancel(self, adm: Admission):
        """Roll an admission back (failed prefill / injected fault /
        engine stop with a fetch still parked): drop adoption
        refs+pins, the budget charge and any fetch pre-charge. Arena
        pins belong to the migration worker — cancelling the job makes
        it release them."""
        with self._lock:
            self.release_transient(adm)
            for pid in adm.shared_pages:
                self.pool.decref(pid)
                self.pool.unpin(pid)
            adm.shared_pages = []
            if adm.fetch_job is not None:
                adm.fetch_job.cancelled = True
            self.pool.release(adm.charge + adm.fetch_reserved)
            adm.charge = 0
            adm.fetch_reserved = 0
            adm.fetch = []
            adm.fetch_job = None

    def charge_chunk(self, adm: Admission, n: int) -> bool:
        """Extend a chunked admission's ledger charge by ``n`` pages —
        the next prefill chunk's own pages, plus (at the final chunk)
        the decode-budget top-up (ISSUE 14). False = the ledger cannot
        cover it RIGHT NOW with nothing charged; the engine keeps
        decoding and retries next pass, shedding (full rollback) after
        its bounded wait so concurrent chunkers can never deadlock the
        pool. Σ(chunk charges) over a completed admission equals the
        unchunked worst-case charge exactly, so EOS release balances."""
        if n <= 0:
            return True
        with self._lock:
            if n > self.pool.budget_avail:
                return False
            self.pool.charge(n)
            adm.charge += n
            return True

    def uncharge_chunk(self, adm: Admission, n: int):
        """Return an unused chunk charge (a chunk dispatch that failed
        after charging): the exact inverse of :meth:`charge_chunk`, so
        the engine's pass retry starts from the pre-pass ledger."""
        if n <= 0:
            return
        with self._lock:
            self.pool.release(n)
            adm.charge -= n

    def release_transient(self, adm: Admission):
        """Drop the COW fork source's transient ref/pin — safe as soon
        as the partial prefill consuming it has been dispatched (the
        donated-pool data dependency orders any later overwrite after
        the gather)."""
        with self._lock:
            if adm.tail_src is not None:
                self.pool.decref(adm.tail_src)
                self.pool.unpin(adm.tail_src)
                adm.tail_src = None

    def release_slot(self, charge: int, owned, adopted):
        """EOS/eviction release: decrement refcounts instead of freeing
        — pages the index still references stay warm for reuse."""
        with self._lock:
            for pid in owned:
                self.pool.decref(pid)
            for pid in adopted:
                self.pool.decref(pid)
                self.pool.unpin(pid)
            self.pool.release(charge)

    # -- index maintenance ---------------------------------------------------
    def insert(self, tokens, pages):
        """Index a chain (prompt at prefill time; prompt+generated at
        EOS). The index takes its own ref on each newly-indexed page."""
        if not self.enabled or not len(tokens):
            return
        with self._lock:
            self.index.insert(tokens, pages)
            self.record_gauges()

    def chain_locations(self, tokens):
        """Where a chain's cached FULL pages live right now (the
        handoff export walk): device page ids for the radix-resident
        prefix, then ``(key, slot)`` arena chunks continuing it. The
        caller (engine, under its lock) pulls the device pages while
        eviction cannot run."""
        with self._lock:
            m = self.index.lookup(tokens)
            dev = list(m.full_pages)
            host = []
            if self.tier is not None:
                base = len(dev) * self.page
                host = self.tier.arena.lookup_chunks(
                    tokens, base, len(tokens))
            return dev, host

    # -- physical pages ------------------------------------------------------
    def ensure_free(self, n: int):
        """Make ``n`` pages allocatable, LRU-evicting index-only chains
        under pool pressure. The ``kvcache.evict`` fault site arms
        eviction races (chaos_check --kvcache); it fires BEFORE any
        mutation so an injected raise is cleanly retryable."""
        short = n - self.pool.free_pages()
        if short <= 0:
            return
        if not self.enabled:
            raise PagePoolError(
                "page shortage with the prefix cache disabled: the "
                "admission budget should have prevented this")
        from bigdl_tpu import reliability
        reliability.inject("kvcache.evict")
        with self._lock:
            freed = self.index.evict_lru(
                short, spill=self._spill if self.tier is not None
                else None)
            self.evictions += len(freed)
            self._count("evictions", len(freed))
            if freed:
                # same site as the counter: the flight cross-check
                # asserts Σ(evict event pages) == evictions_total
                from bigdl_tpu.observability import flight
                flight.record("evict", pages=len(freed),
                              requested=short)
            self.record_gauges()
            if len(freed) < short:
                raise PagePoolError(
                    f"eviction reclaimed {len(freed)}/{short} pages: "
                    "the pin/budget invariant is broken")

    def take_free(self) -> int:
        with self._lock:
            return self.pool.take_free()

    def alloc(self, n: int) -> List[int]:
        with self._lock:
            return self.pool.alloc(n)

    def free_owned(self, pages):
        with self._lock:
            for pid in pages:
                self.pool.decref(pid)

    # -- introspection -------------------------------------------------------
    @property
    def budget_avail(self) -> int:
        return self.pool.budget_avail

    def debug_stats(self) -> Dict[str, Any]:
        """The ``GET /debug/kvcache`` body."""
        with self._lock:
            out = {
                "enabled": self.enabled,
                "page_size": self.page,
                "num_pages": self.pool.num_pages,
                "pages_free": self.pool.free_pages(),
                "pages_allocated": self.pool.allocated(),
                "pages_shared": self.pool.shared_pages(),
                "pages_pinned": self.pool.pinned_pages(),
                "budget_avail": self.pool.budget_avail,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "prefix_tokens_reused": self.prefix_tokens_reused,
            }
            if self.index is not None:
                out["index"] = self.index.stats()
            if self.tier is not None:
                out["tier"] = self.tier.debug_stats()
            return out


__all__ = ["Admission", "KVCacheManager", "PagePool", "PagePoolError",
           "PrefixMatch", "RadixIndex"]
