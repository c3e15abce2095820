"""LLM serving worker — continuous-batching generation service.

Reference: ``P:llm/serving`` (the bigdl-llm FastChat model worker and the
later vLLM integration, SURVEY.md §2.8 llm serving/tools row). The
reference wraps its CPU models behind FastChat's worker API; the analog
here is a TPU-shaped **continuous batching** loop:

- requests enter a queue at any time (``submit`` returns a handle);
- the scheduler packs up to ``max_batch`` active sequences into fixed
  batch slots (static shapes: one compiled decode step serves every
  composition of active requests);
- each engine step decodes ONE token for every active slot via the
  family's paged decode step (under jit, donated page pools);
  finished sequences (EOS or max_tokens) free their slot and pages
  immediately and a queued request takes the slot over — its prefill
  writes the prompt's K/V into pages of the shared pool (the
  "continuous" part: no waiting for the whole batch to drain);
- steps are dispatched PIPELINED (ISSUE 4): sampling runs on device
  inside the compiled step, and up to ``bigdl.llm.pipeline_depth``
  steps are in flight before the oldest's tokens are drained — host
  scheduling (admission, prefill, EOS bookkeeping) overlaps device
  compute instead of round-tripping per token;
- results stream out through the handle (``get()`` blocks; ``tokens``
  grows as the loop runs).

Single-process and thread-driven: the engine loop runs on a background
thread like ClusterServing's job loop; the reference's HTTP surface is a
deployment shim over exactly this object.
"""

from __future__ import annotations

import collections
import heapq
import logging
import queue
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import observability as obs
from bigdl_tpu import reliability
from bigdl_tpu.llm.kernels.sampling import make_sampled_step
from bigdl_tpu.llm.kvcache import KVCacheManager
from bigdl_tpu.llm.kvcache.classes import (PageClass, RingLedger, StateClass,
                                           StateLedger, every_token_class,
                                           page_classes_of)
from bigdl_tpu.llm.kvcache.prefill import make_mixed_step, make_spec_step
from bigdl_tpu.observability import flight
from bigdl_tpu.observability import request_context as rc
from bigdl_tpu.observability import utilization

logger = logging.getLogger("bigdl_tpu.llm.serving")

#: raised again by the same call with the same arguments: shape and
#: type checks at trace time, a path a family does not implement
_DETERMINISTIC_ERRORS = (ValueError, NotImplementedError, TypeError)


def _retry_cannot_cure(exc: BaseException,
                       prev: Optional[BaseException]) -> bool:
    """Whether the engine loop should fail the pass's requests instead
    of retrying it. An injected fault is transient by contract (the
    chaos suites pin its retry). Not transient: the deterministic error
    types, anything that escaped the first call of a compiled program
    (tracing, lowering, compilation — Mosaic refusing a kernel lands
    here), and the same exception as the previous pass."""
    if isinstance(exc, reliability.InjectedFault):
        return False
    if isinstance(exc, _DETERMINISTIC_ERRORS) or \
            obs.compile_recorder.failed_first_call(exc):
        return True
    return (prev is not None and type(prev) is type(exc)
            and str(prev) == str(exc))


def _trace_of(req) -> Optional[str]:
    """The trace id riding a Request handle, if the submitter had one
    (flight events must stitch into the PR-3 trace model)."""
    t = getattr(req, "trace", None)
    return t.get("trace_id") if t else None


def _llm_instruments():
    """Engine metrics (declared only when observability is on): the
    per-phase signals the Ragged-Paged-Attention line of work says you
    need to diagnose serving — prefill vs decode throughput and KV-pool
    occupancy, not end-of-run aggregates."""
    return {
        "prefill_tokens": obs.counter(
            "bigdl_llm_prefill_tokens_total",
            "Prompt tokens prefilled into the KV cache"),
        "prefill_seconds": obs.histogram(
            "bigdl_llm_prefill_seconds",
            "Host wall of one request prefill (compile excluded after "
            "first hit per length bucket). At pipeline_depth 1 this "
            "covers execution (the prefill barriers); at depth > 1 it "
            "is DISPATCH time — execution overlaps decode by design"),
        "decode_tokens": obs.counter(
            "bigdl_llm_decode_tokens_total",
            "Tokens decoded across all slots"),
        "decode_seconds": obs.histogram(
            "bigdl_llm_decode_step_seconds",
            "Host wall attributed to one decode step: scheduling + "
            "fence stall (under pipelining device compute overlaps the "
            "host, so this is NOT pure device time — see the host/stall "
            "split below and docs/PERFORMANCE.md)"),
        "decode_host": obs.histogram(
            "bigdl_llm_decode_host_seconds",
            "Host-side scheduling slice of one decode step (page "
            "allocation + dispatch; no device wait)",
            buckets=obs.FAST_BUCKETS),
        "decode_stall": obs.histogram(
            "bigdl_llm_decode_stall_seconds",
            "Host time blocked on the device fence when draining a "
            "decode step (the pipeline's residual stall)",
            buckets=obs.FAST_BUCKETS),
        "inflight": obs.gauge(
            "bigdl_llm_pipeline_inflight",
            "Decode steps dispatched but not yet drained (bounded by "
            "bigdl.llm.pipeline_depth)"),
        "requests": obs.counter(
            "bigdl_llm_requests_total",
            "Requests finished by the engine", labelnames=("reason",)),
        "active": obs.gauge(
            "bigdl_llm_active_slots", "Slots currently decoding"),
        "queue": obs.gauge(
            "bigdl_llm_queue_depth",
            "Requests accepted and waiting for an engine slot (the "
            "fleet autoscaler's primary pressure signal)"),
        "kv_pages": obs.gauge(
            "bigdl_llm_kv_pages_in_use",
            "Physical KV pages owned by live requests"),
        "kv_occupancy": obs.gauge(
            "bigdl_llm_kv_pool_occupancy",
            "Fraction of the KV page pool in use (0..1)"),
    }


#: SLO classes in strictly descending scheduling priority (ISSUE 17).
#: The wire form is the case-insensitive ``X-BigDL-Priority`` header
#: (see llm/worker.py); anything unknown normalizes to "standard" so a
#: typo degrades to today's behavior instead of a 4xx.
PRIORITY_CLASSES = ("interactive", "standard", "batch")
_PRIORITY_RANK = {c: r for r, c in enumerate(PRIORITY_CLASSES)}
#: Retry-After queue-depth weights per class (ISSUE 17 satellite):
#: batch clients back off harder than interactive ones under the SAME
#: backlog — reliability.retry_after_seconds scales linearly in depth,
#: so weighting the depth weights the backoff.
CLASS_RETRY_WEIGHTS = {"interactive": 0.5, "standard": 1.0, "batch": 2.0}


def normalize_priority(value) -> str:
    """Map a header/ctor value onto a known SLO class ("standard" for
    None/unknown — misdeclared priority must degrade, never fail)."""
    if value is None:
        return "standard"
    v = str(value).strip().lower()
    return v if v in _PRIORITY_RANK else "standard"


class _PriorityScheduler:
    """Class-ordered admission backlog (ISSUE 17 tentpole). A binary
    heap of ``(rank, seq, req)``: rank orders classes, the monotonic
    sequence keeps FIFO within a class AND makes entries totally
    ordered (Request is not comparable). Engine-thread only — the
    thread-safe boundary stays the intake queue, which `_admit` drains
    into this heap every pass. Constructed ONLY when
    ``bigdl.llm.priority.enabled`` — disabled mode has no scheduler
    object at all (the structural-absence contract)."""

    def __init__(self):
        self._heap: List[tuple] = []
        self._seq = 0

    def push(self, req) -> None:
        self._seq += 1
        heapq.heappush(self._heap,
                       (_PRIORITY_RANK[req.priority], self._seq, req))

    def push_entry(self, ent: tuple) -> None:
        """Re-park a popped entry with its ORIGINAL sequence number —
        a budget-blocked head must keep its place in line, not move to
        the back of its class."""
        heapq.heappush(self._heap, ent)

    def pop_entry(self) -> Optional[tuple]:
        return heapq.heappop(self._heap) if self._heap else None

    def __len__(self) -> int:
        return len(self._heap)

    def live(self) -> int:
        """Entries whose request is still waiting (done handles are
        lazily dropped at the next pop)."""
        return sum(1 for _, _, r in list(self._heap)
                   if not r.done.is_set())

    def best_rank(self) -> Optional[int]:
        ranks = [e[0] for e in list(self._heap)
                 if not e[2].done.is_set()]
        return min(ranks) if ranks else None

    def requests(self) -> List[Any]:
        return [r for _, _, r in list(self._heap)]

    def drain(self) -> List[tuple]:
        ents, self._heap = self._heap, []
        return ents

    def depths(self) -> Dict[str, int]:
        """Live backlog per class (the queue-depth-by-class gauges)."""
        out = {c: 0 for c in PRIORITY_CLASSES}
        for _, _, r in list(self._heap):
            if not r.done.is_set():
                out[r.priority] += 1
        return out

    def parked(self) -> int:
        """Preempted requests waiting to resume (the fleet's scale-in
        victim filter reads this through /healthz)."""
        return sum(1 for _, _, r in list(self._heap)
                   if r.resume_ids is not None and not r.done.is_set())


def _priority_instruments():
    """Priority-scheduler metrics (ISSUE 17) — declared only when the
    scheduler exists AND observability records: ``bigdl.llm.priority.
    enabled`` off must leave no ``bigdl_llm_preemptions_total`` /
    ``bigdl_llm_queue_depth_class`` / ``bigdl_llm_preempt_parked``
    series (the disabled-mode absence contract)."""
    return {
        "preemptions": obs.counter(
            "bigdl_llm_preemptions_total",
            "In-flight decodes losslessly preempted for a higher "
            "SLO class, by the victim's class",
            labelnames=("class",)),
        "queue_class": obs.gauge(
            "bigdl_llm_queue_depth_class",
            "Scheduler backlog by SLO class (the fleet autoscaler's "
            "interactive-starvation signal)",
            labelnames=("class",)),
        "parked": obs.gauge(
            "bigdl_llm_preempt_parked",
            "Preempted requests parked for resume on this engine "
            "(scale-in must not drain the worker holding them)"),
    }


def _sync_barrier(*arrays):
    """Bound the in-flight computations producing ``arrays``.

    Besides ``jax.block_until_ready`` this pulls one element of every
    array to the host in a single tiny transfer: a device-to-host fetch
    of data the computations produced cannot complete before they have,
    on any runtime. The pipelined engine (ISSUE 4) uses this only at
    ``pipeline_depth=1`` — its steady-state fence is the drain fetch of
    the step's own (tokens ‖ fence) vector, which delivers the data AND
    the barrier in one transfer (kernels.sampling.fence_token).
    """
    # a one-pool family has no second pool (None: no leaf); a family
    # with several classes hands a tuple of pools
    arrays = jax.tree_util.tree_leaves(arrays)
    jax.block_until_ready(arrays)
    # (the first element by index: an eager ``ravel()`` of a page pool
    # copies the pool, 2.7 GB beside a 13 GB engine at stop())
    np.asarray(jnp.stack([a[(0,) * a.ndim].astype(jnp.float32)
                          for a in arrays]))


# compiled paged steps shared across LLMServer instances of the same
# model config (a fresh server must not recompile: the greedy-parity
# stress test spins up 8 servers under load, and each per-instance
# closure would retrace from scratch)
_PAGED_STEP_CACHE: Dict[tuple, Any] = {}


def compiled_steps() -> List[tuple]:
    """``(kind, detail, wrapper)`` for every entry of the shared step
    cache: ``kind`` names the program (``"decode"``,
    ``"prefill_ragged"``, …), ``detail`` what else keys it (the suffix
    bucket, the sampling mode), ``wrapper`` is its ``obs.compiled``
    function. For tools that must look at what the engine actually
    compiled (chip_smoke.py reads the executables' HLO for the Pallas
    custom calls)."""
    # keys are LLMServer._step_cache_key() — (family, cfg, page, cache
    # dtype) — followed by the kind and its detail
    return [(key[4], key[5:], fn)
            for key, fn in list(_PAGED_STEP_CACHE.items())]


class _EagerTimer:
    """``with timer:`` around the eager device updates inside a pass
    phase (``.at[].set``, ``jnp.asarray``): each is a program of its own
    that queues behind whatever the device is doing, so the host may
    wait in it. Timed and not spanned: a record each would overflow the
    ring. Reads no clock while observability is off."""

    __slots__ = ("seconds", "_t0")

    def __init__(self):
        self.seconds = 0.0
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter() if obs.enabled() else None

    def __exit__(self, *exc):
        if self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
        return False

    def restart(self):
        self.seconds = 0.0

    def microseconds(self) -> float:
        return round(self.seconds * 1e6, 1)


class Request:
    """Handle returned by :meth:`LLMServer.submit`."""

    def __init__(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 priority: str = "standard"):
        self.id = str(uuid.uuid4())
        self.prompt_ids = np.asarray(prompt_ids, np.int32).ravel()
        self.max_new_tokens = max_new_tokens
        self.tokens: List[int] = []
        # SLO class (ISSUE 17): normalized on submit; plain metadata
        # unless the server's priority scheduler exists
        self.priority = priority
        # lossless-preemption state (ISSUE 17): after a preempt the
        # request re-queues journal-style as prompt + generated_so_far
        # (resume_ids) with its remaining budget; _hold_rec pins the
        # in-flight fence record whose drain must retire before the
        # request may re-admit (a same-slot re-admission before the old
        # step's fence drains would absorb that step's stale token)
        self.resume_ids: Optional[np.ndarray] = None
        self.preemptions = 0
        self._hold_rec: Optional[dict] = None
        self.error: Optional[str] = None
        self.done = threading.Event()
        # cooperative cancellation (ISSUE 7): set by LLMServer.abort
        # (hedge loser, client gone) or the watchdog (stalled engine) —
        # the engine finishes the slot at its next drain instead of
        # decoding tokens nobody will read
        self.cancel_requested = False
        # distributed tracing (ISSUE 3): the submitter's ambient context
        # rides the handle into the engine thread (contextvars don't
        # cross threads); None when no trace / observability disabled
        self.trace = rc.to_wire(rc.current())
        self.decode_started_at = 0.0
        # always-on stamps on ``time.perf_counter()``, the clock the
        # trace ring's ``t0`` and a JAX profile's host side share:
        # submit here, admission when the engine gives the request a
        # slot (the first time, if it is preempted and resumed), first
        # token at the engine's drain
        self.t_submit = time.perf_counter()
        self.t_admit = 0.0
        self.t_first_token = 0.0
        # per-request SLO accounting (ISSUE 12, engine scope): last
        # token's drain stamp and the worst inter-token gap so far —
        # two floats, maintained only when the server's SLO account
        # exists
        self.t_last_token = 0.0
        self.itl_max = -1.0
        # per-token fence stamps, always on: the clock read right after
        # the device->host fetch that made the token host-visible (one
        # read per drain, shared by every token of that drain) — the
        # exact arrival times the ITL sketches observe, so a caller or
        # a tool computes per-request gaps without polling
        self.t_tokens: List[float] = []

    def get(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError(f"request {self.id} still running")
        if self.error is not None:
            # the engine failed this request (e.g. its prefill raised):
            # surface it instead of returning an empty "success"
            raise RuntimeError(
                f"request {self.id} failed: {self.error}")
        return list(self.tokens)


class LLMServer:
    """Continuous-batching engine over a paged model family.

    ``model`` is a causal LM of a family whose module defines the two
    paged programs (``paged_decode_step``, ``paged_prefill_ragged``:
    docs/KVCACHE.md "What a family gives the engine"), quantized or
    dense. ``max_batch`` fixes the compiled batch width;
    ``max_seq_len`` the per-request token bound. ``num_pages`` sizes
    the pool of the class that keeps every token; a family with no such
    class (its cache is state a slot holds: docs/KVCACHE.md "State
    classes") ignores it and admits by slot alone.

    **Paged KV cache.** KV lives in a page pool
    ``(L, num_pages, H_kv, page_size, D)``; each request owns
    ``ceil(tokens/page)`` pages named by its block-table row, allocated
    as decode advances and freed the moment the request finishes — HBM
    held is proportional to tokens in flight, not
    ``max_batch × max_seq_len`` (VERDICT r3 missing #1; the reference's
    vLLM-integration lineage, SURVEY §2.8). Admission reserves a page
    *budget* for the request's worst case (prompt + max_new_tokens) so
    decode can never deadlock on an empty pool; physical pages are only
    taken when tokens actually land. Attention over the pool runs the
    Mosaic paged kernel on TPU (kernels/paged_attention.py) and its XLA
    gather twin elsewhere. The decode step runs the layers in a rolled
    ``lax.scan`` that only READS the donated pools (as one flat page
    array, block tables offset per layer); the new tokens' K/V are
    written after the scan, in place and in the pools' own layout
    (:mod:`bigdl_tpu.llm.kvcache.write`), so no compiled step or
    prefill holds a copy of a whole pool
    (:func:`bigdl_tpu.llm.models.llama.paged_decode_step`). A prompt
    is prefilled in place on the pool by ONE program per suffix bucket
    (the family's ``paged_prefill_ragged``): a full prompt is the
    offset-0 case of a prefix hit, on every platform.

    **Pipelined dispatch (ISSUE 4).** Decode no longer round-trips to
    the host per token: sampling is folded into the compiled step (next
    ids are produced on device), block tables and lengths live device-
    resident with incremental scatter updates, and up to
    ``pipeline_depth`` steps (``bigdl.llm.pipeline_depth``, default 2)
    are dispatched before the oldest is drained — so admission, prefill
    scheduling and EOS bookkeeping run WHILE the device computes. Each
    in-flight record pins the (non-donated) buffers its step consumes
    until the drain fetch — a real device→host fetch of the step's
    fence — proves the step retired, preserving the round-4
    buffer-lifetime fix without a blocking barrier per token. Steps
    dispatched for a request that drains as finished are speculative;
    their tokens are discarded and their page use stays inside the
    request's admission budget (dispatches per request are capped at
    ``max_new_tokens``). ``pipeline_depth=1`` reproduces the
    synchronous engine exactly: every step drains (and every prefill
    barriers) before the next dispatch, and no buffer outlives its
    iteration. See docs/PERFORMANCE.md.

    **Prefix-aware KV cache (ISSUE 5, ``bigdl.llm.kvcache.enabled`` /
    ``kvcache=`` ctor arg; default off).** The page pool lives in the
    :mod:`bigdl_tpu.llm.kvcache` subsystem: pages are refcounted, a
    radix index keyed on page-size token chunks keeps finished (and
    live) requests' prompt chains warm, and admission looks up the
    longest cached prefix — the budget is charged only for the uncached
    suffix, prefill runs only over the suffix at a position offset, and
    a partially-matched tail page is copy-on-write forked into the
    request's own first page inside the same dispatch. EOS releases
    DECREMENT refcounts instead of freeing; index-only chains are
    LRU-evicted under pool pressure. Disabled, the manager degenerates
    to the old free-list (same allocation order, full-prompt budgets,
    no index, no extra metric series) — bit-identical to the
    pre-kvcache engine. See docs/KVCACHE.md.

    **Host spill tier (ISSUE 6, ``bigdl.llm.kvtier.enabled`` /
    ``kvtier=`` ctor arg; default off; requires the prefix cache).**
    Radix-evicted full-page chains spill to a pinned host-RAM arena
    instead of being dropped: eviction dispatches a per-page gather and
    a background migration thread pulls the bytes to the host, so the
    spill hides behind in-flight decode. An admission whose prefix is
    host-resident charges only the still-uncached suffix (plus one
    pre-charged pool page per fetched chunk), schedules an async
    host→HBM upload, and is PARKED — later requests admit and decode
    meanwhile; the landed pages then make it an ordinary prefix hit. A
    failed or timed-out fetch degrades to a plain cache miss (never a
    stall). The tier is also the door for disaggregated serving:
    :meth:`export_chain` / :meth:`import_chain` move a request's KV
    chain between a prefill-role and a decode-role worker as one
    serialized blob (see llm/worker.py's router). Disabled, no arena,
    no migration thread, no ``bigdl_kvtier_*`` series — bit-identical
    to the PR 5 engine. See docs/KVCACHE.md ("Host tier").

    **Unified mixed prefill+decode dispatch (ISSUE 14,
    ``bigdl.llm.mixed.enabled`` / ``mixed=`` ctor arg; default
    off).** The two dispatch paths merge: a prompt whose uncached
    suffix exceeds ``bigdl.llm.prefill.chunk_tokens``
    (``chunk_tokens=``; 0 = 4 pages) is fed in page-aligned chunks,
    each fused with the pass's decode rows into ONE compiled step
    (``kvcache.prefill.make_mixed_step`` over the family's two
    programs — the sampled decode body and the ragged chunk body
    verbatim, so each leg stays bit-identical to the split program).
    A long admission therefore never stalls in-flight decodes for a
    whole prefill pass — the mixed-load microbench's
    stream p99 ITL no longer spikes at admission. Chunks charge the
    page ledger incrementally (final chunk tops up the decode budget;
    a chunk that cannot charge within ``bigdl.llm.prefill.chunk.wait``
    / ``chunk_wait=`` seconds sheds with a complete rollback and a
    retriable failure). Disabled: no chunk state, no
    ``bigdl_llm_pass_*``/``bigdl_llm_prefill_chunks_total`` series —
    the split engine exactly. See docs/PERFORMANCE.md ("Mixed
    prefill+decode dispatch").
    """

    def __init__(self, model, max_batch: int = 4, max_seq_len: int = 256,
                 eos_token_id: Optional[int] = None,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_queue: int = 0,
                 pipeline_depth: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0,
                 kvcache: Optional[bool] = None,
                 kvtier: Optional[bool] = None,
                 host_pages: Optional[int] = None,
                 watchdog_timeout: Optional[float] = None,
                 slo: Optional[bool] = None,
                 mixed: Optional[bool] = None,
                 chunk_tokens: Optional[int] = None,
                 chunk_wait: Optional[float] = None,
                 priority: Optional[bool] = None,
                 spec: Optional[bool] = None,
                 spec_k: Optional[int] = None):
        from bigdl_tpu.llm.kernels.paged_attention import LANE
        from bigdl_tpu.utils.conf import conf

        self.model = model
        self.cfg = cfg = model.config
        # a family is the module that defines the model's class: it
        # gives the engine two programs, paged_decode_step and
        # paged_prefill_ragged; the engine composes the sampled step
        # (unless the family writes its own), the mixed and the
        # speculative step from them (docs/KVCACHE.md "What a family
        # gives the engine")
        fam_mod = sys.modules[type(model).__module__]
        self._family = fam_mod.__name__.rsplit(".", 1)[-1]
        self._fam_paged_step = getattr(fam_mod, "paged_decode_step", None)
        self._fam_ragged_prefill = getattr(fam_mod,
                                           "paged_prefill_ragged", None)
        if self._fam_paged_step is None or \
                self._fam_ragged_prefill is None:
            raise NotImplementedError(
                f"{type(model).__name__} has no paged decode step and "
                "ragged prefill (ALiBi needs a kernel bias hook); use "
                "generate() or another family")
        self._fam_sampled_step = getattr(
            fam_mod, "paged_decode_step_sampled", None) or \
            make_sampled_step(self._fam_paged_step)
        # what else a family may say of itself: the page classes it
        # caches in (default: one, a K and a V pool of per-head rows
        # for every layer), the int32 counts its decode step appends to
        # the fetched token vector, and the counts the host can add at
        # dispatch
        self._classes = page_classes_of(fam_mod, cfg)
        self._fam_step_stats = tuple(getattr(fam_mod, "STEP_STATS", ()))
        self._fam_host_stats = getattr(fam_mod, "host_step_stats", None)
        self._fam_prefill_stats = getattr(fam_mod, "host_prefill_stats",
                                          None)
        self.step_counters: Dict[str, int] = dict.fromkeys(
            self._fam_step_stats, 0)
        if self._fam_host_stats is not None:
            self.step_counters.update(dict.fromkeys(
                self._fam_host_stats(self.cfg, np.zeros(0, np.int32)), 0))
        if self._fam_prefill_stats is not None:
            self.step_counters.update(dict.fromkeys(
                self._fam_prefill_stats(self.cfg, 0, page_size), 0))
        self.max_batch = max_batch
        self.max_seq_len = min(max_seq_len, cfg.max_position_embeddings)
        self.eos_token_id = eos_token_id
        # bounded admission (ISSUE 2): max_queue > 0 caps WAITING
        # requests; submit on a full queue raises OverloadError (the
        # worker's 503 + Retry-After shed) instead of growing forever
        self.max_queue = max_queue
        self._queue: "queue.Queue[Request]" = queue.Queue(
            maxsize=max_queue)
        self._draining = threading.Event()
        self._slots: List[Optional[Request]] = [None] * max_batch
        self._remaining = np.zeros(max_batch, np.int64)
        self._last = jnp.zeros((max_batch, self.cfg.vocab_size),
                               jnp.float32)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        # pipelined dispatch (ISSUE 4): bounded window of dispatched-
        # but-undrained steps; each record pins the non-donated buffers
        # its step consumes until the drain fetch proves it retired
        depth = pipeline_depth if pipeline_depth is not None else \
            conf.get_int("bigdl.llm.pipeline_depth", 2)
        self.pipeline_depth = max(1, int(depth))
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._do_sample = self.temperature > 0.0
        self._temp = jnp.float32(self.temperature if self._do_sample
                                 else 1.0)
        self._sample_key = jax.random.PRNGKey(sample_seed)
        self._inflight: "collections.deque" = collections.deque()
        # buffers consumed by eagerly-dispatched bookkeeping updates
        # (prefill scatters, freed-row resets): released at the NEXT
        # dispatched step's fence — those updates enqueue after the
        # already-in-flight steps, so only a later fence bounds them
        self._pending_release: List[Any] = []
        # always-on plain-python accounting (not metric series): the
        # host-vs-stall split tools/microbench_decode.py reads, plus the
        # prefill-token tally tools/microbench_prefix.py diffs cache
        # on/off (prefix reuse shows up as fewer prefilled tokens)
        # (``host_seconds`` brackets the page grant and the step's
        # dispatch only; what else the engine thread does for a pass —
        # admission, the drain's bookkeeping — is in the ``llm/*`` phase
        # spans, docs/OBSERVABILITY.md)
        self.host_seconds = 0.0
        self.stall_seconds = 0.0
        self.prefill_tokens_total = 0
        # token gaps, counted where a token is applied: every token of
        # a request after its first closes one, and it closed BEHIND A
        # PREFILL when a prefill (ragged, solo chunk, mixed) was
        # dispatched since the slot's previous token. ``_prefill_seq``
        # numbers the prefill dispatches, ``_seq_at_token`` keeps the
        # number each slot's last token saw
        self.token_gaps_total = 0
        self.token_gaps_behind_prefill_total = 0
        self._prefill_seq = 0
        self._seq_at_token = [0] * max_batch
        # seconds inside the eager device updates of the open
        # ``llm/grant`` or ``llm/drain`` phase (its ``eager_us``)
        self._eager = _EagerTimer()
        # the phase spans of the pass the engine loop is in (None when a
        # caller drives _admit/_step by hand: there is no pass then),
        # the open llm/dispatch span, which _after_dispatch closes, and
        # the open llm/admit span's args, which the admission paths
        # count into
        self._phases: Optional[List[Any]] = None
        self._dispatch_ph: Optional[Any] = None
        self._admit_args: Dict[str, Any] = {}
        # engine passes that raised (retried or failed): 0 on a healthy
        # run, readable without observability (chip_smoke.py asserts it)
        self.pass_errors = 0
        # unified-dispatch accounting (ISSUE 14, always-on plain ints):
        # chunks dispatched and passes that fused decode rows with a
        # prefill chunk — tools/microbench_mixed.py and the parity
        # tests read these without observability
        self.prefill_chunks_total = 0
        self.mixed_passes = 0
        self._mixed_ins = None
        self._chunk_rr = 0
        self._spec_rr = 0
        # self-speculative decoding accounting (ISSUE 19, always-on
        # plain ints): draft tokens proposed/accepted, tokens emitted
        # by spec passes (accepted drafts + the bonus token) and the
        # verify-pass count — tools/microbench_decode.py computes
        # accepted-tokens-per-tick from these without observability
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.spec_emitted_total = 0
        self.spec_passes = 0
        self._spec_ins = None
        self._thread: Optional[threading.Thread] = None
        self._gc_watched = False
        self.steps = 0
        self._ins = None     # declared lazily: see _instruments()
        # engine watchdog (ISSUE 7): a device step stalled past the
        # timeout flips /healthz to 503, aborts parked fetches and
        # fails pending requests retriably instead of hanging clients
        # forever. 0/None = structurally absent: no monitor thread, no
        # watchdog series, no healthz key.
        # per-request SLO accounting (ISSUE 12): TTFT/ITL quantile
        # sketches + threshold classification, engine scope. None (the
        # default) is structural absence — no sketch series, no
        # bigdl_slo_* series, no extra work in the drain.
        from bigdl_tpu.observability.slo import SLOAccount
        self._slo = SLOAccount.if_enabled("engine", enabled=slo)
        wd = (watchdog_timeout if watchdog_timeout is not None else
              conf.get_float("bigdl.llm.watchdog.step_timeout", 0.0))
        self.watchdog_timeout = float(wd or 0.0)
        self.watchdog_enabled = self.watchdog_timeout > 0.0
        self.watchdog_tripped = False
        self.watchdog_trips = 0
        self._hb = time.monotonic()
        self._watchdog_stop = threading.Event()
        self._watchdog_thread: Optional[threading.Thread] = None

        if page_size <= 0 or LANE % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the kernel lane "
                f"width {LANE} (8/16/32/64/128)")
        self._page = page_size
        ppb = LANE // page_size
        cap = -(-self.max_seq_len // page_size)
        # one pool pair and one table a class. The class that keeps
        # every token is the one ``num_pages``, ``_kv``, ``_bt`` and
        # ``_slot_pages`` are about; a class that keeps a window is a
        # ring (kvcache.classes.RingLedger) with a table and a ledger
        # of its own; a state class (kvcache.classes.StateLedger) is
        # the arrays it names, one row a slot, and has no table to
        # keep: slot ``i`` holds row ``1 + i``, always. Beside a page
        # class a request holds its pages and its slot's row at once:
        # admitted when both are there, released together. A one-class
        # family's programs get the pools and the table as arrays, as
        # they always have; a family of several gets tuples, one entry
        # a class. A family with no every-token class has no pages to
        # hold: ``num_pages`` is ignored, the page ledger admits and
        # grants nothing, and a request needs a free slot and no more.
        self._every = every_token_class(self._classes)
        if self._every is None:
            cap, num_pages = ppb, 2
        self._pages_cap = -(-cap // ppb) * ppb    # kernel block mult
        # page 0 is the trash page: inactive rows and prefill padding
        # write there; no live sequence ever owns it
        self._num_pages = num_pages or (1 + max_batch * cap)
        self._rings = [RingLedger(c, page_size, max_batch)
                       for c in self._classes
                       if isinstance(c, PageClass) and c.keeps is not None]
        self._states = [StateLedger(c, max_batch) for c in self._classes
                        if isinstance(c, StateClass)]
        pools = [c.pools(n, page_size, model.cache_dtype)
                 for c, n in zip(self._classes,
                                 self._if_every(self._num_pages)
                                 + [r.num_pages for r in self._rings])]
        pools += [s.cls.arrays(max_batch) for s in self._states]
        self._multi = len(self._classes) > 1
        if self._multi:
            self._k_pages, self._v_pages = (tuple(p) for p in zip(*pools))
        else:
            self._k_pages, self._v_pages = pools[0]
        self._ring_bt_dev = [jnp.asarray(r.bt) for r in self._rings]
        self._state_bt_dev = [jnp.asarray(s.rows) for s in self._states]
        if self._rings or self._states:
            self.step_counters.update(
                {"decode_rows_total": 0,
                 **{r.cls.name + "_pages_held_total": 0
                    for r in self._rings}})
        if self._states:
            self.step_counters.update(state_slots_held_total=0,
                                      state_slots_zeroed_total=0)
        self._class_ins = None
        self._state_ins = None
        if self._multi or self._every is None \
                or self._every.v_width is None:
            # one pool of another row than per-head K and V (a latent
            # cache), several classes, or state a slot holds: what
            # reads or moves pages as a K/V pair of one class refuses
            # the family
            def on(arg, key):
                return arg if arg is not None else \
                    conf.get_bool(key, False)
            asked = [name for name, yes in (
                ("the prefix cache (bigdl.llm.kvcache)",
                 on(kvcache, "bigdl.llm.kvcache.enabled")),
                ("the host tier and KV handoff (bigdl.llm.kvtier)",
                 on(kvtier, "bigdl.llm.kvtier.enabled")),
                ("mixed dispatch (bigdl.llm.mixed)",
                 on(mixed, "bigdl.llm.mixed.enabled")),
                ("speculation (bigdl.llm.spec)",
                 on(spec, "bigdl.llm.spec.enabled")),
                ("priority preemption (bigdl.llm.priority)",
                 on(priority, "bigdl.llm.priority.enabled"))) if yes]
            if asked:
                n = len(self._classes)
                how = ("one latent pool and no V pool"
                       if self._every is not None and not self._multi else
                       f"{n} class{'es' * (n > 1)} ("
                       + ", ".join(c.name for c in self._classes) + ")")
                if self._states:
                    how += (", state a slot holds and not pages a "
                            "position (a state class)")
                raise NotImplementedError(
                    f"{type(model).__name__} caches {how}; "
                    f"{', '.join(asked)} assume a K pool and a V pool "
                    "of per-head rows in one class")
        # the page pool now lives in the kvcache subsystem (ISSUE 5
        # tentpole): refcounted pages + admission budget; with the
        # prefix cache on, a radix index keeps finished requests'
        # chains warm for reuse. Disabled (the default) allocates
        # bit-identically to the embedded free-list it replaces.
        kv_on = (kvcache if kvcache is not None else
                 conf.get_bool("bigdl.llm.kvcache.enabled", False))
        # unified mixed prefill+decode dispatch (ISSUE 14): one
        # compiled step serves every active decode row PLUS one
        # page-aligned prefill chunk, so a long admission is fed in
        # chunk_tokens slices interleaved with decode instead of
        # monopolizing a pass (the chunk attends the prefix and its
        # own earlier chunks where they sit in the pool).
        mx = (mixed if mixed is not None else
              conf.get_bool("bigdl.llm.mixed.enabled", False))
        ct = (chunk_tokens if chunk_tokens is not None else
              conf.get_int("bigdl.llm.prefill.chunk_tokens", 0))
        if ct <= 0:
            ct = 4 * page_size          # "a few pages" default
        self._chunk_tokens = max(
            page_size, -(-ct // page_size) * page_size)
        self._chunk_wait = (
            chunk_wait if chunk_wait is not None else
            conf.get_float("bigdl.llm.prefill.chunk.wait", 30.0))
        self._mixed_active = bool(mx)
        # per-slot chunked-admission state (None entries = slot not
        # chunking); the list itself exists only when the unified
        # dispatch is live — bigdl.llm.mixed.enabled off keeps the
        # engine structurally identical to the split one
        self._chunk_state: Optional[List[Optional[dict]]] = (
            [None] * max_batch if self._mixed_active else None)
        # model-free self-speculative decoding (ISSUE 19): a pass
        # may carry one row's n-gram drafts as a verify chunk and
        # emit up to k+1 tokens for it (llm/spec.py +
        # kvcache.prefill.make_spec_step: the verify chunk IS a
        # ragged chunk). Needs greedy sampling (the
        # accept rule is exact-match; the rejection-sampling hook
        # for temperature > 0 is gated off). Disabled (the
        # default) is structurally absent: no proposer state, no
        # bigdl_llm_spec_* series, no new code on the step path.
        sp = (spec if spec is not None else
              conf.get_bool("bigdl.llm.spec.enabled", False))
        if sp and self._do_sample:
            raise ValueError(
                "bigdl.llm.spec is greedy-only (temperature == 0): "
                "the rejection-sampling verify hook for sampled "
                "decode is gated off")
        self._spec_active = bool(sp)
        self._spec_state: Optional[List[Optional[dict]]] = (
            [None] * max_batch if self._spec_active else None)
        # slots whose in-flight spec verify has not drained: their
        # host lens advance is data-dependent (accepted length), so
        # they sit out dispatch until the record retires
        self._spec_pending: set = set()
        if self._spec_active:
            from bigdl_tpu.llm.spec import NGramProposer
            self._spec_proposer_cls = NGramProposer
            self._spec_k = max(1, int(
                spec_k if spec_k is not None else
                conf.get_int("bigdl.llm.spec.k", 4)))
            self._spec_min_match = max(1, conf.get_int(
                "bigdl.llm.spec.min_match", 2))
            self._spec_backoff = conf.get_float(
                "bigdl.llm.spec.backoff", 0.5)
        self._kv = KVCacheManager(self._num_pages, page_size,
                                  enabled=bool(kv_on),
                                  keeps_tokens=self._every is not None)
        # host spill tier (ISSUE 6): constructed ONLY when enabled —
        # disabled mode must be structurally absent (no arena, no
        # migration thread, no bigdl_kvtier_* series)
        tier_on = (kvtier if kvtier is not None else
                   conf.get_bool("bigdl.llm.kvtier.enabled", False))
        self._tier = None
        if tier_on:
            if not kv_on:
                raise ValueError(
                    "bigdl.llm.kvtier extends the prefix cache: "
                    "enable bigdl.llm.kvcache too")
            from bigdl_tpu.llm.kvtier import KVTier
            hp = (host_pages if host_pages is not None else
                  conf.get_int("bigdl.llm.kvtier.host_pages", 0))
            self._tier = KVTier(
                hp or 4 * self._num_pages, page_size,
                synchronous=conf.get_bool(
                    "bigdl.llm.kvtier.sync", False),
                fetch_timeout=conf.get_float(
                    "bigdl.llm.kvtier.fetch.timeout", 30.0))
            self._kv.attach_tier(self._tier,
                                 reader=self._read_page_kv,
                                 writer=self._write_pages_kv)
        # host-tier admissions parked while their pages upload, and
        # the landed ones waiting for a slot (engine thread only)
        self._fetch_wait: List[dict] = []
        self._fetch_ready: List[tuple] = []
        self._bt = np.zeros((max_batch, self._pages_cap), np.int32)
        self._lens = np.zeros(max_batch, np.int32)
        # pages the last grant took and releases gave back, by class
        # (the llm/grant span's payload; empty for a one-class family)
        self._grant_by_class: Dict[str, int] = {}
        self._freed_by_class: Dict[str, int] = {}
        # device-resident twins (ISSUE 4): the step reads/advances
        # these on device; the host applies incremental scatters
        # (page grants, prefills, freed-row resets) instead of
        # re-uploading the whole tables every token. The np arrays
        # above remain the host's dispatch-time bookkeeping view.
        self._bt_dev = jnp.asarray(self._bt)
        self._lens_dev = jnp.asarray(self._lens)
        self._slot_pages: List[List[int]] = [[] for _ in
                                             range(max_batch)]
        # per-slot cache grant (suffix budget charge + adopted
        # shared pages) — release decrements refcounts at EOS
        self._slot_adm: List[Optional[Any]] = [None] * max_batch
        # SLO-class priority scheduling + lossless preemption
        # (ISSUE 17): constructed ONLY when enabled — disabled mode
        # is structurally absent (no scheduler object, no parked-
        # blob map, no bigdl_llm_preemptions_total / class-gauge
        # series, admission stays FIFO off the intake queue)
        pr = (priority if priority is not None else
              conf.get_bool("bigdl.llm.priority.enabled", False))
        self._sched = _PriorityScheduler() if pr else None
        # exported-on-preempt KV handoff blobs keyed by request id,
        # dropped at resume (the parked chain survives radix
        # eviction under pool pressure)
        self._parked: Optional[Dict[str, bytes]] = {} if pr else None
        # fence record of the most recent preemption: at most one
        # preemption per in-flight window (its pages free at this
        # fence — preempting again before it drains could not admit
        # the waiter anyway)
        self._preempt_rec: Optional[dict] = None
        self._pri_ins = None
        self.preemptions_total = 0
        self.preempt_resumes_total = 0

    @property
    def pages_in_use(self) -> int:
        """Physical pages currently owned by live requests (the
        proportional-HBM claim, testable) — including the partial
        chains of chunked admissions still mid-prompt (ISSUE 14)."""
        n = sum(len(p) for p in self._slot_pages)
        if self._chunk_state is not None:
            n += sum(len(st["own"]) for st in self._chunk_state
                     if st is not None)
        return n

    @property
    def pages_in_use_by_class(self) -> Dict[str, int]:
        """:attr:`pages_in_use` under the first class's name, and what
        live requests hold of every window class."""
        first = {} if self._every is None else \
            {self._every.name: self.pages_in_use}
        return {**first,
                **{r.cls.name: r.pages_in_use() for r in self._rings}}

    def _if_every(self, entry) -> list:
        """``[entry]``, the every-token class's place in a list with
        one entry a class, or ``[]`` for a family that has no such
        class."""
        return [entry] if self._every is not None else []

    @property
    def state_slots_in_use(self) -> int:
        """Slots seated in a state class (the same in each)."""
        return self._states[0].slots_in_use() if self._states else 0

    def _put_ring_row(self, c: int, i: int):
        """Slot ``i``'s row of window class ``c``'s ring table, host to
        device, whole: the one update a prefill's grant, a decode
        step's grant and a release make, so none is a new program."""
        row = jnp.asarray(self._rings[c].bt[i])
        self._pin(self._ring_bt_dev[c], row)
        self._ring_bt_dev[c] = self._ring_bt_dev[c].at[i].set(row)

    def _tables(self):
        """The tables as the programs take them: the one table, or one
        a class (a state class's names the row of every slot)."""
        tabs = self._if_every(self._bt_dev) + self._ring_bt_dev \
            + self._state_bt_dev
        return tuple(tabs) if self._multi else tabs[0]

    # the pool moved into the kvcache subsystem (ISSUE 5); these views
    # keep the embedded-pool names the tests and tools read
    @property
    def _free(self) -> List[int]:
        return self._kv.pool.free_ids()

    @property
    def _budget_avail(self) -> int:
        return self._kv.budget_avail

    @property
    def prefix_tokens_saved(self) -> int:
        """Prompt tokens served from the prefix cache instead of being
        prefilled (always-on; 0 with the cache disabled)."""
        return self._kv.prefix_tokens_reused

    # -- client API ----------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens: int = 32,
               priority: Optional[str] = None) -> Request:
        reliability.inject("llm.submit")
        if max_new_tokens < 1:
            # a zero-budget request would occupy a slot with no step
            # ever dispatched for it (dispatches are capped at
            # max_new_tokens) — reject instead of wedging the slot
            raise ValueError("max_new_tokens must be >= 1")
        req = Request(prompt_ids, max_new_tokens,
                      priority=normalize_priority(priority))
        if len(req.prompt_ids) + max_new_tokens > self.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        # post-lookup suffix cost (ISSUE 5 satellite): a request
        # whose prefix is cached is charged only for the uncached
        # suffix, so feasibility and the shed diagnostics below
        # must be judged on that cost, not the full prompt
        pages = self._kv.peek(req.prompt_ids, req.max_new_tokens)
        if pages["pages_needed"] > self._num_pages - 1:
            raise ValueError(
                f"request needs {pages['pages_needed']} pages "
                f"(uncached suffix of prompt + max_new_tokens) but "
                f"the pool holds {self._num_pages - 1}; it could "
                "never be admitted")
        if self._draining.is_set():
            reliability.count_shed("llm_server", request_id=req.id,
                                   trace_id=_trace_of(req),
                                   reason="draining")
            err = reliability.OverloadError(
                "server is draining: not accepting new requests")
            # structured marker (ISSUE 15): the worker's 503 body
            # carries {"draining": true} so the router's drain bounce
            # keys on a field, not on the message wording
            err.draining = True
            raise err
        if self.watchdog_enabled and self.watchdog_tripped \
                and time.monotonic() - self._hb > self.watchdog_timeout:
            # the engine is wedged mid-pass RIGHT NOW (tripped flag AND
            # a currently-stale heartbeat — the flag alone lags
            # recovery by up to one monitor tick): anything queued
            # would just hang behind the stalled step until the stream
            # wait times out. Fail fast with the same retriable verdict
            # the trip sweep gives — the stream's terminal chunk
            # carries error+retriable, so a failover router resumes
            # elsewhere (and the prober is already draining us).
            self._watchdog_fail(req, self._watchdog_msg())
            return req
        try:
            # with the priority scheduler the engine drains the intake
            # queue into its heap every pass, so the Queue's own maxsize
            # alone would never fire: bound intake + scheduler backlog
            # together to keep ISSUE 2's backpressure contract
            if self._sched is not None and self.max_queue and \
                    self._queue.qsize() + len(self._sched) >= \
                    self.max_queue:
                raise queue.Full
            self._queue.put_nowait(req)
        except queue.Full:
            # the 503 carries the page accounting (post-lookup suffix
            # cost vs budget actually free) so clients and the shed
            # counter can tell queue pressure from page pressure
            shed_detail = dict(
                request_id=req.id, trace_id=_trace_of(req),
                queue_depth=self._queue.qsize(),
                pages_needed=pages["pages_needed"],
                pages_free=pages["pages_free"])
            if pages["pages_needed"] > pages["pages_free"]:
                reliability.count_shed("llm_server_pages",
                                       reason="page_pressure",
                                       **shed_detail)
            else:
                reliability.count_shed("llm_server",
                                       reason="queue_full", **shed_detail)
            err = reliability.OverloadError(
                f"request queue full ({self.max_queue} waiting); "
                f"retry later [needs {pages['pages_needed']} pages for "
                f"the uncached suffix, {pages['pages_free']} "
                "budget-free]")
            err.pages_needed = pages["pages_needed"]
            err.pages_free = pages["pages_free"]
            raise err from None
        if flight.enabled:
            flight.record(
                "queue", request_id=req.id, trace_id=_trace_of(req),
                prompt_tokens=len(req.prompt_ids),
                max_new_tokens=req.max_new_tokens,
                queue_depth=self._queue.qsize(),
                pages_needed=pages["pages_needed"],
                pages_free=pages["pages_free"])
        return req

    def retry_depth(self, priority: Optional[str] = None) -> float:
        """Queue depth for Retry-After derivation (ISSUE 17 satellite).
        Scheduler off: the plain intake depth, bit-identical to HEAD.
        Scheduler on: intake + class-ordered backlog, weighted by the
        shedded request's class so batch clients back off harder than
        interactive ones under the SAME backlog (float — the caller's
        ``reliability.retry_after_seconds`` truncates)."""
        depth = self._queue.qsize()
        if self._sched is None:
            return depth
        return ((depth + len(self._sched))
                * CLASS_RETRY_WEIGHTS[normalize_priority(priority)])

    def class_depths(self) -> Optional[Dict[str, int]]:
        """Live scheduler backlog per SLO class; None when the priority
        scheduler is off (callers emit no class keys at all)."""
        return self._sched.depths() if self._sched is not None else None

    @property
    def preempt_parked(self) -> int:
        """Preempted requests parked for resume on this engine (0 when
        the scheduler is off — the fleet's scale-in filter is inert)."""
        return self._sched.parked() if self._sched is not None else 0

    def export_chain(self, tokens) -> bytes:
        """Serialize the cached FULL pages of ``tokens`` into a handoff
        blob (ISSUE 6 disaggregation: the prefill-role side). Device
        pages are pulled under the engine lock — eviction cannot run
        concurrently, and the blocking fetch doubles as the dispatch
        fence; host-resident chunks are read straight from the arena.
        Pages already evicted from both tiers are simply absent: the
        importer's decode worker re-prefills whatever is missing."""
        if self._tier is None:
            raise RuntimeError(
                "KV handoff needs bigdl.llm.kvtier.enabled")
        with self._lock:
            return self._export_chain_locked(tokens)

    def _export_chain_locked(self, tokens) -> bytes:
        """Export body, caller holds ``self._lock`` (the lock is NOT
        reentrant — the engine thread's preempt path at _preempt_slot
        already holds it and must call this directly)."""
        from bigdl_tpu.llm.kvtier.handoff import serialize_chain
        dev, host = self._kv.chain_locations(tokens)
        k_pages = [np.asarray(self._k_pages[:, pid]) for pid in dev]
        v_pages = [np.asarray(self._v_pages[:, pid]) for pid in dev]
        for key, slot in host:
            # keyed copy-read: a concurrent import can LRU-re-key
            # the slot between lookup and here — a mismatch
            # truncates the export (contiguity ends at the first
            # missing chunk) instead of shipping wrong bytes
            pages = self._tier.arena.read_keyed(slot, key)
            if pages is None:
                break
            k_pages.append(pages[0])
            v_pages.append(pages[1])
        blob = serialize_chain(
            np.asarray(tokens, np.int64)[:len(k_pages) * self._page],
            k_pages, v_pages, self._page)
        self._tier.count_handoff("export", len(blob))
        return blob

    def import_chain(self, blob: bytes) -> int:
        """Land a handoff blob's pages in the HOST ARENA (the
        decode-role side). Control-plane only — no engine lock, no
        device writes: the next admission of this prompt hits the host
        tier and the ordinary async fetch uploads the pages behind
        in-flight decode. Returns the number of pages imported."""
        from bigdl_tpu.llm.kvtier.handoff import (HandoffError,
                                                  deserialize_chain)
        if self._tier is None:
            raise RuntimeError(
                "KV handoff needs bigdl.llm.kvtier.enabled")
        toks, k_pages, v_pages, header = deserialize_chain(blob)
        if not k_pages:
            return 0
        cfg = self.cfg
        want_shape = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                      self._page, cfg.head_dim)
        want_dtype = str(jnp.dtype(self.model.cache_dtype))
        if int(header["page_size"]) != self._page or \
                tuple(header["shape"]) != want_shape or \
                header["dtype"] != want_dtype:
            raise HandoffError(
                f"handoff pages {header['shape']}/{header['dtype']}"
                f"/page={header['page_size']} do not fit this pool "
                f"{want_shape}/{want_dtype}/page={self._page}")
        arena = self._tier.arena
        n = 0
        for j in range(len(k_pages)):
            key = tuple(toks[:(j + 1) * self._page])
            slot = arena.reserve(key)
            if slot is None:
                break              # arena saturated: partial import
            arena.commit(slot, k_pages[j], v_pages[j])
            n += 1
        self._tier.count_handoff("import", len(blob))
        return n

    # -- graceful drain (ISSUE 15) -------------------------------------------
    def begin_drain(self):
        """Flip to DRAINING without stopping: new submits shed with 503
        ``"server is draining"`` (the router's drain bounce re-routes
        them), ``/healthz`` reports ``"draining"``, and the engine keeps
        decoding every already-accepted request to completion. The
        fleet drain coordinator calls this, waits for
        :meth:`engine_idle`, migrates :meth:`warm_chains`, then the
        worker exits — see bigdl_tpu/llm/fleet.py."""
        self._draining.set()

    def cancel_drain(self):
        """Abandon a drain (scale-in cancelled): the engine accepts
        work again. A no-op on a server that was never draining."""
        self._draining.clear()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def engine_idle(self) -> bool:
        """True when no accepted request remains anywhere: queue,
        held head, fetch-parked, or in a slot (chunked admissions hold
        their slot, so they are covered). The drain coordinator polls
        this; ``stop(drain=True)`` uses the same condition inline."""
        with self._lock:
            return (self._queue.empty()
                    and getattr(self, "_pending_head", None) is None
                    and (self._sched is None or self._sched.live() == 0)
                    and not self._fetch_wait
                    and not self._fetch_ready
                    and all(r is None for r in self._slots))

    def warm_chains(self) -> List[List[int]]:
        """Token chains currently warm in this engine's caches — the
        radix index's leaf paths (truncated to full pages: tails
        re-prefill by the handoff contract) plus host-arena entries —
        deduplicated so only maximal chains remain (exporting a chain
        ships every prefix page with it). The drain coordinator
        migrates exactly these via :meth:`export_chain`. Empty when the
        prefix cache is off (nothing is warm by construction)."""
        if not self._kv.enabled:
            return []
        page = self._page
        chains: Dict[tuple, None] = {}
        with self._lock:
            for path in self._kv.index.leaf_paths():
                full = (len(path) // page) * page
                if full:
                    chains[tuple(path[:full])] = None
            if self._tier is not None:
                for key in self._tier.arena.keys():
                    chains[tuple(key)] = None
        keep: List[tuple] = []
        for c in sorted(chains, key=len, reverse=True):
            if not any(k[:len(c)] == c for k in keep):
                keep.append(c)
        return [list(c) for c in keep]

    def abort(self, req: Request, reason: str = "aborted by caller"):
        """Cooperatively cancel an accepted request (ISSUE 7): the
        hedge loser whose client hung up, or a request nobody will
        read. Thread-safe flag-only — the engine thread finishes the
        slot (releasing its pages through the normal refcounted path)
        at its next drain, and admission skips it if it was still
        queued or fetch-parked."""
        req.cancel_requested = True
        if not req.done.is_set():
            req.error = req.error or f"request aborted: {reason}"
            req.done.set()
        # no metric here: the engine counts the reaped slot as
        # requests{reason="cancelled"} at its next drain — an inc on
        # both sides would double-count every hedge loser

    # -- watchdog (ISSUE 7) --------------------------------------------------
    def _watchdog_loop(self):
        """Step-deadline monitor. The engine loop refreshes ``_hb`` at
        the top of every pass (an idle loop spins every ~2 ms), so a
        stale heartbeat means the engine thread is wedged INSIDE a pass
        — a hung device step, a stuck fetch. Trip: mark unhealthy (the
        worker's /healthz answers 503 and the router's prober drains
        us), abort parked fetches, fail every pending request with a
        retriable error. Recovery: the heartbeat resuming clears the
        tripped flag, and /healthz flips back so the prober re-admits
        this worker.

        An XLA compile is indistinguishable from a hung step from the
        host side, so ``step_timeout`` must sit ABOVE the worst-case
        compile for the served shapes (or the engine warmed first) —
        a cold-start compile longer than the timeout trips exactly
        like a wedged device. The failed requests are retriable
        either way; the cost of a false trip is a failover, not a
        lost answer."""
        interval = min(max(self.watchdog_timeout / 4.0, 0.01), 0.25)
        while not self._watchdog_stop.wait(interval):
            age = time.monotonic() - self._hb
            if age <= self.watchdog_timeout:
                if self.watchdog_tripped:
                    self.watchdog_tripped = False   # engine recovered
                continue
            if self.watchdog_tripped:
                # still wedged: keep sweeping — a request that raced
                # past the submit() gate into the queue after the trip
                # sweep must not hang behind the stalled pass (trip
                # counters fire once per episode, the sweep every tick)
                self._watchdog_sweep(self._watchdog_msg())
                continue
            self._watchdog_trip(age)

    def _watchdog_msg(self) -> str:
        return (f"engine stalled: step exceeded the "
                f"{self.watchdog_timeout:g}s watchdog timeout "
                "(retriable: resubmit to another backend)")

    def _watchdog_trip(self, age: float):
        self.watchdog_tripped = True
        self.watchdog_trips += 1
        failed = self._watchdog_sweep(self._watchdog_msg())
        if obs.enabled():
            obs.counter(
                "bigdl_llm_watchdog_trips_total",
                "Engine stalls detected by the step-deadline "
                "watchdog").inc()
            obs.add_complete("llm/watchdog_trip", time.time() - age, age,
                             stage="llm_server", failed_requests=failed,
                             timeout_s=self.watchdog_timeout)

    def _watchdog_sweep(self, msg: str) -> int:
        failed = 0
        # the engine thread is wedged (possibly holding _lock), so only
        # thread-safe surfaces are touched: the queue, Request handles,
        # and migration-job cancel flags. Page/budget bookkeeping stays
        # with the engine thread — it cleans up when (if) it wakes.
        try:
            while True:
                failed += self._watchdog_fail(self._queue.get_nowait(),
                                              msg)
        except queue.Empty:
            pass
        head = getattr(self, "_pending_head", None)
        if head is not None:
            failed += self._watchdog_fail(head, msg)
        sched = getattr(self, "_sched", None)
        if sched is not None:
            # flag-only, same contract as the queue drain above: the
            # heap itself belongs to the engine thread, which drops
            # done entries at its next pop (if it ever wakes)
            for req in sched.requests():
                failed += self._watchdog_fail(req, msg)
        for req in list(self._slots):
            if req is not None:
                failed += self._watchdog_fail(req, msg)
        for ent in list(self._fetch_wait):
            failed += self._watchdog_fail(ent["req"], msg)
            if self._tier is not None:
                self._tier.cancel_fetch(ent["adm"].fetch_job)
        for req, _adm in list(self._fetch_ready):
            failed += self._watchdog_fail(req, msg)
        return failed

    @staticmethod
    def _watchdog_fail(req: Request, msg: str) -> int:
        req.cancel_requested = True
        if req.done.is_set():
            return 0
        req.error = msg
        req.done.set()
        return 1

    def start(self) -> "LLMServer":
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        if self.watchdog_enabled:
            self._hb = time.monotonic()
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, name="bigdl-llm-watchdog",
                daemon=True)
            self._watchdog_thread.start()
        # time-series plane (ISSUE 18): the engine-side refcount on the
        # sampler, so store-backed SLO burn windows work in processes
        # with no HTTP surface. No-op (builds nothing) when the gate is
        # off.
        from bigdl_tpu.observability import timeseries
        self._timeseries = timeseries.acquire()
        # the collector's pauses stop this engine's thread like any
        # other: counted, and recorded as ``py/gc`` when long
        self._gc_watched = obs.enabled()
        if self._gc_watched:
            obs.tracing.watch_gc()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0):
        """Graceful drain (default): reject new submits, finish every
        accepted request (queued AND in-slot), then stop the engine
        thread. ``drain=False`` is the old immediate stop — accepted
        requests never complete."""
        self._draining.set()
        if drain and self._thread is not None and self._thread.is_alive():
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    idle = (self._queue.empty()
                            and getattr(self, "_pending_head", None) is None
                            and (self._sched is None
                                 or self._sched.live() == 0)
                            and not self._fetch_wait
                            and not self._fetch_ready
                            and all(r is None for r in self._slots))
                if idle:
                    break
                time.sleep(0.005)
        self._stop.set()
        if self._watchdog_thread is not None:
            self._watchdog_stop.set()
            self._watchdog_thread.join(timeout=5)
        if getattr(self, "_timeseries", None) is not None:
            from bigdl_tpu.observability import timeseries
            timeseries.release()
            self._timeseries = None
        if self._gc_watched:
            obs.tracing.unwatch_gc()
            self._gc_watched = False
        if self._thread:
            self._thread.join(timeout=30)
        if self._thread is not None and self._thread.is_alive():
            # join timed out: the engine thread is wedged but still owns
            # the window — touching the deque here would race it
            return
        # resolve any still-in-flight dispatches (stop(drain=False)
        # abandons their tokens by contract; with drain=True the loop
        # idles only once every request finished, so leftovers here are
        # purely speculative) — the fence fetch guarantees no pinned
        # buffer is dropped while a computation still reads it
        while self._inflight:
            rec = self._inflight.popleft()
            try:
                np.asarray(rec["out"])
            except Exception:   # a dead device can't hold references
                pass
            for args in rec.pop("kv_release", ()):
                self._kv.release_slot(*args)
        # fetch-parked admissions hold budget but no slot: with
        # drain=True the loop already landed them all (the idle check
        # above includes both lists), so anything left here is a
        # drain=False abandonment — return the grants, unblock clients
        for ent in self._fetch_wait:
            self._kv.cancel(ent["adm"])
            ent["req"].error = "server stopped before its KV fetch landed"
            ent["req"].done.set()
        self._fetch_wait = []
        for req, adm in self._fetch_ready:
            self._kv.cancel(adm)
            req.error = "server stopped before the request took a slot"
            req.done.set()
        self._fetch_ready = []
        if self._sched is not None:
            # scheduler entries hold no budget (budget-blocked heads
            # re-park WITHOUT an admission grant) — flag-only cleanup
            for _, _, req in self._sched.drain():
                if not req.done.is_set():
                    req.error = ("server stopped before the request "
                                 "took a slot")
                    req.done.set()
        if self._tier is not None:
            self._tier.close()
        if self._pending_release:
            # bookkeeping scatters enqueued AFTER the newest step have
            # no later fence — bound them via their own outputs (the
            # current device tables data-depend on every such update)
            # before the pinned references drop
            try:
                _sync_barrier(self._k_pages, self._v_pages,
                              self._bt_dev, self._lens_dev, self._last)
            except Exception:
                pass
            self._pending_release.clear()

    # -- engine --------------------------------------------------------------
    def _pin(self, *arrays):
        """Keep references to buffers consumed by an in-flight dispatch
        until a later step's fence resolves (the round-4 race: a
        released buffer can be recycled for concurrent jax work while
        the enqueued computation still reads it)."""
        self._pending_release.extend(arrays)
    def _read_page_kv(self, pid: int):
        """Spill-side gather (ISSUE 6): one page's K/V as standalone
        device arrays. Engine thread only — the gather is dispatched
        before any later dispatch can reissue and overwrite the page
        id, so engine-thread program order is the lifetime argument
        (the same one the partial prefill's tail gather relies on)."""
        return self._k_pages[:, pid], self._v_pages[:, pid]

    def _write_pages_kv(self, pids, k_devs, v_devs):
        """Fetch-side scatter (ISSUE 6): land uploaded host-tier pages
        in the pool. Incremental — same pin/barrier contract as the
        prefill scatters."""
        idx = jnp.asarray(np.asarray(pids, np.int32))
        k_new = jnp.stack(k_devs, axis=1).astype(self._k_pages.dtype)
        v_new = jnp.stack(v_devs, axis=1).astype(self._v_pages.dtype)
        self._pin(self._k_pages, self._v_pages, k_new, v_new, idx)
        self._k_pages = self._k_pages.at[:, idx].set(k_new)
        self._v_pages = self._v_pages.at[:, idx].set(v_new)
        if self.pipeline_depth == 1:
            _sync_barrier(self._k_pages, self._v_pages)
            self._pending_release.clear()

    def _poll_fetches(self):
        """Land completed host-tier fetches (ISSUE 6): a finished
        upload is scattered into the pool (the admission then looks
        exactly like a device prefix hit); a failed, cancelled or
        timed-out one degrades to a plain cache miss. An injected
        ``kvcache.evict`` raise during materialization leaves the entry
        parked — the resilient engine loop retries the pass."""
        timeout = self._tier.fetch_timeout
        k = 0
        while k < len(self._fetch_wait):
            ent = self._fetch_wait[k]
            req, adm = ent["req"], ent["adm"]
            job = adm.fetch_job
            done = job is None or job.done.is_set()
            if not done and time.perf_counter() - ent["t0"] <= timeout:
                k += 1
                continue
            landed = (done and job is not None and job.ok
                      and not job.cancelled)
            if landed:
                self._kv.materialize(adm, job.k_dev, job.v_dev)
            else:
                self._kv.degrade(adm)   # failure/timeout → plain miss
            del self._fetch_wait[k]
            wait_s = time.perf_counter() - ent["t0"]
            if flight.enabled:
                flight.record(
                    "fetch", request_id=req.id, trace_id=_trace_of(req),
                    pages=len(adm.shared_pages),
                    wait_ms=round(wait_s * 1000.0, 3),
                    status="landed" if landed else "degraded")
            if req.trace:
                obs.add_complete(
                    "kvtier/fetch_wait", time.time() - wait_s, wait_s,
                    trace=req.trace["trace_id"], request=req.id,
                    pages=len(adm.shared_pages),
                    degraded=adm.matched_len == adm.device_matched
                    and job is not None and not job.ok)
            self._fetch_ready.append((req, adm))

    def _prompt_of(self, req: Request) -> np.ndarray:
        """The token ids admission/prefill must process: the original
        prompt, or prompt + generated_so_far after a preemption
        (ISSUE 17 journal-style resume — greedy decode over the
        extended prompt is deterministic, so the continuation is
        bit-identical to the unpreempted run)."""
        return (req.resume_ids if req.resume_ids is not None
                else req.prompt_ids)

    def _budget_of(self, req: Request) -> int:
        """Decode budget still owed: ``max_new_tokens`` minus tokens
        already drained to the handle before a preemption."""
        return req.max_new_tokens - len(req.tokens)

    def _sched_pop(self) -> Optional[tuple]:
        """Pop the best live, unheld scheduler entry. Done handles are
        dropped; held entries (preempted requests whose old fence
        record has not drained yet — re-admitting one early could
        absorb that step's stale speculative token) are skipped and
        re-parked with their original order."""
        held: List[tuple] = []
        out = None
        while True:
            ent = self._sched.pop_entry()
            if ent is None:
                break
            req = ent[2]
            if req.done.is_set():
                continue           # aborted/failed while queued
            rec = req._hold_rec
            if rec is not None:
                if any(r is rec for r in self._inflight):
                    held.append(ent)
                    continue
                req._hold_rec = None
            out = ent
            break
        for h in held:
            self._sched.push_entry(h)
        return out

    def _phase(self, name: str, **args):
        """One phase of an engine pass, as a span: ``llm/admit``,
        ``llm/grant``, ``llm/dispatch``, ``llm/fence_wait``,
        ``llm/drain``. The phases of a pass follow one another on the
        engine thread and never overlap; ``llm/pass`` is the interval
        from the first one's start to the last one's end, and what they
        leave of it is the pass's self time. Each enters a profiler
        annotation of its own name, so a JAX profile captured from a
        serving process shows them beside the device's timeline."""
        sp = obs.span(name, annotate=True, **args)
        if self._phases is not None:
            self._phases.append(sp)
        return sp

    def _record_pass(self, phases: List[Any]):
        """``llm/pass`` for the loop iteration whose phases these were.
        Ring only: an annotation that encloses the phases would be the
        longest host event over every device-idle gap, and a profile
        reader that names a gap by its longest overlap would then name
        every gap ``llm/pass``."""
        if phases[0].t0 is None or phases[-1].t1 is None:
            return      # observability was off when the pass ran
        args = {"step": self.steps, "rows": 0, "admitted": 0,
                "prefills": 0, "fn": None}
        for sp in phases:
            for k in args.keys() & sp.args.keys():
                args[k] = sp.args[k]
        t0 = phases[0].t0
        dur = phases[-1].t1 - t0
        obs.add_complete("llm/pass", time.time() - (time.perf_counter()
                                                    - t0), dur, t0,
                         **args)

    def _admit_waiting(self) -> bool:
        """Anything an admission sweep could act on: a parked or landed
        host-tier fetch, a held head, a queued or scheduled request."""
        if self._fetch_wait or self._fetch_ready \
                or not self._queue.empty():
            return True
        if getattr(self, "_pending_head", None) is not None:
            return True
        return self._sched is not None and len(self._sched) > 0

    def _seats_somebody(self) -> bool:
        """The coming sweep will prefill a request: one is queued, a
        slot is free, and no head is held back by the page budget (that
        sweep would only fail again, every pass, and draining ahead of
        it would take the pipelining away)."""
        return (self._sched is None and not self._queue.empty()
                and getattr(self, "_pending_head", None) is None
                and any(r is None for r in self._slots))

    def _admit(self):
        """Fill free slots from the queue; per-slot prefill. Paged mode
        additionally requires the request's worst-case page budget
        (prompt + max_new, the conservative vLLM-style reservation) to be
        available — head-of-line: if the next request doesn't fit, no
        later one is admitted either. Host-tier hits (ISSUE 6) are
        PARKED while their pages upload — they hold their budget but no
        slot, so later requests admit and decode meanwhile; completed
        fetches re-enter here first. The sweep is the ``llm/admit``
        phase of the pass (prefills, lookups and fetch waits nest in
        it); a pass with nobody waiting has none."""
        if not self._admit_waiting():
            return
        if self._seats_somebody():
            # deliver before admitting: the step in flight ends within
            # one step's time, a prefill's staging may take several, and
            # a token that waits behind it is a late token of every
            # live row. Drained first, the staging and the prefill fall
            # into ONE gap a row, not two (its own phases of the pass,
            # ahead of ``llm/admit``)
            while self._inflight:
                self._drain_next()
        with self._phase("llm/admit", admitted=0, prefills=0,
                         prompt_tokens=0, bucket_tokens=0,
                         **({"state_zeroed": 0} if self._states
                            else {})) as ph:
            self._admit_args = ph.args
            if self._fetch_wait:
                self._poll_fetches()
            if self._sched is not None:
                # class-ordered admission (ISSUE 17): drain the
                # thread-safe intake queue into the scheduler heap,
                # then admit in (class rank, arrival) order. The heap
                # is engine-thread only; submit() bounds intake + heap
                # together.
                try:
                    while True:
                        self._sched.push(self._queue.get_nowait())
                except queue.Empty:
                    pass
            for i in range(self.max_batch):
                if self._slots[i] is not None:
                    continue
                if not self._admit_into(i):
                    break
            if self._sched is not None and self._sched.live():
                # waiters remain after the sweep (no slot, or the best
                # one is budget-blocked): lossless preemption of a
                # lower-class decode is the relief valve
                self._consider_preempt()

    def _admit_into(self, i: int) -> bool:
        """Admit one request into free slot ``i``. False stops the slot
        sweep: queue exhausted, or the head is budget-blocked
        (head-of-line holds)."""
        while True:
            if self._fetch_ready:
                req, adm = self._fetch_ready[0]
                if req.done.is_set():
                    # aborted / watchdog-failed while fetch-parked: the
                    # grant goes back, nobody decodes for a dead handle
                    self._fetch_ready.pop(0)
                    self._kv.cancel(adm)
                    continue
                # physical headroom for the pages prefill will own,
                # ensured HERE (not at the poll): the entry ahead in
                # this very pass may have consumed what the poll saw
                # free. Peek-then-pop so an injected kvcache.evict
                # raise leaves the entry for the loop's retry.
                own = (-(-len(self._prompt_of(req)) // self._page)
                       - adm.matched_len // self._page)
                if own > 0:
                    self._kv.ensure_free(own)
                self._fetch_ready.pop(0)
                self._slot_adm[i] = adm
                # a landed fetch is indistinguishable from a device
                # prefix hit: a still-long suffix chunks like any
                # other, but its budget was fully charged at admit
                # (the fetch pre-charge contract) — prepaid
                self._prefill_admitted(
                    i, req, adm,
                    chunked=(self._mixed_active
                             and len(self._prompt_of(req))
                             - adm.matched_len > self._chunk_tokens),
                    prepaid=True)
                return True
            ent = None
            if self._sched is not None:
                # class-ordered source (ISSUE 17): the heap replaces
                # both the FIFO queue and the held head — a budget-
                # blocked best entry re-parks below with its ORIGINAL
                # order, so head-of-line becomes head-of-class
                ent = self._sched_pop()
                if ent is None:
                    return False
                req = ent[2]
            else:
                # a budget-blocked head is HELD here (not re-queued:
                # put() appends, and clients submit concurrently, so
                # drain-and-requeue would let a late submit overtake
                # the whole waiting line)
                req = getattr(self, "_pending_head", None)
                if req is None:
                    try:
                        req = self._queue.get_nowait()
                    except queue.Empty:
                        return False
                self._pending_head = None
                if req.done.is_set():
                    # aborted (or watchdog-failed) while queued: skip —
                    # nothing was charged for it yet
                    continue
            ids = self._prompt_of(req)
            budget = self._budget_of(req)
            t_lk = time.perf_counter()
            chunk_first = None
            if self._mixed_active and \
                    len(ids) > self._chunk_tokens:
                # chunked-admission decision (ISSUE 14): a long
                # uncached DEVICE suffix is fed in page-aligned
                # chunks, charging only the first chunk now.
                # Arena-extending matches keep the unchunked fetch
                # path (their budget pre-charges at admit); the
                # peek→admit window is race-free — the engine
                # thread is the only index mutator. Prompts at or
                # under chunk_tokens skip the peek outright (no
                # second radix walk on the short-prompt hot path).
                pk = self._kv.peek(ids, budget)
                if pk["matched_tokens"] == pk["matched_device"] \
                        and pk["pages_needed"] <= \
                        self._num_pages - 1:
                    # the pool-size guard keeps never-admittable
                    # requests (cached prefix evicted since
                    # submit) on the unchunked path, where admit
                    # returns None and the permanent-failure
                    # check below fires — a chunked admit would
                    # loop charge→starve→"retriable" shed forever
                    off0 = pk["matched_device"]
                    suffix = len(ids) - off0
                    if suffix > self._chunk_tokens:
                        end0 = self._chunk_end(
                            off0, len(ids))
                        chunk_first = (-(-end0 // self._page)
                                       - off0 // self._page)
            try:
                # lookup + suffix-only budget charge + adoption refs
                # + pre-eviction for the prompt's own pages, in one
                # atomic manager call (ISSUE 5); chunked admissions
                # charge the first chunk only (ISSUE 14)
                adm = self._kv.admit(ids, budget,
                                     chunk_pages=chunk_first)
            except BaseException:
                # injected kvcache.evict fault: nothing was charged
                # or adopted — hold the head (or re-park the heap
                # entry in place), let the loop retry
                if ent is not None:
                    self._sched.push_entry(ent)
                else:
                    self._pending_head = req
                raise
            if adm is not None and not all(
                    r.admit(i, len(ids) + budget) for r in self._rings):
                for r in self._rings:
                    r.release(i)
                self._kv.cancel(adm)
                adm = None
            if adm is None:
                peek = self._kv.peek(ids, budget)
                if peek["pages_needed"] > self._num_pages - 1:
                    # the cached prefix that made this request
                    # feasible at submit time has been evicted: it
                    # can never be admitted now — fail it instead
                    # of wedging the whole admission line
                    req.error = (
                        f"request needs {peek['pages_needed']} "
                        f"pages but the pool holds "
                        f"{self._num_pages - 1} (cached prefix "
                        "evicted since submit)")
                    req.done.set()
                    continue
                if ent is not None:
                    # budget-blocked: re-park in place, keep
                    # sweeping nothing — the preempt pass at the
                    # end of _admit is the relief valve
                    self._sched.push_entry(ent)
                else:
                    self._pending_head = req   # retry next pass
                return False
            if self._kv.enabled:
                wall = time.perf_counter() - t_lk
                obs.add_complete(
                    "kvcache/lookup", time.time() - wall, wall,
                    request=req.id, matched_tokens=adm.matched_len,
                    prompt_tokens=len(ids))
                if flight.enabled:
                    flight.record(
                        "radix_hit" if adm.matched_len else
                        "radix_miss", request_id=req.id,
                        trace_id=_trace_of(req),
                        matched_tokens=adm.matched_len,
                        device_matched=adm.device_matched,
                        prompt_tokens=len(ids))
                    if adm.tail_src is not None:
                        flight.record(
                            "cow_fork", request_id=req.id,
                            trace_id=_trace_of(req),
                            src_page=adm.tail_src,
                            tail_tokens=adm.tail_len)
            if adm.fetch:
                # host-tier hit: park until the upload lands; keep
                # filling this slot from the queue meanwhile
                if flight.enabled:
                    flight.record(
                        "park", request_id=req.id,
                        trace_id=_trace_of(req),
                        pages=len(adm.fetch))
                self._fetch_wait.append(
                    {"req": req, "adm": adm,
                     "t0": time.perf_counter()})
                continue
            self._slot_adm[i] = adm
            self._prefill_admitted(i, req, adm,
                                   chunked=chunk_first is not None)
            return True

    def _prefill_admitted(self, i: int, req: Request, adm,
                          chunked: bool = False, prepaid: bool = False):
        """Prefill a request whose cache grant is already held (shared
        tail of direct and fetch-parked admissions). ``chunked`` routes
        long-suffix admissions to the unified dispatch (ISSUE 14): no
        model dispatch here — the prompt is fed chunk by chunk in
        subsequent engine passes, interleaved with decode."""
        ctx = rc.from_wire(req.trace)
        now = time.perf_counter()
        if not req.t_admit:
            req.t_admit = now
        # engine-side admission wait of every request; one that carries
        # a trace context is parented to its submitter besides
        args = {}
        if ctx is not None:
            args["trace"] = ctx.trace_id
            if ctx.span_id:
                args["parent_span"] = ctx.span_id
        wait = now - req.t_submit
        obs.add_complete("llm/queue_wait", time.time() - wait, wait,
                         req.t_submit, stage="queue", request=req.id,
                         **args)
        ids = self._prompt_of(req)
        self._admit_args["admitted"] += 1
        self._admit_args["prompt_tokens"] += len(ids) - adm.matched_len
        if flight.enabled:
            flight.record(
                "admit", request_id=req.id, trace_id=_trace_of(req),
                slot=i, chunked=chunked, prepaid=prepaid,
                matched_tokens=adm.matched_len,
                prompt_tokens=len(ids))
        if self._sched is not None and req.resume_ids is not None:
            # a preempted request re-took a slot (ISSUE 17): the resume
            # event mirrors the preempt one — chaos reconciles the two
            # tallies exactly against preemptions_total
            self.preempt_resumes_total += 1
            if self._parked is not None:
                self._parked.pop(req.id, None)
            if flight.enabled:
                flight.record(
                    "preempt_resume", request_id=req.id,
                    trace_id=_trace_of(req), slot=i,
                    priority=req.priority,
                    tokens_done=len(req.tokens),
                    remaining=self._budget_of(req))
        if chunked:
            self._begin_chunked(i, req, adm, prepaid)
            return
        t0 = time.perf_counter()
        try:
            with rc.activate(ctx), \
                    obs.span("llm/prefill", slot=i,
                             tokens=len(ids),
                             stage="llm_server", request=req.id):
                self._prefill_ragged(i, req, adm)
        except BaseException as e:
            # a failing prefill must not leak its admission budget
            # or adoption refcounts (the resilient _loop would
            # otherwise shrink the pool forever) nor leave the
            # client blocked until timeout
            self._kv.cancel(adm)
            for r in self._rings + self._states:
                r.release(i)
            self._slot_adm[i] = None
            req.error = f"{type(e).__name__}: {e}"
            req.done.set()
            raise
        req.decode_started_at = time.time()
        self._admit_args["prefills"] += 1
        self._record_prefill(len(ids) - adm.matched_len,
                             time.perf_counter() - t0)

    def _instruments(self):
        """None when observability is off; declared on first use so
        ``obs.enable()`` starts recording on a LIVE server (the runtime-
        override contract), and a disabled run declares nothing."""
        if not obs.enabled():
            return None
        if self._ins is None:
            self._ins = _llm_instruments()
        return self._ins

    def _priority_instruments_get(self):
        """None unless the priority scheduler exists AND observability
        records — same lazy-declaration contract as _instruments(),
        same structural-absence contract as _mixed_instruments()."""
        if not (self._sched is not None and obs.enabled()):
            return None
        if self._pri_ins is None:
            self._pri_ins = _priority_instruments()
        return self._pri_ins

    def _record_kv_gauges(self, ins):
        backlog = len(self._sched) if self._sched is not None else 0
        ins["queue"].set(self._queue.qsize() + backlog)
        pri = self._priority_instruments_get()
        if pri is not None:
            for cls, depth in self._sched.depths().items():
                pri["queue_class"].labels(**{"class": cls}).set(depth)
            pri["parked"].set(self._sched.parked())
        ins["kv_pages"].set(self.pages_in_use)
        if self._multi and self._every is not None:
            # a family of several classes with pages in one or more of
            # them: each page class's own gauge (a state class has no
            # pages: its gauge is below, and both are live for a family
            # that declares a state class beside a page class)
            if self._class_ins is None:
                self._class_ins = obs.gauge(
                    "bigdl_llm_kv_class_pages_in_use",
                    "Physical KV pages owned by live requests, by page "
                    "class (families that cache in several)",
                    labelnames=("page_class",))
            for name, n in self.pages_in_use_by_class.items():
                self._class_ins.labels(page_class=name).set(n)
        if self._states:
            if self._state_ins is None:
                self._state_ins = obs.gauge(
                    "bigdl_llm_state_slots_in_use",
                    "Engine slots seated in a state class (families "
                    "whose cache is a fixed state a slot)")
            self._state_ins.set(self.state_slots_in_use)
        # page 0 is the reserved trash page, never allocatable
        ins["kv_occupancy"].set(
            self.pages_in_use / max(self._num_pages - 1, 1))
        self._kv.record_gauges()   # bigdl_kvcache_* (enabled only)

    def _record_prefill(self, n_tokens: int, seconds: float):
        self.prefill_tokens_total += n_tokens   # always-on (microbench)
        ins = self._instruments()
        if ins is not None:
            ins["prefill_tokens"].inc(n_tokens)
            ins["prefill_seconds"].observe(seconds)
            self._record_kv_gauges(ins)

    # -- paged engine --------------------------------------------------------
    def _step_cache_key(self) -> tuple:
        """Value key for the shared compiled-step cache. id(cfg) would be
        unsound (a recycled address after GC aliases a different config)
        and the closures bake every cfg field, the page size and the
        cache dtype — so all of them key the entry."""
        import dataclasses
        return (self._family, dataclasses.astuple(self.cfg), self._page,
                str(jnp.dtype(self.model.cache_dtype)))

    def _finish_prefill(self, i: int, req: Request, row_pages, own,
                        last, pins, adm=None):
        """Shared epilogue of a whole-prompt prefill and a chunked
        admission's final chunk: pin every buffer the dispatch consumed
        (the PR 4 buffer-lifetime invariant, docs/PERFORMANCE.md), land
        the slot's block table + length host- and device-side,
        reproduce the synchronous cadence at depth 1, drop the
        admission's transient tail ref (consumed in program order by
        the dispatch), then hand the slot to the request. ONE copy so a
        fix to the pin set or barrier cadence cannot drift between the
        paths. All of it is the ``llm/prefill_finish`` span: its eager
        updates (``updates`` of them) queue behind the prefill just
        dispatched, so where the host waits in one, it waits here."""
        with obs.span("llm/prefill_finish", annotate=True, request=req.id,
                      updates=2 + (self._every is not None)
                      + len(self._rings)):
            self._pin(*pins, last, self._last, self._bt_dev,
                      self._lens_dev)
            self._last = self._last.at[i].set(last)
            T = len(self._prompt_of(req))
            self._lens[i] = T
            if self._every is not None:
                npages = len(row_pages)
                self._bt[i, :] = 0
                self._bt[i, :npages] = row_pages
                row = np.zeros(self._pages_cap, np.int32)
                row[:npages] = row_pages
                row_d = jnp.asarray(row)
                self._pin(row_d)
                self._bt_dev = self._bt_dev.at[i].set(row_d)
            self._lens_dev = self._lens_dev.at[i].set(T)
            for c in range(len(self._rings)):
                self._put_ring_row(c, i)
            if self.pipeline_depth == 1:
                _sync_barrier(self._k_pages, self._v_pages, self._last,
                              self._bt_dev, self._lens_dev)
                self._pending_release.clear()
            if adm is not None:
                self._kv.release_transient(adm)
            self._slot_pages[i] = own
            self._slots[i] = req
            self._remaining[i] = self._budget_of(req)
            self._index_prompt(i, req)

    def _build_ragged_prefill(self, bucket: int):
        """Compile the family's ragged in-place prefill for ONE suffix
        bucket (ISSUE 8). Prefix pages, the position offset and the
        scatter targets are all runtime arguments, so the compile grid
        is O(suffix-buckets) (guarded by the compile-recorder
        regression test)."""
        cfg, page = self.cfg, self._page
        fam = self._fam_ragged_prefill

        def build(params, k_pages, v_pages, toks, length, offset,
                  bt_row, phys, slots, fork_dst, fork_src):
            return fam(params, cfg, k_pages, v_pages, toks, length,
                       offset, bt_row, phys, slots, fork_dst, fork_src,
                       page=page)

        return obs.compiled(build, name="llm/prefill_ragged",
                            donate_argnums=(1, 2))

    def _prefill_ragged(self, i: int, req: Request, adm):
        """Prefill in place on the page pool (ISSUE 8): the suffix runs
        at position offset ``matched_len`` while attention reads the
        adopted prefix pages through the block table — no dense temp
        cache, no prefix gather/scatter. One program serves the full-
        prefill (offset 0) and every partial-prefix case, including
        tier re-prefills (a materialized fetch is indistinguishable
        from a device prefix hit by the time prefill runs). The COW
        tail fork is a single page copy fused ahead of the layer scan.

        Three spans tile it inside the caller's ``llm/prefill``:
        ``llm/prefill_stage`` (everything up to the jit call: host work
        the device waits through, the step in flight having been
        drained ahead of the sweep), ``llm/prefill_dispatch`` (the jit
        call and nothing else) and ``_finish_prefill``'s
        ``llm/prefill_finish``."""
        page = self._page
        own: List[int] = []
        try:
            with obs.span("llm/prefill_stage", annotate=True,
                          request=req.id) as stage:
                prompt = self._prompt_of(req)
                T = len(prompt)
                off = adm.matched_len
                koff = off // page
                own = self._kv.alloc(-(-T // page) - koff
                                     if self._every is not None else 0)
                row_pages = list(adm.shared_pages) + own
                tail = adm.tail_src is not None
                t_suf = T - off
                bucket = max(page, 1 << (t_suf - 1).bit_length())  # pow2
                key = self._step_cache_key() + ("prefill_ragged", bucket)
                fn = _PAGED_STEP_CACHE.get(key)
                if fn is None:
                    fn = _PAGED_STEP_CACHE[key] = \
                        self._build_ragged_prefill(bucket)
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :t_suf] = prompt[off:]
                bt_row = np.zeros(self._pages_cap, np.int32)
                bt_row[:len(row_pages)] = row_pages
                # scatter targets for the suffix window [off,
                # off+bucket): token j lands in (phys[j], slots[j]);
                # positions past the true prompt route to trash page 0
                pos = off + np.arange(bucket)
                phys = np.where(pos < T,
                                bt_row[np.minimum(pos // page,
                                                  self._pages_cap - 1)],
                                0).astype(np.int32)
                slots = (pos % page).astype(np.int32)
                toks_d = jnp.asarray(toks)
                len_d = jnp.asarray(t_suf, jnp.int32)
                off_d = jnp.asarray(off, jnp.int32)
                bt_d = jnp.asarray(bt_row)
                phys_d = jnp.asarray(phys)
                slots_d = jnp.asarray(slots)
                transfers = 8       # these six and the fork's two
                if self._multi or self._every is None:
                    # a window class takes the prompt's pages of its
                    # ring now; every position is written there, a
                    # later one over an earlier one of the same slot,
                    # in order. A state class seats the request in its
                    # slot's row (the program takes what the row holds
                    # as zero) and has nothing to scatter
                    for r in self._rings:
                        r.grant(i, T)
                    seats = [s.seat(i) for s in self._states]
                    bts = self._if_every(bt_d) \
                        + [jnp.asarray(r.bt[i]) for r in self._rings] \
                        + [jnp.asarray(s.rows[i]) for s in self._states]
                    physs = self._if_every(phys_d) + [
                        jnp.asarray(r.scatter_targets(i, pos, T))
                        for r in self._rings] + [phys_d] * len(seats)
                    transfers += 2 * len(self._rings) + len(seats)
                    bt_d, phys_d = (tuple(bts), tuple(physs)) \
                        if self._multi else (bts[0], physs[0])
                    if seats:
                        self.step_counters[
                            "state_slots_zeroed_total"] += 1
                        self._admit_args["state_zeroed"] = \
                            self._admit_args.get("state_zeroed", 0) + 1
                fork_dst = jnp.asarray(own[0] if tail else 0, jnp.int32)
                fork_src = jnp.asarray(adm.tail_src if tail else 0,
                                       jnp.int32)
                stage.args.update(bucket=bucket, transfers=transfers)
            with obs.span("llm/prefill_dispatch", annotate=True,
                          request=req.id, bucket=bucket,
                          fn="llm/prefill_ragged"):
                self._k_pages, self._v_pages, last = fn(
                    self.model.params, self._k_pages, self._v_pages,
                    toks_d, len_d, off_d, bt_d, phys_d, slots_d,
                    fork_dst, fork_src)
            self._prefill_seq += 1
            self._admit_args["bucket_tokens"] += bucket
            if self._fam_prefill_stats is not None:
                for name, n in self._fam_prefill_stats(
                        self.cfg, t_suf, bucket).items():
                    self.step_counters[name] += n
        except BaseException:
            self._kv.free_owned(own)
            raise  # (the rings' pages go with r.release, at the caller)
        # shared epilogue; the fork copy consumed the tail source in
        # dispatch order, so the transient ref/pin drops there (the
        # donated-pool dependency chain orders any later overwrite
        # after the copy)
        self._finish_prefill(i, req, row_pages, own, last,
                             (toks_d, len_d, off_d, bt_d, phys_d,
                              slots_d, fork_dst, fork_src), adm=adm)

    def _index_prompt(self, i: int, req: Request):
        """Make this request's FULL prompt pages reusable immediately
        (not at EOS): concurrent requests sharing the prompt adopt them
        while this one is still decoding. The partially-filled prompt
        tail stays private — it is indexed at EOS, and adopters fork it
        (COW) rather than racing this request's decode writes."""
        if not self._kv.enabled:
            return
        prompt = self._prompt_of(req)
        nfull = len(prompt) // self._page
        if nfull:
            self._kv.insert(prompt[:nfull * self._page],
                            self._bt[i, :nfull])

    # -- unified mixed prefill+decode dispatch (ISSUE 14) --------------------
    def _chunk_end(self, off: int, T: int) -> int:
        """Page-aligned end of the next chunk from offset ``off``: the
        largest page multiple within ``chunk_tokens`` of ``off`` — so
        every chunk after the first starts page-aligned and only the
        final one (which runs to the prompt end) may end mid-page."""
        end = ((off + self._chunk_tokens) // self._page) * self._page
        return T if end >= T else max(end, off + 1)

    def _begin_chunked(self, i: int, req: Request, adm, prepaid: bool):
        """Admit a long-suffix request WITHOUT prefilling it: the
        prompt is fed in page-aligned chunks by subsequent engine
        passes (fused with decode rows — see ``_dispatch_mixed``), so
        one admission never monopolizes a pass. The slot is held
        (admission order and ``stop(drain=True)`` semantics preserved)
        but stays decode-inactive until the final chunk lands.
        ``prepaid`` admissions (host-tier fetches) charged their whole
        budget at admit; everyone else charges chunk by chunk."""
        self._chunk_state[i] = {
            "req": req, "adm": adm, "off": adm.matched_len,
            "row_pages": list(adm.shared_pages), "own": [],
            "prepaid": prepaid, "first": True,
            "t0": time.perf_counter(), "wait_t0": None,
        }
        self._slots[i] = req
        self._remaining[i] = 0
        self._slot_adm[i] = adm

    def _chunk_slot(self) -> Optional[int]:
        """Round-robin pick of ONE chunking slot to advance this pass —
        the scheduler's per-pass prefill budget is a single chunk of at
        most ``chunk_tokens`` tokens, so concurrent chunkers share the
        engine fairly. Dead requests (aborted, watchdog-failed) roll
        back here before they can waste a dispatch."""
        if self._chunk_state is None:
            return None
        n = self.max_batch
        if self._sched is not None:
            # class-ordered chunk selection (ISSUE 17): the per-pass
            # prefill budget goes to the highest-class chunker —
            # within a class, lowest slot keeps the pick stable (no
            # round-robin: two equal-class chunkers alternate only
            # when the leader stalls on the ledger)
            best = None
            for i in range(n):
                st = self._chunk_state[i]
                if st is None:
                    continue
                if st["req"].cancel_requested or \
                        st["req"].done.is_set():
                    self._rollback_chunk(i, None)
                    continue
                key = (_PRIORITY_RANK[st["req"].priority], i)
                if best is None or key < best[0]:
                    best = (key, i)
            return best[1] if best is not None else None
        for k in range(n):
            i = (self._chunk_rr + k) % n
            st = self._chunk_state[i]
            if st is None:
                continue
            if st["req"].cancel_requested or st["req"].done.is_set():
                self._rollback_chunk(i, None)
                continue
            self._chunk_rr = (i + 1) % n
            return i
        return None

    def _prepare_chunk(self, i: int) -> Optional[dict]:
        """Ledger charge + operand build for slot ``i``'s next chunk.
        None = nothing to dispatch this pass: the ``llm.chunk`` fault
        fired (chain rolled back, request failed retriably) or the
        ledger cannot cover the chunk yet — the engine keeps decoding
        and retries next pass, shedding past ``chunk_wait`` so
        concurrent chunkers can never deadlock the pool against each
        other (each holds pages the others wait on)."""
        st = self._chunk_state[i]
        req, adm = st["req"], st["adm"]
        page = self._page
        ids = self._prompt_of(req)
        T = len(ids)
        off = st["off"]
        if not st["first"]:
            # the mid-admission fault site (ISSUE 14): a raise between
            # chunks frees the partial chain and fails the request
            # retriably — chaos_check --mixed proves a resubmission is
            # then bit-identical
            try:
                reliability.inject("llm.chunk")
            except BaseException as e:
                self._rollback_chunk(
                    i, f"chunked admission failed between chunks: "
                       f"{type(e).__name__}: {e} (retriable: partial "
                       "chain rolled back; resubmit)")
                return None
        end = self._chunk_end(off, T)
        c = end - off
        n_new = -(-end // page) - len(st["row_pages"])
        final = end == T
        need = n_new
        if final and not st["prepaid"]:
            # decode-budget top-up: every page the request may still
            # need past its prompt — the reserve that keeps decode
            # deadlock-free, charged at the last possible moment so
            # Σ(admit + chunk charges) equals the unchunked worst case
            # exactly (the first chunk never charges here: suffix >
            # chunk_tokens means it never reaches the prompt end)
            need += (-(-(T + self._budget_of(req)) // page)
                     - (-(-T // page)))
        # ledger FIRST: admit(chunk_pages=) already charged the FIRST
        # chunk, and prepaid (fetch-path) admissions charged in full —
        # only later chunks extend the charge here. A successful
        # charge guarantees free+evictable covers n_new (allocated <=
        # charged pool-wide), so the disabled-cache ensure_free can
        # never hit its "shortage with the cache disabled" invariant.
        # An ensure_free raise (the injected kvcache.evict) uncharges
        # before propagating — the pass retry starts from a clean
        # ledger.
        charge_now = 0 if (st["prepaid"] or st["first"]) else need
        if charge_now and not self._kv.charge_chunk(adm, charge_now):
            now = time.perf_counter()
            if st["wait_t0"] is None:
                st["wait_t0"] = now
            elif now - st["wait_t0"] > self._chunk_wait:
                victim = i
                if self._sched is not None:
                    # class-ordered shed victim (ISSUE 17): a starved
                    # HIGH-class chunker sheds the worst strictly-
                    # lower-class chunker instead of itself — freeing
                    # that chain is exactly what unblocks the ledger.
                    # No lower-class peer → shed self (unchanged).
                    rank_i = _PRIORITY_RANK[req.priority]
                    worst = None
                    for j in range(self.max_batch):
                        sj = self._chunk_state[j]
                        if sj is None or j == i:
                            continue
                        rj = _PRIORITY_RANK[sj["req"].priority]
                        if rj > rank_i and (worst is None
                                            or (rj, j) > worst[0]):
                            worst = ((rj, j), j)
                    if worst is not None:
                        victim = worst[1]
                        st["wait_t0"] = now   # fresh window for i: the
                        # shed frees pages only after the rollback
                self._rollback_chunk(
                    victim,
                    f"chunked admission starved: the ledger could "
                    f"not cover the next {charge_now} pages within "
                    f"{self._chunk_wait:g}s (retriable: partial "
                    "chain rolled back; resubmit)")
            return None
        st["wait_t0"] = None
        try:
            if n_new > 0:
                self._kv.ensure_free(n_new)
            new_pages = self._kv.alloc(n_new) if n_new > 0 else []
        except BaseException:
            self._kv.uncharge_chunk(adm, charge_now)
            raise
        row_pages = st["row_pages"] + new_pages
        tail = st["first"] and adm.tail_src is not None
        bucket = max(page, 1 << (c - 1).bit_length())   # pow2 ladder
        bt_row = np.zeros(self._pages_cap, np.int32)
        bt_row[:len(row_pages)] = row_pages
        # scatter targets for the window [off, off+bucket): positions
        # past this chunk's end route to trash page 0 — their pages may
        # not exist yet (they are a LATER chunk's)
        pos = off + np.arange(bucket)
        phys = np.where(pos < end,
                        bt_row[np.minimum(pos // page,
                                          self._pages_cap - 1)],
                        0).astype(np.int32)
        slots = (pos % page).astype(np.int32)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :c] = ids[off:end]
        with self._eager:
            ops = (jnp.asarray(toks), jnp.asarray(c, jnp.int32),
                   jnp.asarray(off, jnp.int32), jnp.asarray(bt_row),
                   jnp.asarray(phys), jnp.asarray(slots),
                   jnp.asarray(new_pages[0] if tail else 0, jnp.int32),
                   jnp.asarray(adm.tail_src if tail else 0, jnp.int32))
        if flight.enabled:
            flight.record(
                "chunk_charge", request_id=req.id,
                trace_id=_trace_of(req), chunk_tokens=c, off=off,
                end=end, final=final, charged_pages=charge_now,
                new_pages=len(new_pages))
        return {"i": i, "c": c, "end": end, "final": final,
                "bucket": bucket, "new_pages": new_pages,
                "charged": charge_now, "ops": ops}

    def _chunk_dispatched(self, cargs: dict, clast):
        """Post-dispatch chunk bookkeeping (host side, overlapping the
        device): advance the chunk cursor; on the FINAL chunk run the
        ``_finish_prefill`` epilogue — the slot flips to an ordinary
        decode row with the chunk-accumulated page chain. Runs AFTER
        the pass's in-flight record is cut, so the epilogue's scatters
        pin into the NEXT fence (or the depth-1 barrier here), never
        the already-sealed record's."""
        i = cargs["i"]
        st = self._chunk_state[i]
        req, adm = st["req"], st["adm"]
        st["row_pages"].extend(cargs["new_pages"])
        st["own"].extend(cargs["new_pages"])
        st["off"] = cargs["end"]
        if st["first"]:
            st["first"] = False
            # the fork copy consumed the tail source in dispatch order
            # (the _prefill_ragged argument, unchanged)
            self._kv.release_transient(adm)
        c = cargs["c"]
        self.prefill_tokens_total += c
        self.prefill_chunks_total += 1
        ins = self._instruments()
        if ins is not None:
            ins["prefill_tokens"].inc(c)
        if not cargs["final"]:
            if self.pipeline_depth == 1:
                # synchronous cadence per chunk: pool writes resolve
                # before their consumed buffers drop (the
                # _finish_prefill contract)
                _sync_barrier(self._k_pages, self._v_pages)
                self._pending_release.clear()
            return
        # -- final chunk: the SHARED _finish_prefill epilogue (one copy
        # — a fix to the pin set or barrier cadence cannot drift
        # between the whole-prompt paths and this one). The tail ref
        # was already dropped at the first chunk, so adm stays None.
        self._chunk_state[i] = None
        self._finish_prefill(i, req, st["row_pages"], st["own"], clast,
                             ())
        req.decode_started_at = time.time()
        if ins is not None:
            # admission→prompt-complete wall (decode passes interleave
            # by design, so this is CHUNKED-prefill latency, not pure
            # dispatch time — documented in docs/PERFORMANCE.md)
            ins["prefill_seconds"].observe(
                time.perf_counter() - st["t0"])
            self._record_kv_gauges(ins)

    def _rollback_chunk(self, i: int, msg: Optional[str]):
        """Mid-prompt shed/abort/fault (ISSUE 14): free the partial
        chain's pages and every ledger charge taken so far, drop the
        adoption refs, fail the request retriably (``msg`` None =
        already-dead handle, nothing to report). Pages a still-in-
        flight chunk or mixed step reads are released at the newest
        in-flight fence (the PR 4 pin invariant extended to chunk
        chains); with nothing in flight, a barrier bounds any pending
        bookkeeping first."""
        st = self._chunk_state[i]
        req, adm = st["req"], st["adm"]
        self._kv.release_transient(adm)
        entry = (adm.charge + adm.fetch_reserved, list(st["own"]),
                 list(adm.shared_pages))
        adm.charge = 0
        adm.fetch_reserved = 0
        adm.shared_pages = []
        if self._pending_release:
            # bookkeeping or a SOLO chunk dispatched AFTER the newest
            # in-flight record may still read this chain's pages, and
            # no record's fence bounds it — barrier on the current
            # arrays (they data-depend on everything enqueued) before
            # the pages go back. Rollback is rare; the stall is not.
            try:
                _sync_barrier(self._k_pages, self._v_pages,
                              self._bt_dev, self._lens_dev,
                              self._last)
            except Exception:
                pass
            self._pending_release.clear()
            self._kv.release_slot(*entry)
        elif self._inflight:
            # every dispatch touching the chain is inside the window:
            # the newest fence bounds them all (in-order stream)
            self._inflight[-1].setdefault("kv_release", []).append(
                entry)
        else:
            self._kv.release_slot(*entry)
        self._chunk_state[i] = None
        self._slots[i] = None
        self._remaining[i] = 0
        self._slot_adm[i] = None
        if flight.enabled:
            flight.record(
                "rollback", request_id=req.id, trace_id=_trace_of(req),
                reason="cancelled" if msg is None else "starved",
                released_pages=len(entry[1]) + len(entry[2]))
        if msg is not None and not req.done.is_set():
            req.error = msg
            req.done.set()
        ins = self._instruments()
        if ins is not None:
            ins["requests"].labels(
                reason="cancelled" if msg is None else "error").inc()

    def _build_mixed_step(self):
        """Compile the unified mixed step for ONE chunk-suffix bucket
        (the chunk operand shapes fix it), composed here from the
        family's two programs: the decode leg is the sampled step
        VERBATIM, the chunk leg the family ragged prefill VERBATIM —
        see ``kvcache.prefill.make_mixed_step``.
        Offsets, block tables and scatter targets are runtime data, so
        the mixed grid adds O(suffix-buckets) programs total (guarded
        by the compile-recorder test in tests/test_mixed_dispatch.py)."""
        cfg, page = self.cfg, self._page
        fam = make_mixed_step(self._fam_paged_step,
                              self._fam_ragged_prefill)
        do_sample, top_k = self._do_sample, self.top_k

        def step(params, k_pages, v_pages, bt, lens, last, active,
                 temp, key, ctoks, clen, coff, cbt_row, cphys, cslots,
                 fork_dst, fork_src):
            return fam(params, cfg, k_pages, v_pages, bt, lens, last,
                       active, temp, key, ctoks, clen, coff, cbt_row,
                       cphys, cslots, fork_dst, fork_src, page=page,
                       do_sample=do_sample, top_k=top_k)

        return obs.compiled(step, name="llm/step_mixed",
                            donate_argnums=(1, 2))

    def _mixed_instruments(self):
        """Unified-dispatch pass metrics — None unless the mixed gate
        is live AND observability records. ``bigdl.llm.mixed.enabled``
        off must leave no ``bigdl_llm_pass_rows_total`` /
        ``bigdl_llm_prefill_chunks_total`` / ``bigdl_llm_pass_mix``
        series (the disabled-mode absence contract)."""
        if not (self._mixed_active and obs.enabled()):
            return None
        if self._mixed_ins is None:
            self._mixed_ins = {
                "pass_rows": obs.counter(
                    "bigdl_llm_pass_rows_total",
                    "Rows served by unified engine passes, by kind",
                    labelnames=("kind",)),
                "chunks": obs.counter(
                    "bigdl_llm_prefill_chunks_total",
                    "Prefill chunks dispatched by the unified engine"),
                "mix": obs.gauge(
                    "bigdl_llm_pass_mix",
                    "Decode-row fraction of the last unified pass "
                    "(1.0 = pure decode, 0.0 = chunk-only)"),
            }
        return self._mixed_ins

    def _record_mixed_pass(self, n_decode: int, cargs: dict,
                           t_step: float):
        """Per-pass batch-mix attribution (ISSUE 14 observability)."""
        if n_decode:
            self.mixed_passes += 1
        ins = self._mixed_instruments()
        if ins is None:
            return
        wall = time.perf_counter() - t_step
        ins["pass_rows"].labels(kind="prefill_chunk").inc()
        if n_decode:
            ins["pass_rows"].labels(kind="decode").inc(n_decode)
        ins["chunks"].inc()
        ins["mix"].set(n_decode / (n_decode + 1))
        obs.add_complete(
            "llm/mixed_step", time.time() - wall, wall,
            decode_rows=n_decode, chunk_tokens=cargs["c"],
            offset=cargs["end"] - cargs["c"], final=cargs["final"],
            slot=cargs["i"])

    def _restore_chunk_pass(self, cargs: dict):
        """A pass failed AFTER _prepare_chunk allocated/charged but
        before (or at) the dispatch: restore the chunk's pages and
        ledger exactly so the engine loop's pass retry re-prepares the
        same chunk from a clean state (nothing in ``st`` advanced —
        row_pages/own only extend in ``_chunk_dispatched``)."""
        self._kv.free_owned(cargs["new_pages"])
        self._kv.uncharge_chunk(self._chunk_state[cargs["i"]]["adm"],
                                cargs["charged"])

    def _dispatch_chunk_solo(self, cargs: dict, t_step: float):
        """A chunk with no live decode rows to fuse with: dispatch it
        through the per-bucket ragged-prefill program (identical chunk
        math to the mixed program's chunk leg — the parity matrix
        covers both routes) with prefill-style pinning/barriers."""
        key = self._step_cache_key() + ("prefill_ragged",
                                        cargs["bucket"])
        fn = _PAGED_STEP_CACHE.get(key)
        if fn is None:
            fn = _PAGED_STEP_CACHE[key] = \
                self._build_ragged_prefill(cargs["bucket"])
        try:
            self._k_pages, self._v_pages, clast = fn(
                self.model.params, self._k_pages, self._v_pages,
                *cargs["ops"])
        except BaseException:
            # dispatch failed before any state advanced: restore the
            # chunk's ledger/pages exactly — the engine loop retries
            # the whole pass, chunk included
            self._restore_chunk_pass(cargs)
            raise
        self._prefill_seq += 1
        self._pin(*cargs["ops"])
        self._chunk_dispatched(cargs, clast)
        self._record_mixed_pass(0, cargs, t_step)

    def _dispatch_mixed(self, disp, active, cargs: dict,
                        t_step: float) -> bool:
        """One UNIFIED pass (the ISSUE 14 tentpole): every active
        decode row plus one prefill chunk in a single compiled program
        — the chunk no longer stalls the decode stream, and the
        drain/fence machinery treats the pass exactly like a decode
        pass (the chunk row emitted no token, so it drains an empty
        slot)."""
        key = self._step_cache_key() + ("mixed", cargs["bucket"],
                                        self._do_sample, self.top_k)
        pmixed = _PAGED_STEP_CACHE.get(key)
        if pmixed is None:
            pmixed = _PAGED_STEP_CACHE[key] = self._build_mixed_step()
        bt_in, lens_in = self._bt_dev, self._lens_dev
        last_in, key_in = self._last, self._sample_key
        try:
            out, logits, self._k_pages, self._v_pages, \
                self._lens_dev, self._sample_key, clast = pmixed(
                    self.model.params, self._k_pages, self._v_pages,
                    bt_in, lens_in, last_in, active, self._temp,
                    key_in, *cargs["ops"])
        except BaseException:
            self._restore_chunk_pass(cargs)
            raise
        self._prefill_seq += 1
        self._last = logits
        for i in disp:
            self._lens[i] += 1
            self._remaining[i] -= 1
        rec = {"out": out, "fn": "llm/step_mixed",
               "pairs": [(i, self._slots[i]) for i in disp],
               "refs": (bt_in, lens_in, last_in, active, key_in)
               + cargs["ops"],
               "pinned": self._pending_release}
        self._pending_release = []
        # chunk bookkeeping AFTER the record is cut: the finalize
        # epilogue's scatters dispatch behind this step, so their pins
        # must ride the NEXT fence (or the depth-1 barrier inside
        # _chunk_dispatched), never this record's
        self._chunk_dispatched(cargs, clast)
        self._record_mixed_pass(len(disp), cargs, t_step)
        return self._after_dispatch(rec, t_step)

    # -- self-speculative decoding (ISSUE 19) --------------------------------
    def _spec_instruments(self):
        """Speculation counters — None unless the spec gate is live AND
        observability records. ``bigdl.llm.spec.enabled`` off must
        leave no ``bigdl_llm_spec_*`` series (the disabled-mode
        absence contract)."""
        if not (self._spec_active and obs.enabled()):
            return None
        if self._spec_ins is None:
            self._spec_ins = {
                "proposed": obs.counter(
                    "bigdl_llm_spec_proposed_tokens_total",
                    "Draft tokens dispatched to speculative verify"),
                "accepted": obs.counter(
                    "bigdl_llm_spec_accepted_tokens_total",
                    "Draft tokens accepted by speculative verify"),
                "passes": obs.counter(
                    "bigdl_llm_spec_passes_total",
                    "Engine passes carrying a speculative verify "
                    "chunk"),
            }
        return self._spec_ins

    def _spec_proposer(self, i: int, req: Request):
        """Slot ``i``'s draft proposer, (re)created lazily per request
        — the adaptive-k state (acceptance EMA, live draft length) is
        the request's own, so a new occupant starts optimistic."""
        st = self._spec_state[i]
        if st is None or st["req"] is not req:
            st = self._spec_state[i] = {
                "req": req,
                "prop": self._spec_proposer_cls(
                    k=self._spec_k, min_match=self._spec_min_match,
                    backoff=self._spec_backoff)}
        return st["prop"]

    def _prepare_spec(self) -> Optional[dict]:
        """Pick one decode row whose token history predicts its future
        and draft for it. None = no row proposes this pass (or the
        ``llm.spec`` fault fired) — the pass degrades to plain decode,
        bit-identically.

        Two-phase on purpose: drafting needs the row's EXACT emitted
        history and length, which at depth > 1 are only current after
        the in-flight window drains — but draining costs the pipeline
        overlap. So a cheap pre-check proposes on the possibly-stale
        context first, and only a hit pays the drain (then re-proposes
        on the now-exact context). Zero-match rows keep full
        pipelining."""
        cand = None
        start = self._spec_rr % self.max_batch
        for i in (list(range(start, self.max_batch))
                  + list(range(start))):
            req = self._slots[i]
            if req is None or req.cancel_requested:
                continue
            if i in self._spec_pending or self._remaining[i] < 2:
                continue
            if self._chunk_state is not None and \
                    self._chunk_state[i] is not None:
                continue     # mid-prompt chunked admission: not a
                             # decode row yet
            prop = self._spec_proposer(i, req)
            ids = list(map(int, req.prompt_ids)) + \
                list(map(int, req.tokens))
            if prop.propose(ids, limit=int(self._remaining[i])):
                cand = i
                break
        if cand is None:
            return None
        # ISSUE 19 fault site: a ``raise`` between drafting and
        # dispatch drops the drafts on the floor — the pass runs as
        # plain decode, so outputs stay bit-identical (chaos_check
        # --spec proves it); a ``delay`` models a slow host proposer
        try:
            reliability.inject("llm.spec")
        except Exception:
            return None
        while self._inflight:
            self._drain_next()
        i = cand
        req = self._slots[i]
        if req is None or req.cancel_requested \
                or self._remaining[i] < 2 or i in self._spec_pending:
            return None       # the drain finished/cancelled the row
        prop = self._spec_proposer(i, req)
        ids = list(map(int, req.prompt_ids)) + \
            list(map(int, req.tokens))
        # the proposal's FIRST token is the proposer's guess at the
        # very next token — a position the compiled step fills with
        # the device-computed bonus token g0 instead (the host never
        # sees g0 before dispatch; see make_spec_step). The usable
        # drafts are the rest; emitted <= len(proposal) <= remaining.
        proposal = prop.propose(ids, limit=int(self._remaining[i]))
        drafts = proposal[1:]
        if not drafts:
            return None
        self._spec_rr = i + 1
        clen = len(drafts) + 1
        bucket = max(2, 1 << (clen - 1).bit_length())   # pow2 ladder
        pos0 = int(self._lens[i])
        end = pos0 + clen
        page = self._page
        p_have = -(-pos0 // page)
        return {"i": i, "req": req, "drafts": drafts, "clen": clen,
                "bucket": bucket, "pos0": pos0, "end": end,
                "p_have": p_have, "n_new": -(-end // page) - p_have,
                "match": prop.last_match}

    def _build_spec_step(self):
        """Compile the speculative verify step for ONE chunk bucket
        (the draft operand shape fixes it), composed here from the
        family's two programs: the decode leg is the sampled step
        VERBATIM, the verify leg the family ragged prefill VERBATIM
        (full logits) plus the fused accept — see
        ``kvcache.prefill.make_spec_step``. Row index, drafts,
        offsets and scatter targets are runtime data, so speculation
        adds O(k-buckets) programs total (guarded by the
        compile-recorder test in tests/test_spec_decode.py)."""
        cfg, page = self.cfg, self._page
        fam = make_spec_step(self._fam_paged_step,
                             self._fam_ragged_prefill)
        do_sample, top_k = self._do_sample, self.top_k

        def step(params, k_pages, v_pages, bt, lens, last, active,
                 temp, key, srow, ctoks, n_draft, cbt_row, cphys,
                 cslots):
            return fam(params, cfg, k_pages, v_pages, bt, lens, last,
                       active, temp, key, srow, ctoks, n_draft,
                       cbt_row, cphys, cslots, page=page,
                       do_sample=do_sample, top_k=top_k)

        return obs.compiled(step, name="llm/step_spec",
                            donate_argnums=(1, 2))

    def _dispatch_spec(self, disp, active, sargs: dict,
                       t_step: float) -> bool:
        """One speculative pass (the ISSUE 19 tentpole): every other
        active decode row advances one token while the chosen row's
        drafts run as a verify chunk — up to ``n_draft + 1`` tokens
        for that row through ONE fence. The drain applies the
        accepted prefix; rejected-tail K/V is rolled back by length
        bookkeeping alone (docs/KVCACHE.md)."""
        i, req = sargs["i"], sargs["req"]
        bucket, clen = sargs["bucket"], sargs["clen"]
        n_draft = clen - 1
        page = self._page
        bt_row = self._bt[i].copy()     # post-grant view: the pages
        # for [pos0, end) landed in the host table this pass
        pos = sargs["pos0"] + np.arange(bucket)
        phys = np.where(pos < sargs["end"],
                        bt_row[np.minimum(pos // page,
                                          self._pages_cap - 1)],
                        0).astype(np.int32)
        slots = (pos % page).astype(np.int32)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, 1:clen] = sargs["drafts"]   # slot 0 = g0, set on
        # device inside the compiled step
        ops = (jnp.asarray(i, jnp.int32), jnp.asarray(toks),
               jnp.asarray(n_draft, jnp.int32), jnp.asarray(bt_row),
               jnp.asarray(phys), jnp.asarray(slots))
        ck = self._step_cache_key() + ("spec", bucket,
                                       self._do_sample, self.top_k)
        pspec = _PAGED_STEP_CACHE.get(ck)
        if pspec is None:
            pspec = _PAGED_STEP_CACHE[ck] = self._build_spec_step()
        bt_in, lens_in = self._bt_dev, self._lens_dev
        last_in, key_in = self._last, self._sample_key
        out, logits, self._k_pages, self._v_pages, self._lens_dev, \
            self._sample_key = pspec(
                self.model.params, self._k_pages, self._v_pages,
                bt_in, lens_in, last_in, active, self._temp, key_in,
                *ops)
        self._last = logits
        for j in disp:
            self._lens[j] += 1
            self._remaining[j] -= 1
        # the spec row's host advance happens at DRAIN — the accepted
        # length is data on the device — so it sits out dispatch until
        # its record retires
        self._spec_pending.add(i)
        self.spec_proposed_total += n_draft
        self.spec_passes += 1
        ins = self._spec_instruments()
        if ins is not None:
            ins["proposed"].inc(n_draft)
            ins["passes"].inc()
        if flight.enabled:
            # same site as the proposed counter: the chaos harness
            # reconciles draft events == counter == proposed_total
            flight.record(
                "draft", request_id=req.id, trace_id=_trace_of(req),
                slot=i, n_draft=n_draft, match_len=sargs["match"],
                offset=sargs["pos0"])
        wall = time.perf_counter() - t_step
        obs.add_complete("llm/spec_step", time.time() - wall, wall,
                         decode_rows=len(disp), n_draft=n_draft,
                         slot=i)
        rec = {"out": out, "fn": "llm/step_spec",
               "pairs": [(j, self._slots[j]) for j in disp],
               "spec": {"i": i, "req": req, "n_draft": n_draft,
                        "bucket": bucket},
               "refs": (bt_in, lens_in, last_in, active, key_in)
               + ops,
               "pinned": self._pending_release}
        self._pending_release = []
        return self._after_dispatch(rec, t_step)

    def _build_paged_decode(self):
        """One pipelined decode step over the page pool — the family's
        ``paged_decode_step_sampled`` jitted with donated pools:
        consumes the previous step's logits, samples on device, writes
        K/V, advances the device-resident lengths for active rows and
        returns the sampled ids with a fence element appended."""
        cfg = self.cfg
        page = self._page
        fam_sampled = self._fam_sampled_step
        do_sample, top_k = self._do_sample, self.top_k

        def step(params, k_pages, v_pages, bt, lens, last, active, temp,
                 key):
            return fam_sampled(params, cfg, k_pages, v_pages, bt, lens,
                               last, active, temp, key, page=page,
                               do_sample=do_sample, top_k=top_k)

        return obs.compiled(step, name="llm/decode_paged",
                            donate_argnums=(1, 2))

    def _record_decode(self, applied: int, host_s: float, stall_s: float,
                       finished: int, cancelled: int = 0,
                       fn: Optional[str] = None):
        """Per-step attribution (ISSUE 4 satellite): the old single wall
        number silently included the sync barrier and overstated device
        cost; host scheduling and the device-fence stall are now
        separate series (their sum is the host wall this step cost —
        device compute overlapped by the pipeline shows up in neither).
        ``applied`` counts only DELIVERED tokens — speculative rows
        (finished requests) decoded but discarded don't inflate the
        token counter."""
        if fn is not None:
            # live roofline attribution (ISSUE 16): the drain-fence
            # wall of this dispatch, no new device syncs — gated on
            # the flight switch inside observe()
            utilization.observe(fn, host_s + stall_s)
        ins = self._instruments()
        if ins is None:
            return
        wall = host_s + stall_s
        ins["decode_tokens"].inc(applied)
        ins["decode_seconds"].observe(wall)
        ins["decode_host"].observe(host_s)
        ins["decode_stall"].observe(stall_s)
        # live occupancy, not the drained record's pair count: a record
        # may carry speculative pairs for requests finished by an
        # earlier drain, which would leave a phantom nonzero gauge on
        # an idle server
        ins["active"].set(sum(r is not None for r in self._slots))
        if finished:
            ins["requests"].labels(reason="done").inc(finished)
        if cancelled:
            # aborted/watchdog-failed slots reaped this drain — counted
            # HERE only (ISSUE 7): abort() itself does not increment,
            # else every hedge loser would land twice
            ins["requests"].labels(reason="cancelled").inc(cancelled)
        self._record_kv_gauges(ins)

    def _emit_decode_span(self, req: Request):
        """One ``llm/decode`` span covering a finished request's whole
        decode phase, stitched under its trace — decode steps are shared
        by every active slot, so the per-request attribution has to be
        emitted per request, not per step."""
        if not req.trace or not req.decode_started_at:
            return
        args = {"trace": req.trace["trace_id"], "stage": "llm_server",
                "request": req.id, "tokens": len(req.tokens)}
        if req.trace.get("parent_span"):
            args["parent_span"] = req.trace["parent_span"]
        obs.add_complete("llm/decode", req.decode_started_at,
                         time.time() - req.decode_started_at, **args)

    def _dispatchable(self) -> List[int]:
        """Slots a new step should decode for: occupied AND with
        dispatch budget left. A request gets at most ``max_new_tokens``
        dispatched steps — so speculative dispatches past a data-
        dependent EOS never allocate pages beyond the admission
        reserve, and a slot whose final step is in flight goes quiet.
        A slot whose spec verify is in flight (ISSUE 19) also sits
        out: its host length advance is data-dependent (the accepted
        prefix), so the engine cannot place its next token until the
        record drains."""
        return [i for i, r in enumerate(self._slots)
                if r is not None and self._remaining[i] > 0
                and i not in self._spec_pending]

    def _after_dispatch(self, rec: dict, t0: float) -> bool:
        """Shared dispatch epilogue: account host time, push the record
        onto the in-flight window — there the pass's ``llm/dispatch``
        phase ends, for every dispatch path alike — and drain down to
        the depth bound (depth 1 drains immediately — the synchronous
        engine); each drain is a phase pair of its own."""
        rec["host_s"] = time.perf_counter() - t0
        self.host_seconds += rec["host_s"]
        self.steps += 1
        self._inflight.append(rec)
        ins = self._instruments()
        if ins is not None:
            ins["inflight"].set(len(self._inflight))
        self._dispatch_ph.end(fn=rec["fn"], rows=len(rec["pairs"]),
                              **rec.get("host_stats", {}))
        while len(self._inflight) >= self.pipeline_depth:
            self._drain_next()
        return True

    def _drain_next(self):
        """Retire the oldest in-flight step: ONE device→host fetch of
        its (tokens ‖ fence) vector — the portable completion barrier —
        then EOS/max-token bookkeeping one step behind dispatch
        (mirroring the optimizer's ``_pending_loss`` drain). Slots whose
        request finished meanwhile discard their speculative token.
        Two phases of the pass: ``llm/fence_wait`` brackets the fetch
        and nothing else, ``llm/drain`` everything after it (with the
        token gaps it closed, those behind a prefill among them, and
        the time inside the freed slots' eager resets)."""
        rec = self._inflight.popleft()
        t0 = time.perf_counter()
        with self._phase("llm/fence_wait"):
            vals = np.asarray(rec["out"])
        # one clock read per drain, always: the tokens of this record
        # became host-visible at this fetch, so it is the arrival time
        # of every one of them (``Request.t_tokens``, the SLO stamps)
        now = time.perf_counter()
        stall = now - t0
        self.stall_seconds += stall
        with self._phase("llm/drain") as ph:
            gaps = self.token_gaps_total
            behind = self.token_gaps_behind_prefill_total
            self._eager.restart()
            ph.args["requests"], ph.args["finished"] = \
                self._retire(rec, vals, now, stall)
            ph.args.update(
                gaps=self.token_gaps_total - gaps,
                gaps_behind_prefill=self.token_gaps_behind_prefill_total
                - behind,
                eager_us=self._eager.microseconds())
            # the family's own counts of this step: the device's,
            # fetched with its tokens (kernels.sampling.make_sampled_
            # step), and the host's from its dispatch, both counted here
            # so that they always cover the same steps
            stats = dict(zip(rec.get("stats", ()),
                             vals[self.max_batch + 1:].tolist()))
            ph.args.update(stats)
            for name, n in (*stats.items(),
                            *rec.get("host_stats", {}).items()):
                self.step_counters[name] += n

    def _retire(self, rec: dict, vals, now: float, stall: float):
        """Everything a drain does once the record's values are on the
        host: drop the references the fence was guarding, apply the
        tokens, release finished slots, record the metrics. Returns the
        ids of the requests that got a token and how many finished."""
        # the fence proves every computation enqueued before this step —
        # including the updates rec["pinned"] was holding buffers for —
        # has retired; the references may drop now, and so may the page
        # refcounts held for finished requests' in-flight block tables
        rec["pinned"] = rec["refs"] = None
        for args in rec.pop("kv_release", ()):
            self._kv.release_slot(*args)
        finished = applied = cancelled = 0
        served: List[str] = []
        for i, req in rec["pairs"]:
            if self._slots[i] is not req:
                continue   # speculative token for a finished request
            if req.cancel_requested:
                # aborted mid-decode (hedge loser, watchdog, client
                # gone): release the slot and its pages now — the
                # drained token is discarded like any speculative one.
                # Not SLO-classified: an abort is the caller's choice,
                # not a latency verdict.
                self._finish_slot(i, req)
                cancelled += 1
                continue
            tok = int(vals[i])
            applied += 1
            served.append(req.id)
            if self._apply_token(i, req, tok, now):
                finished += 1
        sp = rec.get("spec")
        if sp is not None:
            i, req = sp["i"], sp["req"]
            self._spec_pending.discard(i)
            if self._slots[i] is not req:
                pass     # slot reassigned under us: nothing to apply
            elif req.cancel_requested:
                self._finish_slot(i, req)
                cancelled += 1
            else:
                # the accepted-length vector: [B decode ids][n_acc]
                # [bucket chunk toks][fence]. The host learns BOTH the
                # bonus token g0 (device-computed, never seen before)
                # and how many drafts survived from this one fetch.
                n_acc = int(vals[self.max_batch])
                self._lens[i] += n_acc       # device twin advanced in
                self._remaining[i] -= n_acc  # the compiled step
                st = self._spec_state[i] if self._spec_state else None
                if st is not None:
                    st["prop"].observe(sp["n_draft"], n_acc - 1)
                self.spec_accepted_total += n_acc - 1
                self.spec_emitted_total += n_acc
                ins_s = self._spec_instruments()
                if ins_s is not None:
                    ins_s["accepted"].inc(n_acc - 1)
                if flight.enabled:
                    kind = ("verify_accept"
                            if n_acc - 1 == sp["n_draft"]
                            else "verify_reject")
                    flight.record(
                        kind, request_id=req.id,
                        trace_id=_trace_of(req), slot=i,
                        n_draft=sp["n_draft"], accepted=n_acc - 1,
                        emitted=n_acc)
                base = self.max_batch + 1
                if n_acc:
                    served.append(req.id)
                for j in range(n_acc):
                    applied += 1
                    if self._apply_token(i, req,
                                         int(vals[base + j]), now):
                        finished += 1
                        break
        if (finished or cancelled) and self.pipeline_depth == 1:
            # strict synchrony at depth 1: the freed-row resets above
            # must resolve before their consumed buffers drop (exactly
            # the old engine's per-step barrier cadence)
            _sync_barrier(self._bt_dev, self._lens_dev)
            self._pending_release.clear()
        ins = self._instruments()
        if ins is not None:
            ins["inflight"].set(len(self._inflight))
        self._record_decode(applied, rec.get("host_s", 0.0), stall,
                            finished, cancelled, fn=rec.get("fn"))
        return served, finished

    def _apply_token(self, i: int, req: Request, tok: int,
                     now: float) -> bool:
        """Append one drained token to ``req`` with its fence stamp
        and the SLO/TTFT stamps, finishing the slot on EOS or budget
        exhaustion.
        Returns True when the request finished — the shared tail of
        the plain decode drain and the speculative accepted-prefix
        drain (ISSUE 19), which applies up to k+1 tokens per pass
        through this same path so EOS semantics cannot diverge."""
        req.tokens.append(tok)
        req.t_tokens.append(now)
        seq = self._prefill_seq
        if len(req.tokens) > 1:
            # a gap between two fence stamps; a prefill was dispatched
            # between them when the number moved
            self.token_gaps_total += 1
            if self._seq_at_token[i] != seq:
                self.token_gaps_behind_prefill_total += 1
        self._seq_at_token[i] = seq
        if len(req.tokens) == 1:
            req.t_first_token = time.perf_counter()  # TTFT stamp
            if self._slo is not None:
                self._slo.observe_ttft(now - req.t_submit)
                req.t_last_token = now
        elif self._slo is not None:
            gap = now - req.t_last_token
            req.t_last_token = now
            if gap > req.itl_max:
                req.itl_max = gap
            self._slo.observe_itl(gap)
        if (self.eos_token_id is not None
                and tok == self.eos_token_id) \
                or len(req.tokens) >= req.max_new_tokens:
            self._finish_slot(i, req)
            if self._slo is not None:
                self._slo.finish(
                    (req.t_first_token - req.t_submit
                     if req.t_first_token else None),
                    req.itl_max if req.itl_max >= 0 else None)
            return True
        return False

    def _finish_slot(self, i: int, req: Request):
        self._emit_decode_span(req)
        if flight.enabled:
            flight.record(
                "finish", request_id=req.id, trace_id=_trace_of(req),
                tokens=len(req.tokens),
                cancelled=req.cancel_requested or None,
                ttft_ms=(round((req.t_first_token - req.t_submit)
                               * 1000.0, 3)
                         if req.t_first_token else None))
        req.done.set()
        self._slots[i] = None
        self._remaining[i] = 0
        if self._spec_state is not None:
            self._spec_state[i] = None     # proposer state is per
            # request — the next occupant starts fresh
        adm = self._slot_adm[i]
        owned = self._slot_pages[i]
        adopted = adm.shared_pages if adm is not None else []
        charge = adm.charge if adm is not None else 0
        if self._kv.enabled:
            # keep the chain warm (ISSUE 5): index the full pages of
            # prompt+output plus the partial tail, THEN drop this
            # request's refs — indexed pages survive at refcount 1
            # (evictable), unindexed ones free immediately
            toks = list(map(int, req.prompt_ids)) + \
                list(map(int, req.tokens))
            self._kv.insert(toks,
                            self._bt[i, :-(-len(toks) // self._page)])
        self._slot_pages[i] = []
        self._slot_adm[i] = None
        if self._kv.enabled and self._inflight:
            # pinned pages hold refcounts (the PR 4 buffer-pinning
            # invariant extended): in-flight speculative steps still
            # read these pages through their device block tables, so
            # the decrefs run at the newest in-flight step's fence
            self._inflight[-1].setdefault("kv_release", []).append(
                (charge, owned, adopted))
        else:
            self._kv.release_slot(charge, owned, adopted)
        self._bt[i, :] = 0    # orphaned rows must point at trash:
        self._lens[i] = 0     # a stale id could alias a reissued
        # page and the inactive row's dummy write would clobber it
        with self._eager:
            self._pin(self._bt_dev, self._lens_dev)
            if self._every is not None:
                self._bt_dev = self._bt_dev.at[i].set(0)
            self._lens_dev = self._lens_dev.at[i].set(0)
            if self._rings:
                freed = self._freed_by_class
                name = self._classes[0].name
                freed[name] = freed.get(name, 0) + len(owned)
                for c, r in enumerate(self._rings):
                    freed[r.cls.name] = freed.get(r.cls.name, 0) \
                        + r.release(i)
                    self._put_ring_row(c, i)    # zeros: the trash page
        for s in self._states:
            # the row keeps what it holds until its next occupant's
            # prefill takes it as zero; an empty slot is never active,
            # so the sampled step sends it to the trash row
            s.release(i)

    # -- lossless preemption (ISSUE 17) --------------------------------------
    def _consider_preempt(self):
        """A higher-class request is waiting and the admission sweep
        could not seat it: evict the worst strictly-lower-class decode,
        losslessly. At most one preemption per in-flight window — the
        victim's pages only return at the newest fence, so a second
        victim before that drains could not seat the waiter either."""
        rec = self._preempt_rec
        if rec is not None and any(r is rec for r in self._inflight):
            return
        self._preempt_rec = None
        best = self._sched.best_rank()
        if best is None:
            return
        victim = None
        for i in range(self.max_batch):
            req = self._slots[i]
            if req is None or req.done.is_set() or req.cancel_requested:
                continue
            if self._chunk_state is not None and \
                    self._chunk_state[i] is not None:
                continue     # mid-prompt chunked admission: no usable
                             # chain yet, rollback (not preempt) owns it
            if self._remaining[i] <= 0:
                continue     # budget exhausted: finishing at the next
                             # drain anyway, eviction would save nothing
            if i in self._spec_pending:
                continue     # spec verify in flight: the row's length
                             # advance is data-dependent, park/export
                             # bookkeeping would race the drain
            rank = _PRIORITY_RANK[req.priority]
            if rank <= best:
                continue     # only a STRICTLY lower class is evicted
            key = (rank, -len(req.tokens), i)
            if victim is None or key > victim[0]:
                # worst class first; among equals the youngest decode
                # (fewest tokens to re-prefill at resume)
                victim = (key, i)
        if victim is not None:
            self._preempt_slot(victim[1])

    def _preempt_slot(self, i: int):
        """Losslessly evict the decode in slot ``i`` (ISSUE 17): park
        its KV chain (radix index + optional host-tier handoff blob),
        free the slot and pages at the in-flight fence exactly like
        ``_finish_slot``, and re-queue the request journal-style as
        ``prompt + generated_so_far`` with its remaining budget. Greedy
        decode over the extended prompt is deterministic, so the resume
        — with or without a surviving cached chain — continues
        bit-identical to the unpreempted run; the chain only decides
        how much prefill the resume pays, never what it generates."""
        reliability.inject("llm.preempt")
        req = self._slots[i]
        t0 = time.perf_counter()
        with obs.span("llm/preempt", slot=i, stage="llm_server",
                      request=req.id, victim_class=req.priority,
                      tokens_done=len(req.tokens)):
            adm = self._slot_adm[i]
            owned = self._slot_pages[i]
            adopted = adm.shared_pages if adm is not None else []
            charge = adm.charge if adm is not None else 0
            toks = list(map(int, req.prompt_ids)) + \
                list(map(int, req.tokens))
            mode = "dropped"
            if self._kv.enabled:
                # park index-only: the chain survives at refcount 1
                # (evictable) and the resume admission re-adopts it as
                # an ordinary radix hit. In-flight speculative writes
                # land PAST the indexed length — harmless, the same
                # argument _finish_slot relies on.
                self._kv.insert(toks,
                                self._bt[i, :-(-len(toks)
                                               // self._page)])
                mode = "indexed"
                if self._tier is not None:
                    # belt and braces: a handoff blob pins the chain
                    # against radix eviction under pool pressure, and
                    # the router journal can resume on ANOTHER worker
                    # by importing it (the PR 6 disaggregation path)
                    try:
                        self._parked[req.id] = \
                            self._export_chain_locked(toks)
                        mode = "exported"
                    except Exception:
                        pass   # export is an optimization, not a
                               # correctness dependency: resume
                               # re-prefills whatever is missing
            self._slots[i] = None
            self._remaining[i] = 0
            self._slot_pages[i] = []
            self._slot_adm[i] = None
            if self._kv.enabled and self._inflight:
                # the fence-deferred release walk, exactly as
                # _finish_slot: in-flight speculative steps still read
                # these pages through their device block tables
                self._inflight[-1].setdefault("kv_release", []).append(
                    (charge, owned, adopted))
            else:
                self._kv.release_slot(charge, owned, adopted)
            self._bt[i, :] = 0
            self._lens[i] = 0
            self._pin(self._bt_dev, self._lens_dev)
            self._bt_dev = self._bt_dev.at[i].set(0)
            self._lens_dev = self._lens_dev.at[i].set(0)
            # journal-style re-queue: resume = prompt + generated, with
            # the remaining budget; the hold record keeps the request
            # out of a slot until its old steps' fences drain (a
            # same-slot re-admission could absorb a stale speculative
            # token through the drain's identity check)
            req.resume_ids = np.asarray(toks, np.int32)
            req.preemptions += 1
            req._hold_rec = self._inflight[-1] if self._inflight \
                else None
            self._preempt_rec = req._hold_rec
            self.preemptions_total += 1
            self._sched.push(req)
        pri = self._priority_instruments_get()
        if pri is not None:
            pri["preemptions"].labels(**{"class": req.priority}).inc()
        if flight.enabled:
            # same site as the counter: the chaos harness reconciles
            # flight preempt events == counter == preemptions_total
            flight.record(
                "preempt", request_id=req.id, trace_id=_trace_of(req),
                slot=i, priority=req.priority, mode=mode,
                tokens_done=len(req.tokens),
                remaining=self._budget_of(req),
                wall_ms=round((time.perf_counter() - t0) * 1000.0, 3))

    def _step_paged(self) -> bool:
        ci = self._chunk_slot()
        disp = self._dispatchable()
        if not disp and ci is None:
            if self._inflight:   # nothing new to dispatch: keep draining
                self._drain_next()
                return True
            return False
        t_step = time.perf_counter()
        cargs = None
        if ci is not None:
            # unified dispatch (ISSUE 14): this pass carries one
            # prefill chunk — fused with the decode rows when any are
            # live, solo through the ragged-prefill program otherwise.
            # None = the chunk faulted (request already failed) or is
            # budget-stalled (decode continues; the chunk retries)
            with self._phase("llm/grant") as ph:
                self._eager.restart()
                cargs = self._prepare_chunk(ci)
                ph.args["pages"] = len(cargs["new_pages"]) if cargs else 0
                ph.args["eager_us"] = self._eager.microseconds()
        if cargs is None and not disp:
            if self._inflight:
                self._drain_next()
                return True
            return False
        if cargs is not None and not disp:
            with self._phase("llm/dispatch", fn="llm/prefill_ragged",
                             rows=0):
                self._dispatch_chunk_solo(cargs, t_step)
            return True
        sargs = None
        if cargs is None and ci is None and self._spec_active:
            # self-speculative pass (ISSUE 19): a pass carries EITHER
            # a prefill chunk OR one row's verify chunk (chunked
            # admissions keep priority — TTFT over throughput)
            sargs = self._prepare_spec()
            # _prepare_spec may drain the whole in-flight window, and
            # rows can finish or free at those fences: recompute the
            # decode set either way (minus the verify row — its
            # advance is the chunk's, not the decode leg's)
            si = sargs["i"] if sargs is not None else -1
            disp = [j for j in self._dispatchable() if j != si]
            if sargs is None and not disp:
                if self._inflight:
                    self._drain_next()
                return True
        with self._phase("llm/grant") as ph:
            self._eager.restart()
            ph.args["pages"] = self._grant_pages(disp, sargs, cargs)
            ph.args["eager_us"] = self._eager.microseconds()
            if self._states and self._every is not None:
                # pages granted beside seated slots: a request of such
                # a family holds both at once
                ph.args["state_slots"] = self.state_slots_in_use
            if self._rings:
                # by class: granted in this pass, freed since the last
                for name, n in self._grant_by_class.items():
                    ph.args["pages_" + name] = n
                for name in list(self._freed_by_class):
                    ph.args["freed_" + name] = \
                        self._freed_by_class.pop(name)
        with self._phase("llm/dispatch") as self._dispatch_ph:
            mask = np.zeros(self.max_batch, bool)
            mask[disp] = True
            active = jnp.asarray(mask)
            if sargs is not None:
                return self._dispatch_spec(disp, active, sargs, t_step)
            if cargs is not None:
                return self._dispatch_mixed(disp, active, cargs, t_step)
            return self._dispatch_decode(disp, active, t_step)

    def _grant_pages(self, disp, sargs: Optional[dict],
                     cargs: Optional[dict]) -> int:
        """The pass's page grant (its ``llm/grant`` phase): one page for
        every decode row at a page boundary, and a verify chunk's
        pages, into the host ledger and — one incremental scatter —
        the device-resident block table (timed: the phase's
        ``eager_us``). Returns the pages granted."""
        page = self._page
        # the page for position lens[i] must exist before the step; the
        # grant is an incremental scatter into the device-resident block
        # table, not a re-upload (ISSUE 4). Under the prefix cache the
        # free list may be held by warm chains — pre-evict for ALL the
        # grants this step needs BEFORE mutating any table, so an
        # injected kvcache.evict raise is cleanly retryable. With a
        # chunk prepared, a raise here must also restore the chunk's
        # alloc/charge, or the retried pass re-prepares on top of
        # orphaned pages.
        paged = disp if self._every is not None else ()
        try:
            boundary = sum(1 for i in paged
                           if int(self._lens[i]) % page == 0)
            need = boundary + (sargs["n_new"] if sargs is not None
                               else 0)
            if need:
                self._kv.ensure_free(need)
            allocs = []
            for i in paged:
                pos = int(self._lens[i])
                if pos % page == 0:
                    pid = self._kv.take_free()  # guaranteed by reserve
                    self._bt[i, pos // page] = pid
                    self._slot_pages[i].append(pid)
                    allocs.append((i, pos // page, pid))
            if sargs is not None:
                # verify-chunk pages (ISSUE 19): every page covering
                # [pos0, pos0 + clen) that the row does not own yet —
                # within the admission worst-case charge (clen <=
                # remaining), so no extra ledger traffic; a fully
                # rejected tail leaves them as the row's ordinary
                # decode pages for later positions
                si = sargs["i"]
                for j in range(sargs["n_new"]):
                    pid = self._kv.take_free()
                    col = sargs["p_have"] + j
                    self._bt[si, col] = pid
                    self._slot_pages[si].append(pid)
                    allocs.append((si, col, pid))
        except BaseException:
            if cargs is not None:
                self._restore_chunk_pass(cargs)
            raise
        if allocs:
            rows, cols, vals = (np.asarray(v, np.int32)
                                for v in zip(*allocs))
            with self._eager:
                vals_d = jnp.asarray(vals)
                self._pin(self._bt_dev, vals_d)
                self._bt_dev = self._bt_dev.at[rows, cols].set(vals_d)
        granted = len(allocs)
        for c, r in enumerate(self._rings):
            # a window class grants while a row's ring is filling and
            # never after: the position written this step must have
            # its page
            new = 0
            for i in disp:
                got = len(r.grant(i, int(self._lens[i]) + 1))
                if got:
                    with self._eager:
                        self._put_ring_row(c, i)
                    new += got
            self._grant_by_class[r.cls.name] = new
            granted += new
        if self._rings:
            self._grant_by_class[self._classes[0].name] = len(allocs)
        return granted

    def _dispatch_decode(self, disp, active, t_step: float) -> bool:
        """One plain decode pass over the page pool: every active row
        advances one token."""
        if self._mixed_active:
            # pure-decode pass on a unified server: the batch-mix
            # series still tell the whole story
            mins = self._mixed_instruments()
            if mins is not None:
                mins["pass_rows"].labels(kind="decode").inc(len(disp))
                mins["mix"].set(1.0)
        key = self._step_cache_key() + ("decode", self._do_sample,
                                        self.top_k)
        pdecode = _PAGED_STEP_CACHE.get(key)
        if pdecode is None:
            pdecode = _PAGED_STEP_CACHE[key] = self._build_paged_decode()
        bt_in, lens_in = self._tables(), self._lens_dev
        last_in, key_in = self._last, self._sample_key
        out, logits, self._k_pages, self._v_pages, self._lens_dev, \
            self._sample_key = pdecode(
                self.model.params, self._k_pages, self._v_pages, bt_in,
                lens_in, last_in, active, self._temp, key_in)
        self._last = logits
        for i in disp:
            self._lens[i] += 1
            self._remaining[i] -= 1
        rec = {"out": out, "fn": "llm/decode_paged",
               "pairs": [(i, self._slots[i]) for i in disp],
               "refs": (bt_in, lens_in, last_in, active, key_in),
               "pinned": self._pending_release,
               "stats": self._fam_step_stats}
        if self._fam_host_stats is not None:
            # lens were advanced above: the step attended one fewer
            rec["host_stats"] = self._fam_host_stats(
                self.cfg, self._lens[disp] - 1)
        if self._rings or self._states:
            # the allocator's own witness: the step's rows, the pages
            # they hold in a window class, the slots seated in a state
            # class (a slot a row: a slot never released reads above)
            hs = rec.setdefault("host_stats", {})
            hs["decode_rows_total"] = len(disp)
            for r in self._rings:
                hs[r.cls.name + "_pages_held_total"] = sum(
                    len(r.owned[i]) for i in disp)
            if self._states:
                hs["state_slots_held_total"] = self.state_slots_in_use
        self._pending_release = []
        return self._after_dispatch(rec, t_step)

    def _step(self):
        """Decode one token for every active slot."""
        reliability.inject("llm.step")
        # ISSUE 7 fault site: a ``delay`` rule here wedges the engine
        # thread inside its locked pass — exactly what a hung device
        # step looks like to the watchdog (a ``raise`` is just another
        # failing step for the resilient loop). Gated on live slots so
        # idle passes don't burn a seeded plan's bounded stall events
        # before any request is actually mid-step.
        if any(r is not None for r in self._slots):
            reliability.inject("worker.stall")
        return self._step_paged()

    def _fail_pass(self, exc: BaseException):
        """Fail what the raising pass was working on — the held
        admission head and every request in a slot (they share the
        step) — so ``get()`` raises the engine's error instead of
        timing out, and release their slots and pages."""
        msg = f"{type(exc).__name__}: {exc}"
        with self._lock:
            head = getattr(self, "_pending_head", None)
            if head is not None:
                self._pending_head = None
                if not head.done.is_set():
                    head.error = msg
                    head.done.set()
            for i, req in enumerate(self._slots):
                if req is None:
                    continue
                if self._chunk_state is not None and \
                        self._chunk_state[i] is not None:
                    self._rollback_chunk(i, msg)
                    continue
                if not req.done.is_set():
                    req.error = msg
                self._finish_slot(i, req)

    def _loop(self):
        backoff = reliability.RetryPolicy(max_attempts=1 << 30,
                                          base_delay=0.005, max_delay=0.5)
        delays = None
        prev_exc = None
        while not self._stop.is_set():
            self._hb = time.monotonic()   # watchdog heartbeat: stale =
            # wedged INSIDE this pass
            self._phases = phases = []
            try:
                with self._lock:
                    self._admit()
                    busy = self._step()
            except Exception as e:  # noqa: BLE001 — the engine thread
                # must survive a failing pass (injected or real): log
                # it, then either retry after a backoff (transient) or
                # fail the requests it was serving; the surviving queue
                # keeps being served either way
                self.pass_errors += 1
                fatal = _retry_cannot_cure(e, prev_exc)
                logger.exception(
                    "engine pass failed (%s)",
                    "failing its requests" if fatal else "retrying")
                prev_exc = e
                if fatal:
                    self._fail_pass(e)
                from bigdl_tpu.reliability.policies import _count
                _count("bigdl_reliability_retries_total",
                       "Retries performed under a RetryPolicy",
                       component="llm_server")
                if delays is None:
                    delays = backoff.delays()
                time.sleep(next(delays, 0.5))
                continue
            delays = prev_exc = None   # healthy pass resets the backoff
            if phases:
                self._record_pass(phases)
            if not busy:
                time.sleep(0.002)
        self._phases = None
