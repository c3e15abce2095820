"""Declared name registries (ISSUE 11 tentpole pass 3, source side).

The single source of truth for the repo's five string namespaces. An
entry here is a *declaration*: the name exists on purpose, means what
the description says, and (for conf keys and metric series) is
documented in the user-facing docs. The registry-drift pass
(:mod:`bigdl_tpu.analysis.registrydrift`) enforces both directions —
every literal in code resolves to an entry, and every entry is still
used by code — so a typo'd metric name or a deleted-but-still-registered
knob fails ``tools/check_static.py`` instead of shipping.

Mirrors: ``CONF_KEYS`` must cover ``bigdl_tpu.utils.conf._DEFAULTS``;
``FAULT_SITES`` must equal ``bigdl_tpu.reliability.faults.SITES``;
``PYTEST_MARKERS`` must equal the markers ``tests/conftest.py``
declares. The pass AST-parses those sources (never imports them) and
flags drift in either direction.

This module is import-light on purpose (no jax, no bigdl_tpu) so the
analyzer, the CLI gate and CI can load it anywhere.
"""

from __future__ import annotations

from typing import Dict, Tuple

#: ``bigdl.*`` configuration keys -> one-line meaning. Filled below.
CONF_KEYS: Dict[str, str] = {}

#: ``bigdl_*`` metric series -> one-line meaning. Filled below.
METRICS: Dict[str, str] = {}

#: metric names without the ``bigdl_`` prefix that are still ours
#: (Prometheus ecosystem conventions).
METRIC_EXTRA_NAMES: Tuple[str, ...] = ("process_start_time_seconds",)

#: trace span names (``category/what``) -> emitting subsystem.
SPAN_NAMES: Dict[str, str] = {}

#: fault-injection sites — must mirror ``reliability.faults.SITES``.
FAULT_SITES: Dict[str, str] = {}

#: pytest markers — must mirror ``tests/conftest.py``.
PYTEST_MARKERS: Dict[str, str] = {}

#: feature gates (ISSUE 13): conf keys whose FALSE value must make a
#: subsystem structurally absent. ``package`` names the gated code (a
#: directory or single module, repo-relative) — None for the pervasive
#: planes whose gating is runtime state rather than construction. The
#: gatecheck pass enforces default-off, no import-time side effects in
#: the package, gate-guarded construction from outside it, and the
#: existence of a disabled-mode test.
FEATURE_GATES: Dict[str, dict] = {}

#: HTTP endpoints served by the five hand-rolled surfaces. Keys may end
#: in ``*`` (prefix routes). ``gate`` names the feature gate that must
#: 404 the endpoint when off; ``gate404: "helper"`` marks routes whose
#: 404-when-off lives inside a shared helper (tracing.debug_endpoint).
HTTP_ENDPOINTS: Dict[str, dict] = {}
CONF_KEYS.update({
    "bigdl.analysis.lockwatch":
        "runtime lock-order witness for chaos runs; off = stock lock factories",
    "bigdl.checkpoint.keep":
        "retention; 0 = unlimited",
    "bigdl.coordinator.address":
        "jax.distributed coordinator host:port ('' = single-process)",
    "bigdl.elastic.enabled":
        "elastic training master switch; false = structurally absent",
    "bigdl.elastic.generation":
        "set by the launcher env",
    "bigdl.elastic.heartbeat.interval":
        "agent beat cadence (s)",
    "bigdl.elastic.heartbeat.timeout":
        "peer presumed dead (s)",
    "bigdl.elastic.join.timeout":
        "join deadline: fail the generation if the world never fully joins",
    "bigdl.elastic.max.restarts":
        "restart budget (both tiers)",
    "bigdl.elastic.snapshot.every":
        "steps per RAM snapshot",
    "bigdl.elastic.snapshot.flush.every":
        "commit-floor advances per durable checkpoint flush on process 0",
    "bigdl.elastic.snapshot.ring":
        "RAM ring capacity",
    "bigdl.elastic.step.timeout":
        "collective-hang watchdog step timeout (seconds); 0 = off",
    "bigdl.elastic.supervisor.address":
        "host:port; '' = ring-only",
    "bigdl.engine.type":
        "'' = auto (jax.default_backend)",
    "bigdl.llm.api.chat_template":
        "chat-template family for /v1/chat/completions: plain | llama | chatglm",
    "bigdl.llm.api.enabled":
        "OpenAI-compatible /v1/* gateway with SSE streaming; false = routes 404, structurally absent",
    "bigdl.llm.api.tokenizer":
        "gateway tokenizer: '' = token-id prompts only, 'byte' = deterministic utf-8 byte tokenizer",
    "bigdl.llm.failover.enabled":
        "router journals in-flight requests and resumes on another backend",
    "bigdl.llm.failover.max.attempts":
        "dispatch tries/request",
    "bigdl.llm.hedge.budget":
        "hedges / requests cap",
    "bigdl.llm.hedge.delay.ms":
        "0 = p95-based (observed)",
    "bigdl.llm.hedge.enabled":
        "duplicate a slow call to a second backend; first success wins",
    "bigdl.llm.hedge.min.delay.ms":
        "floor under the p95 rule",
    "bigdl.llm.fleet.enabled":
        "elastic serving fleet: autoscaler + graceful drain with KV handoff; false = absent",
    "bigdl.llm.fleet.min":
        "autoscaler floor on decode-pool size",
    "bigdl.llm.fleet.max":
        "autoscaler ceiling on decode-pool size",
    "bigdl.llm.fleet.interval":
        "autoscaler control-loop tick (seconds)",
    "bigdl.llm.fleet.cooldown":
        "seconds after any scale action before the next (flap damping)",
    "bigdl.llm.fleet.sustain":
        "consecutive pressured/idle ticks before the autoscaler acts",
    "bigdl.llm.fleet.queue.high":
        "per-worker queue depth above which the pool is under pressure",
    "bigdl.llm.fleet.idle.low":
        "total queued+active work at or below which the pool is idle",
    "bigdl.llm.fleet.drain.timeout":
        "seconds a graceful drain may take before it is abandoned",
    "bigdl.llm.fleet.pressure.interactive":
        "autoscaler also treats interactive-class backlog alone as pressure",
    "bigdl.llm.kvcache.enabled":
        "radix-indexed KV page reuse with refcounts + COW; false = off",
    "bigdl.llm.kvtier.enabled":
        "host-RAM spill tier behind the radix pool; false = absent",
    "bigdl.llm.kvtier.fetch.timeout":
        "stuck fetch -> plain miss",
    "bigdl.llm.kvtier.host_pages":
        "0 = auto (4x device pool)",
    "bigdl.llm.kvtier.sync":
        "inline migration (tests)",
    "bigdl.llm.pipeline_depth":
        "decode steps dispatched ahead of the host drain; 1 = synchronous",
    "bigdl.llm.mixed.enabled":
        "unified mixed prefill+decode dispatch: one compiled step serves decode rows + one prefill chunk",
    "bigdl.llm.prefill.chunk.wait":
        "seconds a budget-starved chunked admission waits before shedding with a clean rollback",
    "bigdl.llm.prefill.chunk_tokens":
        "page-aligned prefill chunk size for the unified dispatch; 0 = auto (4 pages)",
    "bigdl.llm.priority.enabled":
        "SLO-class priority scheduling with lossless preemption; false = FIFO, structurally absent",
    "bigdl.llm.prober.interval":
        "/healthz poll (seconds)",
    "bigdl.llm.retry_after.base":
        "derived Retry-After base seconds (clamped with per_queued/max)",
    "bigdl.llm.retry_after.jitter":
        "Retry-After random stretch fraction",
    "bigdl.llm.retry_after.max":
        "Retry-After clamp ceiling (seconds)",
    "bigdl.llm.retry_after.per_queued":
        "Retry-After seconds added per queued request",
    "bigdl.llm.role":
        "worker role: '' unified, 'prefill' or 'decode' side of the KV handoff",
    "bigdl.llm.spec.enabled":
        "model-free self-speculative decoding (n-gram drafts + fused verify); false = structurally absent",
    "bigdl.llm.spec.k":
        "speculative draft-token ceiling per engine tick",
    "bigdl.llm.spec.min_match":
        "shortest suffix n-gram the proposer trusts for a draft",
    "bigdl.llm.spec.backoff":
        "acceptance-rate EMA floor below which the live draft length halves",
    "bigdl.llm.watchdog.step_timeout":
        "engine watchdog: a stalled step flips /healthz and fails retriably; 0 = off",
    "bigdl.device.peak.gbps":
        "peak HBM GB/s for the roofline gauges; 0 = auto from device_kind",
    "bigdl.device.peak.tflops":
        "peak dense bf16 TFLOP/s for the roofline gauges; 0 = auto",
    "bigdl.mesh.axes":
        "comma-separated axis names",
    "bigdl.mesh.shape":
        "comma-separated ints; '' = auto",
    "bigdl.num.processes":
        "multi-process world size ('' = single process)",
    "bigdl.observability.alerts.rules":
        "JSON rule list replacing the built-in burn-rate alert set",
    "bigdl.observability.enabled":
        "metrics + trace spans",
    "bigdl.observability.exemplars":
        "slowest-N latency traces",
    "bigdl.observability.federation":
        "fleet collector + /metrics/snapshot + /fleet/status; false = absent",
    "bigdl.observability.federation.interval":
        "member scrape cadence (seconds)",
    "bigdl.observability.flight.capacity":
        "flight-recorder ring entries (oldest decision events dropped)",
    "bigdl.observability.flight.enabled":
        "flight recorder + explain endpoints + roofline gauges; false = absent",
    "bigdl.observability.sketch.alpha":
        "quantile-sketch relative-error bound (merge requires equal alpha)",
    "bigdl.observability.timeseries.enabled":
        "windowed metric store + alert engine + timeline endpoints; "
        "false = absent",
    "bigdl.observability.timeseries.interval":
        "registry-snapshot sampling cadence (seconds)",
    "bigdl.observability.timeseries.retention":
        "ring horizon (seconds); older samples evicted",
    "bigdl.observability.timeseries.slo.window":
        "window (seconds) backing the store-fed SLO burn gauges",
    "bigdl.observability.trace.capacity":
        "span ring entries",
    "bigdl.optimizer.max.retry":
        "iteration-retry attempts",
    "bigdl.process.id":
        "this process's rank in the multi-process world",
    "bigdl.reliability.enabled":
        "fault sites + policies",
    "bigdl.reliability.retry.base.delay":
        "retry backoff base delay (seconds)",
    "bigdl.reliability.retry.max.attempts":
        "tries, not retries",
    "bigdl.reliability.retry.max.delay":
        "backoff cap",
    "bigdl.slo.enabled":
        "per-request TTFT/ITL SLO accounting; false = no sketch/slo series",
    "bigdl.slo.itl_ms":
        "inter-token-latency objective: worst gap per request",
    "bigdl.slo.objective":
        "availability objective; alert burn = violation_ratio / "
        "(1 - objective)",
    "bigdl.slo.ttft_ms":
        "time-to-first-token objective (admission to first token)",
    "bigdl.slo.window":
        "rolling burn-rate window (requests)",
    "bigdl.train.prefetch":
        "stage batch N+1 during N",
    "bigdl.train.prefetch.depth":
        "staged batches held ahead",
})

METRICS.update({
    "bigdl_alerts_firing":
        "Alert rules currently in the firing state",
    "bigdl_alerts_recorded":
        "Recording-rule outputs, one series per rule",
    "bigdl_alerts_transitions_total":
        "Alert state-machine transitions by rule and new state",
    "bigdl_api_requests_total":
        "OpenAI gateway requests by route and outcome "
        "(ok/shed/invalid/error/disconnect)",
    "bigdl_build_info":
        "Constant 1; the build identity lives in the labels",
    "bigdl_cluster_serving_batch_size":
        "Records packed per inference batch",
    "bigdl_cluster_serving_batches_total":
        "Inference batches executed",
    "bigdl_cluster_serving_infer_seconds":
        "Wall time of one InferenceModel.predict call",
    "bigdl_cluster_serving_records_total":
        "Records answered by the ClusterServing batch loop",
    "bigdl_collective_calls_total":
        "Collective call sites traced",
    "bigdl_collective_traced_bytes_total":
        "Input payload bytes per compiled collective call site (trace-time accounting: multiply by executions, and by the op's wire amplification — e.g. ~(n-1) recv copies for all_gather, ~2(n-1)/n for ring all_reduce — for actual traffic)",
    "bigdl_device_bw_util":
        "Achieved HBM bandwidth as a fraction of the platform peak — the live decode-is-bandwidth-bound alarm",
    "bigdl_device_hbm_bw_gbps":
        "Achieved HBM traffic (cost-analysis bytes accessed per wall second) over the recent sampled-dispatch window",
    "bigdl_device_mfu":
        "Achieved flops / peak dense bf16 flops over the recent sampled-dispatch window",
    "bigdl_elastic_committed_step":
        "Newest snapshot step every live peer has taken",
    "bigdl_elastic_flushes_total":
        "Committed snapshots flushed to the durable tier",
    "bigdl_elastic_generation":
        "Worker-set generation (restarts of the world)",
    "bigdl_elastic_heartbeat_failures_total":
        "Heartbeats that failed to reach the supervisor",
    "bigdl_elastic_heartbeats_total":
        "Agent heartbeats delivered to the supervisor",
    "bigdl_elastic_restarts_total":
        "Elastic restarts performed",
    "bigdl_elastic_snapshot_age_steps":
        "Iterations since the last RAM snapshot was taken",
    "bigdl_elastic_snapshots_total":
        "RAM snapshots taken into the elastic ring",
    "bigdl_elastic_stalls_total":
        "Wedged optimizer steps detected by the collective-hang watchdog",
    "bigdl_elastic_step_skew":
        "Max-min optimizer step across live peers (straggler gauge)",
    "bigdl_elastic_world_size":
        "Live (heartbeating) training processes this generation",
    "bigdl_engine_init_failures_total":
        "jax.distributed.initialize failures during Engine.init",
    "bigdl_federation_members":
        "Members the fleet collector is scraping",
    "bigdl_federation_scrapes_total":
        "Member snapshot scrapes by outcome",
    "bigdl_federation_stale_instances":
        "Members whose last /metrics/snapshot scrape failed (serving last-known state)",
    "bigdl_fleet_chains_migrated_total":
        "Warm KV chains migrated to survivors during drains",
    "bigdl_fleet_drains_total":
        "Graceful worker drains by outcome",
    "bigdl_fleet_scale_events_total":
        "Autoscaler pool changes by direction",
    "bigdl_fleet_workers":
        "Decode-pool size the autoscaler currently maintains",
    "bigdl_flight_events_total":
        "Flight-recorder decision events by kind",
    "bigdl_kvcache_evictions_total":
        "Pages evicted from the prefix index under pool pressure",
    "bigdl_kvcache_hits_total":
        "Admissions that reused a cached prefix",
    "bigdl_kvcache_indexed_pages":
        "Pages currently referenced by the prefix index",
    "bigdl_kvcache_misses_total":
        "Admissions with no cached prefix",
    "bigdl_kvcache_pool_occupancy":
        "Fraction of the usable page pool allocated (live + indexed)",
    "bigdl_kvcache_prefix_tokens_reused_total":
        "Prompt tokens served from cached prefixes instead of prefill",
    "bigdl_kvcache_shared_pages":
        "Pages with more than one reference (index + live requests)",
    "bigdl_kvtier_fetch_failures_total":
        "Host-tier fetches that degraded to a cache miss",
    "bigdl_kvtier_fetches_total":
        "Pages fetched from the host arena back into HBM",
    "bigdl_kvtier_handoff_bytes_total":
        "Serialized KV bytes moved by handoffs",
    "bigdl_kvtier_handoffs_total":
        "KV-chain handoffs across the prefill/decode split",
    "bigdl_kvtier_host_pages":
        "Host arena capacity in page slots",
    "bigdl_kvtier_host_pages_used":
        "Host arena slots currently holding a page",
    "bigdl_kvtier_inflight_migrations":
        "Migration jobs queued or running",
    "bigdl_kvtier_spills_total":
        "Pages spilled from HBM to the host arena",
    "bigdl_llm_active_slots":
        "Slots currently decoding",
    "bigdl_llm_decode_host_seconds":
        "Host-side scheduling slice of one decode step (page allocation + dispatch; no device wait)",
    "bigdl_llm_decode_stall_seconds":
        "Host time blocked on the device fence when draining a decode step (the pipeline's residual stall)",
    "bigdl_llm_decode_step_seconds":
        "Host wall attributed to one decode step: scheduling + fence stall (under pipelining device compute overlaps the host, so this is NOT pure device time — see the host/stall split below and docs/PERFORMANCE.md)",
    "bigdl_llm_decode_tokens_total":
        "Tokens decoded across all slots",
    "bigdl_llm_itl_seconds":
        "Engine gap between consecutive drained tokens of one request, mergeable quantile sketch",
    "bigdl_llm_kv_class_pages_in_use":
        "Physical KV pages owned by live requests, by page class (families that cache in several)",
    "bigdl_llm_kv_pages_in_use":
        "Physical KV pages owned by live requests",
    "bigdl_llm_kv_pool_occupancy":
        "Fraction of the KV page pool in use (0..1)",
    "bigdl_llm_pass_mix":
        "Decode-row fraction of the last unified engine pass (1.0 = pure decode, 0.0 = chunk-only)",
    "bigdl_llm_pass_rows_total":
        "Rows served by unified engine passes, by kind (decode | prefill_chunk)",
    "bigdl_llm_queue_depth":
        "Requests accepted and waiting for an engine slot (the fleet autoscaler's primary pressure signal)",
    "bigdl_llm_pipeline_inflight":
        "Decode steps dispatched but not yet drained (bounded by bigdl.llm.pipeline_depth)",
    "bigdl_llm_prefill_chunks_total":
        "Prefill chunks dispatched by the unified mixed engine",
    "bigdl_llm_prefill_seconds":
        "Host wall of one request prefill (compile excluded after first hit per length bucket). At pipeline_depth 1 this covers execution (the prefill barriers); at depth > 1 it is DISPATCH time — execution overlaps decode by design",
    "bigdl_llm_prefill_tokens_total":
        "Prompt tokens prefilled into the KV cache",
    "bigdl_llm_preempt_parked":
        "Preempted requests whose exported KV chain is parked awaiting resume",
    "bigdl_llm_preemptions_total":
        "In-flight decodes losslessly preempted for a higher class, by victim class",
    "bigdl_llm_queue_depth_class":
        "Requests waiting for an engine slot, by SLO class (priority scheduler only)",
    "bigdl_llm_requests_total":
        "Requests finished by the engine",
    "bigdl_llm_spec_accepted_tokens_total":
        "Draft tokens accepted by the speculative verify pass",
    "bigdl_llm_spec_passes_total":
        "Engine passes that carried a speculative verify chunk",
    "bigdl_llm_spec_proposed_tokens_total":
        "Draft tokens dispatched to speculative verify",
    "bigdl_llm_state_slots_in_use":
        "Engine slots seated in a state class (families whose cache is a fixed state a slot)",
    "bigdl_llm_ttft_seconds":
        "Engine time to first token (submit to first drained token), mergeable quantile sketch",
    "bigdl_llm_watchdog_trips_total":
        "Engine stalls detected by the step-deadline watchdog",
    "bigdl_lockwatch_inversions_total":
        "Lock-order inversions observed by the bigdl.analysis.lockwatch witness",
    "bigdl_reliability_breaker_transitions_total":
        "CircuitBreaker state transitions",
    "bigdl_reliability_checkpoints_quarantined_total":
        "Corrupt/incomplete checkpoints moved aside during recovery scans",
    "bigdl_reliability_deadline_expired_total":
        "Deadlines that ran out before the work completed",
    "bigdl_reliability_injected_faults_total":
        "Faults fired by the armed FaultPlan",
    "bigdl_reliability_preemptions_total":
        "SIGTERM/SIGINT preemptions that checkpointed and exited",
    "bigdl_reliability_retries_total":
        "Retries performed under a RetryPolicy",
    "bigdl_reliability_shed_total":
        "Requests rejected by admission control",
    "bigdl_router_backend_healthy":
        "Prober verdict per backend (1 healthy)",
    "bigdl_router_breaker_state":
        "Per-backend circuit-breaker state (0=closed, 1=half_open, 2=open)",
    "bigdl_router_failovers_total":
        "Requests re-dispatched to another backend after a failure",
    "bigdl_router_hedges_total":
        "Hedged backend calls by outcome",
    "bigdl_router_itl_seconds":
        "Client-visible gap between streamed tokens at the router (resumed/hedged tokens stamped once), mergeable quantile sketch",
    "bigdl_router_journal_inflight":
        "Routed requests currently in the failover journal",
    "bigdl_router_ttft_seconds":
        "Client-visible time to first streamed token at the router, mergeable quantile sketch",
    "bigdl_serving_errors_total":
        "Predict requests failing (bad request or timeout)",
    "bigdl_serving_queue_depth":
        "Requests submitted and still awaiting a result",
    "bigdl_serving_request_seconds":
        "End-to-end /predict latency (submit to result)",
    "bigdl_serving_requests_total":
        "HTTP requests by endpoint outcome",
    "bigdl_serving_served_total":
        "Predict requests answered with a result",
    "bigdl_slo_burn_rate":
        "Fraction of the last bigdl.slo.window requests violating the SLO",
    "bigdl_slo_requests_total":
        "Finished requests classified against the bigdl.slo.* thresholds",
    "bigdl_summary_scalar":
        "Last value of each Train/ValidationSummary scalar tag",
    "bigdl_timeseries_sample_overhead_us":
        "Host microseconds the last time-series sample cost",
    "bigdl_timeseries_samples_total":
        "Registry snapshots taken into the time-series ring",
    "bigdl_train_compute_seconds_total":
        "Cumulative host time spent dispatching the compiled step",
    "bigdl_train_data_wait_seconds_total":
        "Cumulative host time spent staging input batches",
    "bigdl_train_examples_total":
        "Training examples consumed",
    "bigdl_train_grad_norm":
        "Global gradient L2 norm at the last drained step",
    "bigdl_train_learning_rate":
        "Learning rate at the last drained step",
    "bigdl_train_loss":
        "Last drained train loss",
    "bigdl_train_step_seconds":
        "Wall time of one optimizer iteration (data wait + step dispatch; the loop is pipelined, so this bounds dispatch, not device occupancy)",
    "bigdl_train_steps_total":
        "Optimizer steps taken",
    "bigdl_train_throughput_examples_per_sec":
        "Throughput of the last completed epoch",
    "bigdl_xla_bytes_accessed_per_call":
        "cost_analysis() bytes accessed (HBM traffic) per call",
    "bigdl_xla_compile_seconds":
        "Wall time of one XLA compilation",
    "bigdl_xla_compiles_total":
        "XLA compilations per wrapped jit entry point",
    "bigdl_xla_flops_per_call":
        "cost_analysis() FLOPs of one call of the latest executable",
    "bigdl_xla_live_buffer_bytes":
        "Total bytes of live jax arrays, sampled at compile time",
    "bigdl_xla_peak_hbm_bytes":
        "memory_analysis() argument+output+temp-alias bytes of the latest executable (its device-memory high-water mark)",
    "bigdl_xla_recompiles_total":
        "Compilations beyond the first signature of a function — the silent-perf-killer alarm (triggering signature logged)",
    "process_start_time_seconds":
        "Unix epoch seconds this process started",
})

SPAN_NAMES.update({
    "api/request":
        "one OpenAI gateway request, translation through final chunk",
    "elastic/flush":
        "durable snapshot flush (elastic training, process 0)",
    "federation/scrape":
        "completion: one fleet-collector sweep over the members",
    "fleet/scale":
        "completion: one autoscaler scale action (out or in)",
    "worker/drain":
        "completion: one graceful worker drain (finish + migrate)",
    "elastic/restart":
        "completion: a generation restart round-trip",
    "elastic/rollback":
        "completion: in-process ring rollback",
    "elastic/snapshot":
        "RAM snapshot capture in the elastic step hooks",
    "kvcache/lookup":
        "radix prefix-index lookup at admission",
    "kvtier/fetch_wait":
        "engine-side wait on a parked host-tier fetch",
    "kvtier/migrate":
        "completion: one HBM<->host migration job",
    "llm/decode":
        "per-request decode phase on the engine (PR 3)",
    "llm/admit":
        "engine pass phase: the admission sweep (prefills nest in it)",
    "llm/dispatch":
        "engine pass phase: mask build + the step program's jit call",
    "llm/drain":
        "engine pass phase: token bookkeeping after the fence",
    "llm/fence_wait":
        "engine pass phase: the device->host fetch of a step's tokens",
    "llm/grant":
        "engine pass phase: page ledger + block-table scatter",
    "llm/handoff_export":
        "KV chain serialized for disaggregated handoff",
    "llm/handoff_import":
        "KV handoff blob landed into pool/arena",
    "llm/mixed_step":
        "one unified mixed prefill+decode pass (decode rows + a chunk)",
    "llm/pass":
        "completion: one engine loop iteration, first phase to last",
    "llm/preempt":
        "completion: one lossless preemption of an in-flight decode",
    "llm/prefill":
        "prompt prefill (the uncached suffix, ragged in place) on the engine",
    "llm/prefill_dispatch":
        "a whole-prompt prefill's jit call and nothing else",
    "llm/prefill_finish":
        "a prefill's epilogue: eager table updates queued behind it",
    "llm/prefill_stage":
        "a whole-prompt prefill's host staging, entry to the jit call",
    "llm/queue_wait":
        "request time between submit and slot admission",
    "llm/request":
        "LLMWorker HTTP request envelope",
    "llm/route":
        "LLMRouter dispatch envelope (prefill+decode legs)",
    "llm/spec_step":
        "completion: one speculative pass (decode rows + a verify chunk)",
    "llm/watchdog_trip":
        "completion: engine watchdog declared a stall",
    "py/gc":
        "completion: one garbage collection of 0.5 ms or more",
    "router/failover":
        "completion: one journal resume onto a new backend",
    "router/hedge":
        "hedged duplicate dispatch (first success wins)",
    "serving/batch":
        "ClusterServing batch execution",
    "serving/predict":
        "ServingFrontend HTTP /predict envelope",
    "train/epoch":
        "BaseOptimizer epoch bracket",
    "train/step":
        "BaseOptimizer training step bracket",
    "xla/compile":
        "completion: one XLA compile (flight recorder)",
})

FAULT_SITES.update({
    "checkpoint.commit":
        "before the atomic rename",
    "checkpoint.load":
        "load_checkpoint entry",
    "checkpoint.write":
        "save_checkpoint entry",
    "checkpoint.write.arrays":
        "after arrays land (corrupt-capable)",
    "checkpoint.write.manifest":
        "between arrays and manifest writes",
    "elastic.heartbeat":
        "agent->supervisor beat (ISSUE 10)",
    "elastic.step":
        "elastic-guarded train step (ISSUE 10)",
    "federation.scrape":
        "fleet collector member scrape (ISSUE 12)",
    "fleet.scale":
        "autoscaler scale action (ISSUE 15)",
    "worker.drain":
        "per-chain drain migration (ISSUE 15)",
    "kvcache.evict":
        "prefix-cache LRU eviction (ISSUE 5)",
    "kvtier.fetch":
        "host->HBM page fetch (ISSUE 6)",
    "kvtier.spill":
        "HBM->host page spill (ISSUE 6)",
    "llm.chunk":
        "between chunks of one chunked admission (ISSUE 14)",
    "llm.preempt":
        "before a victim's KV chain is exported (ISSUE 17)",
    "llm.spec":
        "between drafting and the verify dispatch (ISSUE 19)",
    "llm.step":
        "LLM engine decode step",
    "llm.submit":
        "LLMServer request admission",
    "optimizer.checkpoint":
        "before the optimizer persists state",
    "optimizer.step":
        "top of each training iteration",
    "router.dispatch":
        "router->backend call/stream (ISSUE 7)",
    "serving.backend.pop":
        "queue backend read",
    "serving.backend.push":
        "queue backend write",
    "serving.batch":
        "cluster-serving batch execution",
    "serving.frontend.request":
        "HTTP /predict admission",
    "worker.stall":
        "hung engine decode step (ISSUE 7)",
})

FEATURE_GATES.update({
    "bigdl.analysis.lockwatch": {
        "package": "bigdl_tpu/analysis/lockwatch.py",
        "desc": "runtime lock-order witness; off = stock lock factories"},
    "bigdl.elastic.enabled": {
        "package": "bigdl_tpu/elastic",
        "desc": "elastic training: supervisor/agent/snapshot ring"},
    "bigdl.llm.api.enabled": {
        "package": "bigdl_tpu/llm/api",
        "desc": "OpenAI-compatible /v1/* gateway + SSE relay from the "
                "failover journal drain; off = routes 404 naming the "
                "gate, no bigdl_api_* series"},
    "bigdl.llm.failover.enabled": {
        "package": "bigdl_tpu/llm/failover.py",
        "desc": "router journal + prober + resume machinery"},
    "bigdl.llm.hedge.enabled": {
        "package": "bigdl_tpu/llm/failover.py",
        "desc": "hedged dispatch (shares the failover module)"},
    "bigdl.llm.fleet.enabled": {
        "package": "bigdl_tpu/llm/fleet.py",
        "desc": "elastic serving fleet: autoscaler + graceful drain "
                "with KV handoff"},
    "bigdl.llm.kvcache.enabled": {
        "package": "bigdl_tpu/llm/kvcache",
        "desc": "radix prefix index + refcounted page pool"},
    "bigdl.llm.kvtier.enabled": {
        "package": "bigdl_tpu/llm/kvtier",
        "desc": "host-RAM arena + async migration + handoff"},
    "bigdl.llm.mixed.enabled": {
        "package": None,            # lives inside the engine hot path:
        "desc": "unified mixed prefill+decode dispatch with chunked "
                "admission; off = the split engine exactly"},
    "bigdl.llm.priority.enabled": {
        "package": None,            # lives inside the engine hot path:
        "desc": "SLO-class scheduler + lossless preemption of in-flight "
                "decodes; off = FIFO, structurally absent"},
    "bigdl.llm.prefill.chunk_tokens": {
        "package": None,            # tuning knob of the mixed gate
        "desc": "chunk size for the unified dispatch (0 = 4 pages); "
                "read only when bigdl.llm.mixed.enabled"},
    "bigdl.llm.spec.enabled": {
        "package": "bigdl_tpu/llm/spec.py",
        "desc": "model-free self-speculative decoding (n-gram drafts "
                "+ fused verify); off = no proposer state, no "
                "bigdl_llm_spec_* series"},
    "bigdl.observability.enabled": {
        "package": None,            # pervasive: runtime-gated via _state
        "desc": "metrics + spans; no-op instruments when off"},
    "bigdl.observability.federation": {
        "package": "bigdl_tpu/observability/federation.py",
        "desc": "fleet collector + snapshot endpoints"},
    "bigdl.observability.flight.enabled": {
        "package": "bigdl_tpu/observability/flight.py",
        "desc": "decision-event ring + explain endpoints + live "
                "roofline gauges (utilization.py shares the gate)"},
    "bigdl.observability.timeseries.enabled": {
        "package": "bigdl_tpu/observability/timeseries.py",
        "desc": "windowed metric store + query/timeline endpoints "
                "(alerts.py shares the gate: the engine is only ever "
                "built by timeseries.acquire())"},
    "bigdl.reliability.enabled": {
        "package": None,            # pervasive: runtime-gated via _state
        "desc": "fault sites + retry/deadline/breaker policies"},
    "bigdl.slo.enabled": {
        "package": "bigdl_tpu/observability/slo.py",
        "desc": "per-request TTFT/ITL accounting"},
})

HTTP_ENDPOINTS.update({
    "/v1/chat/completions": {
        "methods": ("POST",), "gate": "bigdl.llm.api.enabled",
        "desc": "OpenAI chat completions (templated), blocking or SSE"},
    "/v1/completions": {
        "methods": ("POST",), "gate": "bigdl.llm.api.enabled",
        "desc": "OpenAI text completions, blocking or SSE stream"},
    "/v1/models": {
        "methods": ("GET",), "gate": "bigdl.llm.api.enabled",
        "desc": "OpenAI model list (the one served model)"},
    "/alerts": {
        "methods": ("GET",),
        "gate": "bigdl.observability.timeseries.enabled",
        "gate404": "helper",
        "desc": "alert rule table + firing set (worker/router/elastic "
                "supervisor)"},
    "/backends": {
        "methods": ("POST",), "gate": "bigdl.llm.failover.enabled",
        "desc": "live router pool membership (add/remove backends)"},
    "/debug/kvcache": {
        "methods": ("GET",), "gate": "bigdl.llm.kvcache.enabled",
        "desc": "prefix-cache pool/radix/tier state"},
    "/debug/explain/*": {
        "methods": ("GET",),
        "gate": "bigdl.observability.flight.enabled",
        "gate404": "helper",
        "desc": "causal decision timeline + verdict for one request id"},
    "/debug/flight": {
        "methods": ("GET",),
        "gate": "bigdl.observability.flight.enabled",
        "gate404": "helper",
        "desc": "recent flight-recorder ring (?kind=/?request=/?limit=)"},
    "/debug/trace/*": {
        "methods": ("GET",), "gate": "bigdl.observability.enabled",
        "gate404": "helper",
        "desc": "assembled spans + stage rollup for one trace id"},
    "/debug/traces": {
        "methods": ("GET",), "gate": "bigdl.observability.enabled",
        "gate404": "helper",
        "desc": "slowest-N latency exemplars"},
    "/elastic/heartbeat": {
        "methods": ("POST",),
        "desc": "agent->supervisor beat (membership + commit floor)"},
    "/elastic/status": {
        "methods": ("GET",),
        "desc": "supervisor membership/state/commit-floor view"},
    "/fleet/autoscaler": {
        "methods": ("GET",), "gate": "bigdl.llm.fleet.enabled",
        "desc": "autoscaler state: bounds, signals, recent scale events"},
    "/fleet/status": {
        "methods": ("GET",), "gate": "bigdl.observability.federation",
        "desc": "fleet collector member/staleness status"},
    "/fleet/timeline": {
        "methods": ("GET",),
        "gate": "bigdl.observability.timeseries.enabled",
        "gate404": "helper",
        "desc": "per-member + merged windowed series for one metric"},
    "/healthz": {
        "methods": ("GET",),
        "desc": "liveness + checks (503 = drain/stall/restarting)"},
    "/metrics": {
        "methods": ("GET",),
        "desc": "Prometheus exposition (fleet-merged when federated)"},
    "/metrics.json": {
        "methods": ("GET",),
        "desc": "legacy JSON counters on ServingFrontend"},
    "/metrics/query": {
        "methods": ("GET",),
        "gate": "bigdl.observability.timeseries.enabled",
        "gate404": "helper",
        "desc": "typed window query (?series=&window=&fn=) over the "
                "time-series ring"},
    "/metrics/snapshot": {
        "methods": ("GET",), "gate": "bigdl.observability.federation",
        "desc": "full registry JSON for the fleet collector's merge"},
    "/predict": {
        "methods": ("POST",),
        "desc": "ServingFrontend inference request"},
    "/worker_drain": {
        "methods": ("GET", "POST"), "gate": "bigdl.llm.fleet.enabled",
        "desc": "graceful drain control (begin/cancel) + status poll"},
    "/worker_generate": {
        "methods": ("POST",),
        "desc": "blocking generate on worker and router"},
    "/worker_generate_stream": {
        "methods": ("POST",),
        "desc": "chunked streaming generate (failover drain path)"},
    "/worker_get_status": {
        "methods": ("GET",),
        "desc": "model/role/queue/speed worker status"},
    "/worker_import_chain": {
        "methods": ("POST",),
        "desc": "land a serialized KV handoff blob (disaggregation)"},
    "/worker_prefill": {
        "methods": ("POST",),
        "desc": "prefill-role side of the KV handoff"},
})

PYTEST_MARKERS.update({
    "api":
        "OpenAI-compatible gateway tests (translation, SSE, parity)",
    "analysis":
        "static-analysis suite tests (passes, baseline, lockwatch)",
    "chaos":
        "seeded fault-injection chaos runs (always also slow)",
    "elastic":
        "elastic multi-host training tests",
    "failover":
        "request-level failover / hedging / watchdog tests",
    "fleet":
        "elastic serving fleet tests (autoscaler, drain, KV migration)",
    "kernels":
        "Pallas/Mosaic kernel family tests",
    "kvcache":
        "prefix-aware KV-cache subsystem tests",
    "kvtier":
        "tiered KV-cache (host arena / migration / handoff) tests",
    "mixed":
        "unified mixed prefill+decode dispatch tests (ISSUE 14)",
    "perf":
        "performance microbenchmarks (advisory on shared hosts)",
    "priority":
        "SLO-class priority scheduling / preemption tests (ISSUE 17)",
    "slo":
        "fleet telemetry plane tests (sketches, federation, SLO accounting)",
    "slow":
        "excluded from the tier-1 gate (-m 'not slow')",
    "spec":
        "self-speculative decoding tests (ISSUE 19)",
    "timeseries":
        "time-series plane tests (windowed store, alert engine, "
        "timelines)",
})
