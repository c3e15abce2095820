"""Training orchestration (ref: .../optim/Optimizer.scala,
LocalOptimizer.scala, DistriOptimizer.scala + parameters/AllReduceParameter.scala).

The reference's DistriOptimizer runs one Spark job per iteration: broadcast
model, per-core forward/backward, BlockManager parameter-slice shuffle
(AllReduceParameter) for the allreduce, slice-owner applies the OptimMethod,
workers re-fetch weights. On TPU the whole iteration is ONE compiled SPMD
program: params live replicated on the mesh, the global batch is sharded
over the mesh's data axis, XLA inserts the gradient all-reduce over ICI
during partitioning, and the optim update happens in the same program
(SURVEY.md §7.1). FP16 wire compression → bf16-in-compute; straggler
dropPercentage has no SPMD analog (documented N/A).

The driver loop keeps the reference's semantics: Triggers, checkpointing,
validation, summaries, per-phase Metrics timers.
"""

from __future__ import annotations

import logging
import os
import pickle
import queue as _queue
import signal
import threading
import time
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import observability as obs
from bigdl_tpu import reliability
from bigdl_tpu.observability import utilization
from bigdl_tpu.feature.dataset import (
    AbstractDataSet, LocalDataSet, MiniBatch, SampleToMiniBatch)
from bigdl_tpu.nn.module import Criterion, Module
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation import ValidationMethod
from bigdl_tpu.utils.engine import Engine

logger = logging.getLogger("bigdl_tpu.optim")


def _grad_norm(grads):
    return jnp.sqrt(sum(
        jnp.sum(g.astype(jnp.float32) ** 2)
        for g in jax.tree_util.tree_leaves(grads)))


def _train_instruments():
    """Declare (or fetch) the training metrics — called only when
    observability is enabled, so disabled runs leave the registry
    untouched."""
    return {
        "step": obs.histogram(
            "bigdl_train_step_seconds",
            "Wall time of one optimizer iteration (data wait + step "
            "dispatch; the loop is pipelined, so this bounds dispatch, "
            "not device occupancy)"),
        "data_wait": obs.counter(
            "bigdl_train_data_wait_seconds_total",
            "Cumulative host time spent staging input batches"),
        "compute": obs.counter(
            "bigdl_train_compute_seconds_total",
            "Cumulative host time spent dispatching the compiled step"),
        "examples": obs.counter(
            "bigdl_train_examples_total",
            "Training examples consumed"),
        "steps": obs.counter(
            "bigdl_train_steps_total", "Optimizer steps taken"),
        "loss": obs.gauge("bigdl_train_loss", "Last drained train loss"),
        "lr": obs.gauge("bigdl_train_learning_rate",
                        "Learning rate at the last drained step"),
        "grad_norm": obs.gauge(
            "bigdl_train_grad_norm",
            "Global gradient L2 norm at the last drained step"),
        "throughput": obs.gauge(
            "bigdl_train_throughput_examples_per_sec",
            "Throughput of the last completed epoch"),
    }


class BatchPrefetcher:
    """Double-buffered host→device batch staging (ISSUE 4).

    The synchronous loop places batch N+1 only after step N returns, so
    the device idles for the whole host-side stage (numpy assembly +
    ``device_put``) every iteration — exactly the stall the reference's
    DistriOptimizer hides by overlapping data prep with training (arXiv
    1804.05839 §4). Here a background thread runs ``place_fn`` (the
    optimizer's ``_place_batch``) for upcoming batches while the main
    loop's current step is still dispatching/executing, holding at most
    ``depth`` staged batches in a bounded queue. The main loop's data
    timer then measures only queue-pop latency — visible in the
    existing ``bigdl_train_data_wait_seconds_total`` /
    ``..._compute_seconds_total`` split.

    Gated by ``bigdl.train.prefetch`` (default true); ``false`` restores
    the exact synchronous behavior (placement inline in the loop, no
    thread, no queue). Iteration yields ``(x, t, size)`` with inputs
    already on device. Errors in the producer (a failing transform, a
    device_put OOM) surface on the consuming thread; ``close()`` (or an
    abandoned epoch — early trigger fire, preemption) unblocks and
    retires the producer. This complements ``DataSet.prefetch`` (which
    overlaps host-side decode/augment): this stage overlaps the final
    host→device placement with device compute.
    """

    _END = object()

    def __init__(self, batches, place_fn, depth: int = 2):
        self._q: "_queue.Queue" = _queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(batches, place_fn), daemon=True)
        self._thread.start()

    def _run(self, batches, place_fn):
        try:
            for mb in batches:
                x, t = place_fn(mb.get_input(), mb.get_target())
                if not self._put((x, t, mb.size())):
                    return
            self._put(self._END)
        except BaseException as e:  # surface errors on the consumer
            self._put(e)

    def _put(self, item) -> bool:
        # bounded put that gives up when the consumer is gone, so an
        # abandoned epoch cannot leave the producer blocked forever
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        if item is self._END:
            self._stop.set()
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            raise item
        return item

    def close(self):
        self._stop.set()
        try:                       # unblock a producer stuck on put()
            while True:
                self._q.get_nowait()
        except _queue.Empty:
            pass
        # retire the producer before the caller reuses the device: a
        # still-running place_fn (device_put) must not race the next
        # epoch's donated buffers. _put gives up within its 0.1 s poll
        # once _stop is set, so this returns promptly.
        self._thread.join(timeout=5.0)


def _to_device(tree, sharding=None):
    if sharding is None:
        # force fresh buffers: the jitted step donates its inputs, and a
        # plain asarray would alias the live Module's own param arrays
        return jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), tree)
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.asarray(a), sharding), tree)


class BaseOptimizer:
    """Shared driver loop for Local/Distri optimizers."""

    def __init__(self, model: Module, dataset: AbstractDataSet,
                 criterion: Criterion, batch_size: int = 32,
                 end_trigger: Optional[Trigger] = None):
        self.model = model
        if isinstance(dataset, tuple) and len(dataset) == 2 and \
                not isinstance(dataset[0], (Module,)) and \
                hasattr(dataset[0], "__len__"):
            # (x, y) array-pair sugar; tuples of Samples go through
            # LocalDataSet directly
            dataset = LocalDataSet(*dataset)
        self.dataset = dataset
        self.criterion = criterion
        self.batch_size = batch_size
        self.end_trigger = end_trigger or Trigger.max_epoch(1)
        self.optim_method: OptimMethod = SGD()
        self.metrics = Metrics()
        self.state = {"epoch": 1, "neval": 1, "iteration_done": 0,
                      "loss": float("nan"), "record_count": 0,
                      "batch_in_epoch": 0}
        self._resume_opt_state = None
        self._checkpoint_path: Optional[str] = None
        self._checkpoint_trigger: Optional[Trigger] = None
        self._validation_trigger: Optional[Trigger] = None
        self._validation_dataset = None
        self._validation_methods: Sequence[ValidationMethod] = ()
        self._train_summary = None
        self._val_summary = None
        self._clip_l2: Optional[float] = None
        self._clip_const: Optional[tuple] = None
        self._step_fn = None
        self._drop_percentage = 0.0  # parity knob; N/A under SPMD
        self._max_retry: Optional[int] = None
        self._elastic = None         # built per-run by optimize()

    # -- builder API (ref: Optimizer setters) --------------------------------
    def set_optim_method(self, method: OptimMethod):
        self.optim_method = method
        self._step_fn = None   # compiled step closed over the old method
        return self

    set_optim_methods = set_optim_method

    def set_end_when(self, trigger: Trigger):
        self.end_trigger = trigger
        return self

    def set_checkpoint(self, path: str, trigger: Trigger):
        os.makedirs(path, exist_ok=True)
        self._checkpoint_path = path
        self._checkpoint_trigger = trigger
        return self

    def set_validation(self, trigger: Trigger, dataset,
                       methods: Sequence[ValidationMethod],
                       batch_size: Optional[int] = None):
        self._validation_trigger = trigger
        self._validation_dataset = dataset
        self._validation_methods = list(methods)
        self._validation_batch = batch_size or self.batch_size
        return self

    def set_train_summary(self, summary):
        self._train_summary = summary
        return self

    def set_val_summary(self, summary):
        self._val_summary = summary
        return self

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        self._clip_l2 = clip_norm
        self._step_fn = None
        return self

    def set_constant_gradient_clipping(self, min_v: float, max_v: float):
        self._clip_const = (min_v, max_v)
        self._step_fn = None
        return self

    def disable_gradient_clipping(self):
        self._clip_l2 = None
        self._clip_const = None
        self._step_fn = None
        return self

    def set_max_retry(self, n: int):
        """Iteration-retry budget (ref: DistriOptimizer catches iteration
        failures and rebuilds executor caches from the last in-memory
        state, up to maxRetry). Here: on any exception during the train
        loop, restore from the newest on-disk checkpoint (set_checkpoint)
        — or the initial weights when none exists — and replay. Also
        settable via config key ``bigdl.optimizer.max.retry``."""
        self._max_retry = int(n)
        return self

    def set_drop_module_property(self, *a, **k):  # parity no-op
        logger.warning("straggler dropPercentage has no analog in compiled "
                       "SPMD execution; ignoring")
        return self

    # -- compiled step --------------------------------------------------------
    def _build_step(self):
        model, criterion, optim = self.model, self.criterion, self.optim_method
        clip_l2, clip_const = self._clip_l2, self._clip_const
        # telemetry gate is baked at compile time: a disabled run's step
        # computes nothing extra and returns an empty telemetry pytree
        want_gnorm = self._step_obs_gate = obs.enabled()

        def train_step(params, states, opt_state, x, t, lr, rng):
            def loss_fn(p):
                y, s2 = model.apply(p, states, x, training=True, rng=rng)
                return criterion.apply_loss(y, t), s2

            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            tele = {"grad_norm": _grad_norm(grads)} if want_gnorm else {}
            if clip_const is not None:
                lo, hi = clip_const
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, lo, hi), grads)
            if clip_l2 is not None:
                gnorm = _grad_norm(grads)
                scale = jnp.minimum(1.0, clip_l2 / (gnorm + 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            new_params, new_opt = optim.step(params, grads, opt_state, lr)
            return new_params, new_states, new_opt, loss, tele

        # ISSUE 3 flight recorder: compile count/time + cost/memory
        # analysis per signature, recompiles (a drifting batch shape mid-
        # run) alarmed on bigdl_xla_recompiles_total{fn}
        return obs.compiled(train_step, name="optimizer/train_step",
                            donate_argnums=(0, 1, 2))

    def _place_batch(self, x, t):
        return jnp.asarray(x), jnp.asarray(t)

    def _replicate(self, tree):
        return _to_device(tree)

    # -- the driver loop ------------------------------------------------------
    def optimize(self) -> Module:
        from bigdl_tpu.utils.conf import conf

        retries = self._max_retry if self._max_retry is not None \
            else (conf.get_int("bigdl.optimizer.max.retry", 0) or 0)
        attempt = 0
        # elastic supervision (ISSUE 10): constructed ONLY when enabled
        # — a disabled run has no agent thread, no ring, no series
        self._elastic = None
        elastic_restarts = 0
        if conf.get_bool("bigdl.elastic.enabled", False):
            from bigdl_tpu import elastic
            self._elastic = elastic.TrainElastic.from_conf().start()
            if getattr(self.dataset, "_shuffle", False):
                # exact resume re-skips the interrupted epoch's batches
                # by COUNT; a stateful shuffle gives the restarted
                # process a different permutation, so the skip drops
                # the wrong samples and the replay silently diverges
                logger.warning(
                    "elastic exact-resume requires a deterministic "
                    "per-epoch data order, but %s shuffles with "
                    "process-local RNG state — a resumed run may "
                    "diverge from an uninterrupted one (use "
                    "shuffle=False or stateless shuffling)",
                    type(self.dataset).__name__)
        # snapshot for checkpoint-less recovery: initial weights AND the
        # iteration counters (a replay from fresh weights with advanced
        # counters would silently under-train)
        if retries or self._elastic is not None:
            import copy
            init_params = jax.tree_util.tree_map(
                np.asarray, self.model.parameters_dict())
            init_states = jax.tree_util.tree_map(
                np.asarray, self.model.states_dict())
            init_train_state = copy.deepcopy(dict(self.state))
            init_host_state = copy.deepcopy(
                self.optim_method.get_state())
            self._initial_snapshot = (init_params, init_states,
                                      init_train_state, init_host_state)
        rel_on = reliability.enabled()
        if rel_on or self._elastic is not None:
            # preemption/elastic recovery: a fresh run against a
            # checkpoint dir that already holds valid state (a previous
            # process was SIGTERMed, or a restarted elastic generation
            # finding the durable snapshot tier) resumes exactly at the
            # saved iteration — elastic recovery must not silently
            # depend on the unrelated reliability switch
            self._maybe_auto_resume()
        policy = reliability.RetryPolicy() if rel_on else None
        backoff = policy.delays() if rel_on else iter(())
        # past the schedule, keep sleeping at the cap — a long retry
        # budget must never degenerate into a zero-backoff hammer
        backoff_floor = policy.max_delay if rel_on else 0.0
        restore_handlers = self._install_preemption_handlers() \
            if rel_on else None
        try:
            while True:
                try:
                    return self._optimize_once()
                except (KeyboardInterrupt,
                        reliability.TrainingPreempted):
                    raise    # preemption is not a failure: no retry
                except Exception as e:  # noqa: BLE001 — retry contract
                    if self._elastic is not None and \
                            self._elastic.owns(e):
                        if self._elastic.process_restart_required():
                            # the whole worker set restarts together
                            # (rejoining a collective solo would hang on
                            # peers that are also restarting): persist
                            # the newest committed snapshot and let the
                            # launcher respawn the world — the fresh
                            # processes auto-resume from disk
                            self._elastic.abort_flush(self)
                            raise
                        elastic_restarts += 1
                        if elastic_restarts > \
                                self._elastic.max_restarts:
                            raise
                        logger.warning(
                            "elastic restart %d/%d: %s",
                            elastic_restarts,
                            self._elastic.max_restarts, e)
                        self._elastic.on_restart()
                        if not self._elastic.rollback(self):
                            self._restore_latest_checkpoint()
                        continue
                    attempt += 1
                    if attempt > retries:
                        raise
                    logger.warning(
                        "training iteration failed (%s: %s); retry %d/%d "
                        "from the last checkpoint", type(e).__name__, e,
                        attempt, retries)
                    from bigdl_tpu.reliability.policies import _count
                    _count("bigdl_reliability_retries_total",
                           "Retries performed under a RetryPolicy",
                           component="optimizer")
                    time.sleep(next(backoff, backoff_floor))
                    self._restore_latest_checkpoint()
        finally:
            if restore_handlers is not None:
                restore_handlers()
            if self._elastic is not None:
                self._elastic.close()

    # -- preemption safety (ISSUE 2) -----------------------------------------
    def _install_preemption_handlers(self):
        """SIGTERM/SIGINT → checkpoint-then-exit (the dominant TPU-VM
        failure mode is preemption with a grace window). Installed only
        on the main thread (signal.signal is illegal elsewhere), only
        when a checkpoint path is configured, and always restored after
        optimize() — callers' handlers are never clobbered for good.
        Returns the restore callable, or None when not installed."""
        if not self._checkpoint_path:
            return None
        if threading.current_thread() is not threading.main_thread():
            return None
        self._preempt_requested = False
        optimizer = self

        def on_signal(signum, frame):
            if optimizer._preempt_requested:
                # second signal: the user/platform insists — don't stay
                # stuck behind a hung step waiting for the iteration
                # boundary; restore the interruptibility contract
                raise KeyboardInterrupt
            # only a flag: the training loop checkpoints at the next
            # iteration boundary (handlers must not run jax code)
            optimizer._preempt_requested = True
            optimizer._preempt_signum = signum

        prev = {}
        try:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev[sig] = signal.signal(sig, on_signal)
        except (ValueError, OSError):   # exotic embedding: keep going
            for sig, h in prev.items():
                signal.signal(sig, h)
            return None

        def restore():
            for sig, h in prev.items():
                signal.signal(sig, h)

        return restore

    def _check_preemption(self, params, states, opt_state, state):
        if not getattr(self, "_preempt_requested", False):
            return
        self._preempt_requested = False
        self._drain_loss()
        if self._checkpoint_path:
            self._save_checkpoint(params, states, opt_state, state)
        from bigdl_tpu.reliability.policies import _count
        _count("bigdl_reliability_preemptions_total",
               "SIGTERM/SIGINT preemptions that checkpointed and exited")
        signum = getattr(self, "_preempt_signum", signal.SIGTERM)
        logger.warning(
            "preemption signal %s: checkpoint saved at iteration %d; "
            "exiting (a fresh optimize() resumes here)", signum,
            state["neval"])
        raise reliability.TrainingPreempted(
            f"preempted at iteration {state['neval']} "
            f"(checkpoint: {self._checkpoint_path})")

    def _maybe_auto_resume(self):
        """On a FRESH optimizer (no iterations done) pointed at a
        checkpoint dir holding valid state, resume at the exact saved
        iteration — the second half of the preemption round-trip."""
        from bigdl_tpu.utils import checkpoint as ckpt
        if not self._checkpoint_path or self.state.get("iteration_done"):
            return
        if not os.path.isdir(self._checkpoint_path):
            return
        tag = ckpt.latest(self._checkpoint_path, prefix="optim.",
                          paired_prefix="model.")
        if tag is None:
            return
        logger.info("auto-resuming from checkpoint %s @ %s",
                    self._checkpoint_path, tag)
        self.resume_from_checkpoint(self._checkpoint_path, tag)

    def _restore_latest_checkpoint(self):
        """Reference recovery semantics: resume from the newest VALID
        persisted checkpoint if set_checkpoint was configured (corrupt
        or incomplete candidates are quarantined and skipped); else
        restart from the live module's initial state."""
        if self._checkpoint_path and os.path.isdir(self._checkpoint_path):
            from bigdl_tpu.utils import checkpoint as ckpt
            tag = ckpt.latest(self._checkpoint_path, prefix="optim.",
                              paired_prefix="model.")
            if tag is not None:
                self.resume_from_checkpoint(self._checkpoint_path, tag)
                return
        # no persisted checkpoint: true restart — initial weights AND
        # initial counters/trigger state
        p0, s0, ts0, hs0 = self._initial_snapshot
        self.model.load_parameters_dict(p0)
        self.model.load_states_dict(s0)
        self.state.clear()
        self.state.update(ts0)
        self.optim_method.load_state(hs0)
        self._step_fn = None

    def _optimize_once(self) -> Module:
        params = self._replicate(self.model.parameters_dict())
        states = self._replicate(self.model.states_dict())
        if self._resume_opt_state is not None:
            opt_state = self._replicate(self._resume_opt_state)
            self._resume_opt_state = None
        else:
            opt_state = self._replicate(
                self.optim_method.init_state(self.model.parameters_dict()))
        if self._step_fn is not None and \
                getattr(self, "_step_obs_gate", None) != obs.enabled():
            # the telemetry gate is baked into the compiled step: a
            # toggle between runs must recompile, or a disabled run keeps
            # computing grad-norm (and an enabled one never gets it)
            self._step_fn = None
        if self._step_fn is None:
            self._step_fn = self._build_step()
        step = self._step_fn
        from bigdl_tpu.utils.engine import train_rng_key
        key = train_rng_key(self.optim_method.host_state.get("seed", 0))
        # exact-resume contract (ISSUE 10): a replay — elastic rollback,
        # retry restore, preemption auto-resume — must consume the SAME
        # split-chain positions the uninterrupted run would, or any
        # rng-consuming layer (dropout) diverges. One split was burned
        # per completed iteration; fast-forward past them in ONE
        # dispatched scan (a host loop would cost O(iterations) device
        # round-trips on a deep resume).
        ff_n = int(self.state.get("iteration_done", 0) or 0)
        if ff_n:
            key = jax.lax.scan(
                lambda k, _: (jax.random.split(k)[0], None),
                key, None, length=ff_n)[0]

        batcher = SampleToMiniBatch(self.batch_size)
        state = self.state
        end_uses_loss = getattr(self.end_trigger, "uses_loss", False)
        self._pending_loss = None
        # observability is sampled once per run: the hot loop sees a bool
        # and (when off) touches neither the registry nor the trace ring
        self._obs = obs.enabled()
        ins = _train_instruments() if self._obs else None
        self._obs_ins = ins

        from bigdl_tpu.utils.conf import conf
        prefetch_on = conf.get_bool("bigdl.train.prefetch", True)
        prefetch_depth = conf.get_int("bigdl.train.prefetch.depth", 2)

        while not self.end_trigger(state):
            records = 0
            t_epoch = time.perf_counter()
            ended_mid_epoch = False
            # ISSUE 4: with prefetch on, a background thread stages batch
            # N+1 (including device placement) while step N is in
            # flight; the data timer below then measures queue-pop
            # latency, not staging. Off → inline placement, exactly the
            # synchronous loop.
            source = batcher(self.dataset.data(train=True))
            # mid-epoch resume (ISSUE 10): a snapshot taken inside an
            # epoch records how many batches that epoch had consumed;
            # replaying them would re-train data the restored counters
            # (and weights) already include. Skip them unplaced — the
            # cadence resets to 0 at every epoch boundary, so a fresh
            # epoch skips nothing.
            for _ in range(int(state.get("batch_in_epoch", 0) or 0)):
                if next(source, None) is None:
                    break
            batches = BatchPrefetcher(source, self._place_batch,
                                      depth=prefetch_depth) \
                if prefetch_on else self._staged_batches(source)
            try:
                with obs.span("train/epoch", epoch=state["epoch"]):
                    while True:
                        t0 = time.perf_counter()
                        item = next(batches, None)
                        t_data = time.perf_counter() - t0
                        if item is None:
                            break
                        x, t, nrec = item
                        reliability.inject("optimizer.step")
                        if self._elastic is not None:
                            # fault site + step heartbeat + abort check
                            # — a directed/stalled world aborts HERE,
                            # before dispatching into a collective its
                            # peers will never join
                            self._elastic.on_step_begin(state)
                        with obs.span("train/step", step=state["neval"]):
                            self.metrics.add("data", t_data)
                            lr = self.optim_method.current_lr()
                            key, sub = jax.random.split(key)
                            t0 = time.perf_counter()
                            params, states, opt_state, loss, tele = step(
                                params, states, opt_state, x, t, lr, sub)
                            t_compute = time.perf_counter() - t0
                            self.metrics.add("compute", t_compute)
                            # live roofline attribution (ISSUE 16):
                            # same clock the compute metric reads —
                            # no new device syncs
                            utilization.observe(
                                getattr(step, "name",
                                        "optimizer/train_step"),
                                t_compute)
                            # loss is materialized one step late so the
                            # host can dispatch iteration N+1 while the
                            # device still runs N
                            self._drain_loss()
                            self._pending_loss = (loss, tele,
                                                  state["neval"], lr)
                            records += nrec
                            state["record_count"] += nrec
                            if ins is not None:
                                ins["step"].observe(t_data + t_compute)
                                ins["data_wait"].inc(t_data)
                                ins["compute"].inc(t_compute)
                                ins["examples"].inc(nrec)
                                ins["steps"].inc()
                        self.optim_method.host_state["eval_counter"] += 1
                        state["neval"] += 1
                        state["iteration_done"] += 1
                        state["batch_in_epoch"] = \
                            state.get("batch_in_epoch", 0) + 1
                        self._after_iteration(params, states, opt_state,
                                              state)
                        if self._elastic is not None:
                            # snapshot cadence + durable flush (after
                            # _after_iteration so the snapshot carries
                            # validation scores/trigger effects exactly
                            # like a trigger checkpoint would)
                            self._elastic.on_step_end(
                                self, params, states, opt_state, state)
                        self._check_preemption(params, states, opt_state,
                                               state)
                        if end_uses_loss:
                            self._drain_loss()
                        if self.end_trigger(state):
                            ended_mid_epoch = True
                            break
            finally:
                # an abandoned epoch (early trigger fire, preemption,
                # a raising step) must retire the producer thread
                if isinstance(batches, BatchPrefetcher):
                    batches.close()
                if self._elastic is not None:
                    # epoch-boundary work (validation, checkpointing)
                    # legitimately keeps the loop away from its step
                    # heartbeat — park the collective-hang watchdog
                    # until the next step re-arms it
                    self._elastic.on_loop_exit()
            self._drain_loss()
            thr = records / max(time.perf_counter() - t_epoch, 1e-9)
            logger.info(
                "Epoch %d done: loss=%.6f throughput=%.1f records/s (%s)",
                state["epoch"], state["loss"], thr, self.metrics.summary())
            if ins is not None:
                ins["throughput"].set(thr)
            if self._train_summary is not None:
                self._train_summary.add_scalar(
                    "Throughput", thr, state["neval"])
            if ended_mid_epoch:
                # end_trigger fired inside the epoch: don't advance the
                # epoch counter, but still give epoch-cadence checkpoint/
                # validation triggers a final chance to persist state
                state["epoch_finished"] = True
                self._after_iteration(params, states, opt_state, state)
                state["epoch_finished"] = False
                break
            state["epoch"] += 1
            state["batch_in_epoch"] = 0
            self.optim_method.host_state["epoch"] = state["epoch"]
            state["epoch_finished"] = True
            self._after_iteration(params, states, opt_state, state)
            state["epoch_finished"] = False

        # write trained values back into the live module (facade parity)
        self.model.load_parameters_dict(
            jax.tree_util.tree_map(np.asarray, params))
        self.model.load_states_dict(
            jax.tree_util.tree_map(np.asarray, states))
        # expose the final optimizer slots (momenta etc.) so drivers that
        # re-enter training across process boundaries (nano
        # multi-instance) can resume instead of resetting them
        self._last_opt_state = jax.tree_util.tree_map(np.asarray,
                                                      opt_state)
        return self.model

    def _staged_batches(self, source):
        """Synchronous staging (``bigdl.train.prefetch=false``): place
        each batch inline so the loop's data timer covers the full
        host-side stage, exactly like the pre-prefetch loop."""
        for mb in source:
            x, t = self._place_batch(mb.get_input(), mb.get_target())
            yield x, t, mb.size()

    def _drain_loss(self):
        pending = getattr(self, "_pending_loss", None)
        if pending is not None:
            dev_loss, tele, neval, lr = pending
            self.state["loss"] = float(dev_loss)
            ins = getattr(self, "_obs_ins", None)
            if ins is not None:
                # the loss fetch above is the loop's existing host sync
                # point; telemetry piggybacks on it (the grad-norm value
                # materialized alongside the loss, this is a fetch of a
                # ready buffer, not a new synchronization)
                ins["loss"].set(self.state["loss"])
                ins["lr"].set(float(lr))
                if "grad_norm" in tele:
                    ins["grad_norm"].set(float(tele["grad_norm"]))
            if self._train_summary is not None:
                self._train_summary.add_scalar(
                    "Loss", self.state["loss"], neval)
                self._train_summary.add_scalar("LearningRate", lr, neval)
            self._pending_loss = None

    def _after_iteration(self, params, states, opt_state, state):
        # each trigger is evaluated exactly ONCE per pass (triggers may be
        # stateful, e.g. _EveryEpoch's latch); the neval dedup stops the
        # epoch-end pass from re-firing an iteration-cadence trigger that
        # already fired in-loop at the same neval
        if self._validation_trigger is not None:
            if getattr(self._validation_trigger, "uses_loss", False):
                self._drain_loss()
            if self._validation_trigger(state) and \
                    getattr(self, "_last_val_neval", -1) != state["neval"]:
                self._last_val_neval = state["neval"]
                self._drain_loss()
                self._run_validation(params, states, state)
        if self._checkpoint_trigger is not None:
            if getattr(self._checkpoint_trigger, "uses_loss", False):
                self._drain_loss()
            if self._checkpoint_trigger(state) and \
                    getattr(self, "_last_ckpt_neval", -1) != state["neval"]:
                self._last_ckpt_neval = state["neval"]
                self._drain_loss()
                self._save_checkpoint(params, states, opt_state, state)

    def _run_validation(self, params, states, state):
        results = validate(self.model, params, states,
                           self._validation_dataset,
                           self._validation_methods,
                           self._validation_batch)
        for method, res in zip(self._validation_methods, results):
            logger.info("Validation @ iter %d: %s = %s",
                        state["neval"], method, res)
            if self._val_summary is not None:
                self._val_summary.add_scalar(
                    str(method), res.result, state["neval"])
        if results:
            state["score"] = results[0].result
            sched = getattr(self.optim_method, "schedule", None)
            if sched is not None and hasattr(sched, "record_score"):
                sched.record_score(results[0].result)

    def _save_checkpoint(self, params, states, opt_state, state):
        reliability.inject("optimizer.checkpoint")
        self._write_checkpoint(
            jax.tree_util.tree_map(np.asarray, params),
            jax.tree_util.tree_map(np.asarray, states),
            jax.tree_util.tree_map(np.asarray, opt_state),
            self.optim_method.get_state(), dict(state))

    def _world_signature(self) -> dict:
        """The shard-math identity a checkpoint is only resumable
        under (ISSUE 10 satellite): process/device counts, plus the
        mesh geometry for distributed optimizers."""
        sig = {"processes": jax.process_count(),
               "devices": jax.device_count()}
        mesh = getattr(self, "mesh", None)
        if mesh is not None:
            sig["mesh_shape"] = [int(d) for d in mesh.devices.shape]
            sig["mesh_axes"] = list(mesh.axis_names)
        return sig

    def _write_checkpoint(self, params, states, opt_state, host_state,
                          train_state):
        """Persist one checkpoint pair from HOST trees — shared by the
        trigger/preemption path (:meth:`_save_checkpoint`, live state)
        and the elastic durable-tier flush (a committed ring entry)."""
        if jax.process_count() > 1 and jax.process_index() != 0:
            # multi-host: training state is replicated and the
            # checkpoint dir is shared — exactly one writer, or two
            # processes race their atomic renames onto the same tag.
            # Peers resume from process 0's tags.
            if not getattr(self, "_warned_ckpt_delegated", False):
                self._warned_ckpt_delegated = True
                logger.warning(
                    "multi-host checkpointing: process %d delegates "
                    "writes to process 0 — the checkpoint dir %r must "
                    "be on storage SHARED across hosts (GCS/NFS); on "
                    "node-local paths this process would find no tags "
                    "to resume from", jax.process_index(),
                    self._checkpoint_path)
            return
        tag = f"{train_state['epoch']}.{train_state['neval']}"
        self.model.load_parameters_dict(params)
        self.model.load_states_dict(states)
        # model first, optim second: latest() requires the valid PAIR,
        # so a crash between the two leaves tag invisible to recovery
        self.model.save_module(
            os.path.join(self._checkpoint_path, f"model.{tag}"))
        from bigdl_tpu.utils.checkpoint import (prune_checkpoints,
                                                save_checkpoint)
        save_checkpoint(
            os.path.join(self._checkpoint_path, f"optim.{tag}"),
            {"opt_state": opt_state,
             "host_state": host_state,
             "train_state": dict(train_state),
             "world": self._world_signature()})
        logger.info("checkpoint saved: %s @ %s", self._checkpoint_path, tag)
        from bigdl_tpu.utils.conf import conf
        keep = conf.get_int("bigdl.checkpoint.keep", 0) or 0
        if keep > 0:
            prune_checkpoints(self._checkpoint_path, keep)

    def _check_world(self, saved: Optional[dict], path: str, tag: str):
        """Fail fast on a world-size / mesh-shape change (ISSUE 10
        satellite): resuming a replicated-params checkpoint into a
        different data-parallel degree silently changes the per-shard
        batch math — the run would converge to different weights with
        no error. Pre-ISSUE-10 checkpoints carry no signature and skip
        the check (resume was always same-world in practice)."""
        if not saved:
            return
        cur = self._world_signature()
        mismatched = [k for k in ("processes", "devices", "mesh_shape",
                                  "mesh_axes")
                      if k in saved and k in cur and saved[k] != cur[k]]
        if not mismatched:
            return
        def fmt(sig):
            out = (f"{sig.get('processes')} process(es) / "
                   f"{sig.get('devices')} device(s)")
            if sig.get("mesh_shape"):
                out += (f", mesh {tuple(sig['mesh_shape'])} over "
                        f"{tuple(sig.get('mesh_axes', ()))}")
            return out
        raise ValueError(
            f"checkpoint {path} @ {tag} was saved by a different world: "
            f"saved {fmt(saved)}, current {fmt(cur)} (mismatched: "
            f"{', '.join(mismatched)}). Resuming would silently change "
            "the shard math; restart with the saved world size, or load "
            "the weights explicitly via Module.load_module to retrain "
            "under the new topology")

    def resume_from_checkpoint(self, path: str, tag: str):
        """Resume (ref: Optimizer resume = loadModule + OptimMethod.load)."""
        optim_path = os.path.join(path, f"optim.{tag}")
        if os.path.isdir(optim_path):
            from bigdl_tpu.utils.checkpoint import load_checkpoint
            blob, _ = load_checkpoint(optim_path, to_jax=False)
        else:  # legacy round-1 pickle checkpoints
            with open(optim_path, "rb") as f:
                blob = pickle.load(f)
        # the world guard runs BEFORE any state mutates: a rejected
        # resume leaves the optimizer untouched
        self._check_world(blob.get("world"), path, tag)
        self.model = Module.load_module(os.path.join(path, f"model.{tag}"))
        self._step_fn = None   # compiled step closed over the old model
        self.optim_method.load_state(blob["host_state"])
        # keys absent from an older blob must not inherit live values:
        # a stale nonzero batch_in_epoch would make the resumed epoch
        # skip batches that were never trained under these counters
        self.state["batch_in_epoch"] = 0
        self.state.update(blob["train_state"])
        self.state["epoch_finished"] = False
        self._resume_opt_state = blob["opt_state"]
        return self


class LocalOptimizer(BaseOptimizer):
    """Single-chip training (ref: LocalOptimizer.scala — whose per-core model
    clones are unnecessary here: one jit step saturates the chip)."""


class DistriOptimizer(BaseOptimizer):
    """Mesh data-parallel training (ref: DistriOptimizer.scala).

    Params/optimizer state are replicated on the mesh; each global batch is
    sharded over the ``data`` axis. XLA's partitioner inserts the gradient
    all-reduce (psum over ICI) exactly where AllReduceParameter's
    BlockManager shuffle sat in the reference.
    """

    def __init__(self, model, dataset, criterion, batch_size: int = 32,
                 end_trigger=None, mesh=None, data_axis: str = "data"):
        super().__init__(model, dataset, criterion, batch_size, end_trigger)
        self.mesh = mesh or Engine.mesh()
        self.data_axis = data_axis
        self._grad_compression: Optional[str] = None
        from jax.sharding import NamedSharding, PartitionSpec as P
        self._rep = NamedSharding(self.mesh, P())
        self._batch_sharding = NamedSharding(self.mesh, P(data_axis))
        n_data = self.mesh.shape[data_axis]
        if batch_size % n_data != 0:
            raise ValueError(
                f"batch_size {batch_size} not divisible by data-parallel "
                f"degree {n_data} (ref requires batch % nodes == 0 too)")

    def set_gradient_compression(self, mode: Optional[str]):
        """Wire-compress the gradient all-reduce (ref: AllReduceParameter's
        FP16CompressedTensor, optim/parameters/ — gradients cross the wire
        at 16 bits). ``mode``: "bf16"/"fp16" → bf16 wire dtype
        (compressed_all_reduce); "int8" → EQuARX-style shared-scale int8
        (quantized_all_reduce); None → plain f32 psum.

        Compression requires a bound axis name, so the step is built via
        ``shard_map`` over the mesh's data axis instead of relying on the
        auto-partitioner — gradients are explicitly all-reduced in the
        wire dtype, and the (replicated) optimizer update runs per-device
        on identical reduced gradients. Normalization layers see their
        per-device batch shard and their running stats are pmean'd, which
        matches the reference's per-worker batch-statistics semantics."""
        if mode not in (None, "bf16", "fp16", "int8"):
            raise ValueError(f"unknown gradient compression {mode!r}")
        self._grad_compression = mode
        self._step_fn = None
        return self

    def _build_step(self):
        if not self._grad_compression:
            return super()._build_step()
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from bigdl_tpu.parallel.collectives import (
            compressed_all_reduce, quantized_all_reduce)

        model, criterion, optim = (self.model, self.criterion,
                                   self.optim_method)
        clip_l2, clip_const = self._clip_l2, self._clip_const
        mode, axis = self._grad_compression, self.data_axis
        want_gnorm = self._step_obs_gate = obs.enabled()

        def local_step(params, states, opt_state, x, t, lr, rng):
            def loss_fn(p):
                y, s2 = model.apply(p, states, x, training=True, rng=rng)
                return criterion.apply_loss(y, t), s2

            (loss, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # the compressed wire crossing — this is where the reference
            # casts to fp16 before the BlockManager shuffle
            if mode == "int8":
                grads = quantized_all_reduce(grads, axis, mean=True)
            else:
                grads = compressed_all_reduce(grads, axis, mean=True)
            loss = lax.pmean(loss, axis)
            new_states = jax.tree_util.tree_map(
                lambda s: lax.pmean(s, axis)
                if jnp.issubdtype(s.dtype, jnp.floating) else s, new_states)
            # telemetry reads the REDUCED gradient: the global norm, same
            # value every replica (so the replicated out_spec is sound)
            tele = {"grad_norm": _grad_norm(grads)} if want_gnorm else {}
            # clip AFTER the reduce: global-gradient clipping semantics
            if clip_const is not None:
                lo, hi = clip_const
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, lo, hi), grads)
            if clip_l2 is not None:
                gnorm = _grad_norm(grads)
                scale = jnp.minimum(1.0, clip_l2 / (gnorm + 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            new_params, new_opt = optim.step(params, grads, opt_state, lr)
            return new_params, new_states, new_opt, loss, tele

        rep, sh = P(), P(self.data_axis)
        smap = jax.shard_map(local_step, mesh=self.mesh,
                             in_specs=(rep, rep, rep, sh, sh, rep, rep),
                             out_specs=(rep, rep, rep, rep, rep))
        return obs.compiled(smap, name="optimizer/train_step_compressed",
                            donate_argnums=(0, 1, 2))

    def _replicate(self, tree):
        return _to_device(tree, self._rep)

    def _place_batch(self, x, t):
        multi_host = jax.process_count() > 1

        def put(a):
            a = np.asarray(a)
            if multi_host:
                # each host holds only its local shard; device_put to a
                # global NamedSharding is illegal for non-addressable
                # devices — assemble the global array from per-process data
                return jax.make_array_from_process_local_data(
                    self._batch_sharding, a)
            return jax.device_put(jnp.asarray(a), self._batch_sharding)

        x = jax.tree_util.tree_map(put, x) if isinstance(x, list) else put(x)
        t = jax.tree_util.tree_map(put, t) if isinstance(t, list) else put(t)
        return x, t


class Optimizer:
    """Facade choosing Local vs Distri (ref: Optimizer.apply)."""

    def __new__(cls, model: Module, dataset, criterion,
                batch_size: int = 32, end_trigger=None,
                distributed: Optional[bool] = None, **kwargs):
        # tuple sugar handled once, in BaseOptimizer.__init__
        if distributed is None:
            devices = jax.devices()
            distributed = Engine.is_initialized() and len(devices) > 1
            if len(devices) > 1 and not distributed:
                logger.warning(
                    "Engine.init() has not been called: training runs "
                    "on %s alone although JAX sees %d devices; call "
                    "Engine.init() first (or pass distributed=True) to "
                    "train over all of them", devices[0], len(devices))
        if distributed:
            return DistriOptimizer(model, dataset, criterion, batch_size,
                                   end_trigger, **kwargs)
        return LocalOptimizer(model, dataset, criterion, batch_size,
                              end_trigger)


# ---------------------------------------------------------------------------
# Evaluation / prediction (ref: optim/Evaluator.scala, Predictor.scala)
# ---------------------------------------------------------------------------

def _forward_fn(model: Module):
    # cache the jitted eval forward on the module: validation triggers /
    # Evaluator calls reuse the compiled executable instead of re-tracing
    cached = getattr(model, "_jit_fwd", None)
    if cached is not None:
        return cached

    def fwd(params, states, x):
        y, _ = model.apply(params, states, x, training=False, rng=None)
        return y

    fwd = obs.compiled(fwd, name="optimizer/eval_forward")
    object.__setattr__(model, "_jit_fwd", fwd)
    return fwd


def validate(model: Module, params, states, dataset,
             methods: Sequence[ValidationMethod], batch_size: int = 32):
    """Distributed-eval equivalent: jitted forward over the dataset, results
    merged across batches (ref: Evaluator.scala)."""
    if isinstance(dataset, tuple):
        dataset = LocalDataSet(*dataset, shuffle=False)
    fwd = _forward_fn(model)
    batcher = SampleToMiniBatch(batch_size, drop_remainder=False)
    results = [None] * len(methods)
    for mb in batcher(dataset.data(train=False)):
        y = fwd(params, states, jnp.asarray(mb.get_input()))
        for i, m in enumerate(methods):
            r = m(y, mb.get_target())
            results[i] = r if results[i] is None else results[i].merge(r)
    return results


class Evaluator:
    def __init__(self, model: Module):
        self.model = model

    def evaluate(self, dataset, methods: Sequence[ValidationMethod],
                 batch_size: int = 32):
        params = self.model.parameters_dict()
        states = self.model.states_dict()
        return validate(self.model, params, states, dataset, methods,
                        batch_size)


class Predictor:
    def __init__(self, model: Module, batch_size: int = 32):
        self.model = model
        self.batch_size = batch_size

    def predict(self, dataset):
        if isinstance(dataset, np.ndarray):
            dataset = LocalDataSet(dataset, shuffle=False)
        fwd = _forward_fn(self.model)
        params = self.model.parameters_dict()
        states = self.model.states_dict()
        batcher = SampleToMiniBatch(self.batch_size, drop_remainder=False)
        outs = [np.asarray(fwd(params, states, jnp.asarray(mb.get_input())))
                for mb in batcher(dataset.data(train=False))]
        return np.concatenate(outs, axis=0)

    def predict_class(self, dataset):
        return self.predict(dataset).argmax(axis=-1) + 1  # 1-based parity
