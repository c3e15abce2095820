"""XLA compile/HBM flight recorder (ISSUE 3 tentpole part 2).

:func:`compiled` wraps a jit entry point so that every *compilation* the
function undergoes over the process lifetime is recorded, and silent
**recompiles** — the classic TPU perf killer, where a shape/dtype drift
quietly turns a sub-millisecond step into a multi-second one — trip an
alarm counter with the exact signature that triggered them:

- ``bigdl_xla_compiles_total{fn}`` / ``bigdl_xla_compile_seconds{fn}``
  — compile count and time per wrapped function;
- ``bigdl_xla_recompiles_total{fn}`` — compiles *beyond the first
  signature* of a function (the alarm; the triggering shape/dtype
  signature is logged and kept in :func:`compile_stats`);
- ``bigdl_xla_flops_per_call{fn}`` / ``bigdl_xla_bytes_accessed_per_call
  {fn}`` — harvested from the lowered executable's ``cost_analysis()``:
  the *attributed* FLOPs/step and HBM traffic the MFU numbers in
  ``bench.py`` are computed from;
- ``bigdl_xla_peak_hbm_bytes{fn}`` — ``memory_analysis()`` argument +
  output + temp (minus donated aliasing), the executable's device-memory
  high-water mark;
- ``bigdl_xla_live_buffer_bytes`` — total bytes of live jax arrays on
  the devices, sampled at each compile (compiles are exactly when HBM
  pressure decisions get made).

Dispatch model: when observability is enabled the wrapper compiles
ahead-of-time (``fn.lower(...).compile()``) once per distinct abstract
signature and dispatches to its own executable cache — compile time is
measured exactly (not smeared into the first call) and the analyses
come from the very executable that serves traffic. When disabled, calls
go straight to the plain ``jax.jit`` function: one attribute check, no
signature computation, no new series (the zero-cost contract). Any AOT
API hiccup falls back to plain jit dispatch permanently for that
function — telemetry degrades (compile time measured as first-call
wall), correctness never.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from bigdl_tpu.observability import _state

logger = logging.getLogger("bigdl_tpu.observability")

#: Compile times live in a very different range from request latency.
COMPILE_BUCKETS: Tuple[float, ...] = (
    .01, .05, .1, .25, .5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
    300.0, 600.0)

# process-global compile ledger, keyed by fn name: survives the wrapper
# being dropped (a bench builds a step, runs, returns — the telemetry
# block still reports it) WITHOUT pinning the wrapper itself, whose
# closure may hold full model params. History is capped per name.
_stats_lock = threading.Lock()
_stats: Dict[str, Dict[str, Any]] = {}
_HISTORY_CAP = 64


def _ledger_record(name: str, entry: Dict[str, Any],
                   is_recompile: bool):
    with _stats_lock:
        rec = _stats.setdefault(name, {"fn": name, "compiles": 0,
                                       "recompiles": 0, "history": []})
        rec["compiles"] += 1
        rec["recompiles"] += int(is_recompile)
        rec["history"].append(entry)   # entry is shared with the
        # instance history and filled in-place as analyses land
        del rec["history"][:-_HISTORY_CAP]


def _instruments():
    from bigdl_tpu import observability as obs
    return {
        "compiles": obs.counter(
            "bigdl_xla_compiles_total",
            "XLA compilations per wrapped jit entry point",
            labelnames=("fn",)),
        "recompiles": obs.counter(
            "bigdl_xla_recompiles_total",
            "Compilations beyond the first signature of a function — "
            "the silent-perf-killer alarm (triggering signature logged)",
            labelnames=("fn",)),
        "compile_seconds": obs.histogram(
            "bigdl_xla_compile_seconds",
            "Wall time of one XLA compilation",
            labelnames=("fn",), buckets=COMPILE_BUCKETS),
        "flops": obs.gauge(
            "bigdl_xla_flops_per_call",
            "cost_analysis() FLOPs of one call of the latest executable",
            labelnames=("fn",)),
        "bytes": obs.gauge(
            "bigdl_xla_bytes_accessed_per_call",
            "cost_analysis() bytes accessed (HBM traffic) per call",
            labelnames=("fn",)),
        "peak_hbm": obs.gauge(
            "bigdl_xla_peak_hbm_bytes",
            "memory_analysis() argument+output+temp-alias bytes of the "
            "latest executable (its device-memory high-water mark)",
            labelnames=("fn",)),
        "live_bytes": obs.gauge(
            "bigdl_xla_live_buffer_bytes",
            "Total bytes of live jax arrays, sampled at compile time"),
    }


def _leaf_sig(leaf: Any):
    # jax arrays: the aval (hashable ShapedArray — shape, dtype, weak
    # type) IS what keys jit's executable cache, and reading it costs a
    # C attribute lookup. str(dtype) here was measured 20x slower.
    aval = getattr(leaf, "aval", None)
    if aval is not None:
        return aval
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:     # numpy
        return (tuple(shape), dtype)
    # python scalars are weakly typed under jit: the VALUE does not key
    # a new executable, only the python type does — including it would
    # flag every lr change as a recompile
    return (type(leaf).__name__,)


def signature_of(args: tuple, kwargs: dict) -> Tuple:
    """Hashable abstract signature (treedef + per-leaf avals) of one
    call — exactly what keys jit's own executable cache, minus
    weak-typed scalar values. Measured cost: ~11µs for a 20-leaf
    stacked-LLM tree, ~0.5ms for a 320-leaf CNN tree — noise against
    the tens-of-ms steps those trees drive, and skipped entirely when
    observability is disabled."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple([_leaf_sig(leaf) for leaf in leaves]))


def _render_leaf(leaf) -> str:
    if isinstance(leaf, tuple):
        if len(leaf) == 2:
            shape, dtype = leaf
            return f"{dtype}[{','.join(map(str, shape))}]"
        return str(leaf[0])
    # a ShapedArray: 'float32[2,2]' — rendered only when a compile is
    # being recorded, never on the dispatch hot path
    short = getattr(leaf, "str_short", None)
    return short() if short is not None else str(leaf)


def format_signature(sig: Tuple) -> str:
    """Human-readable shape/dtype rendering for logs and /debug."""
    return "(" + ", ".join(_render_leaf(leaf) for leaf in sig[1]) + ")"


def _cost_analysis(executable) -> dict:
    try:
        ca = executable.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def _memory_analysis(executable) -> Optional[dict]:
    try:
        ma = executable.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if v is not None:
            out[field] = int(v)
    return out or None


def _live_buffer_bytes() -> Optional[int]:
    try:
        import jax
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.live_arrays())
    except Exception:
        return None


class CompiledFunction:
    """The wrapper :func:`compiled` returns. Callable like the jitted
    function; exposes per-signature compile history via ``stats()``."""

    def __init__(self, fn: Callable, name: str, jit_kwargs: dict):
        import jax
        self.fn = fn
        self.name = name
        self._jit = jax.jit(fn, **jit_kwargs)
        self._lock = threading.Lock()
        # serializes compiles: without it two threads racing on the
        # same fresh signature would both compile, double-counting and
        # firing a FALSE recompile alarm on the second one
        self._compile_lock = threading.Lock()
        self._executables: Dict[Tuple, Any] = {}
        self._history: List[Dict[str, Any]] = []   # capped; see counters
        self._compiles = 0
        self._recompiles = 0
        self._aot_broken = False

    # -- plain jit passthroughs ------------------------------------------
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        if not _state.enabled:
            return self._jit(*args, **kwargs)
        sig = signature_of(args, kwargs)
        with self._lock:
            executable = self._executables.get(sig)
            known = sig in self._executables
        if executable is not None:
            return executable(*args, **kwargs)
        if known or self._aot_broken:
            # signature seen but AOT unusable: plain jit dispatch
            return self._jit(*args, **kwargs)
        with self._compile_lock:
            # re-check under the compile lock: a racing thread may have
            # just compiled this very signature
            with self._lock:
                executable = self._executables.get(sig)
                known = sig in self._executables
            if executable is not None:
                return executable(*args, **kwargs)
            if known:
                return self._jit(*args, **kwargs)
            return self._compile_and_call(sig, args, kwargs)

    def _compile_and_call(self, sig: Tuple, args: tuple, kwargs: dict):
        t0 = time.perf_counter()
        wall0 = time.time()
        executable = None
        try:
            executable = self._jit.lower(*args, **kwargs).compile()
            out = None
        except Exception as e:  # noqa: BLE001 — AOT quirks (exotic
            # static args, backend gaps) must never break the call path
            if not self._aot_broken:
                logger.warning(
                    "AOT compile of %s unavailable (%s: %s); falling "
                    "back to plain jit dispatch (compile time will "
                    "include the first execution)", self.name,
                    type(e).__name__, e)
            self._aot_broken = True
            try:
                out = self._jit(*args, **kwargs)
            except Exception as e2:
                # not an AOT quirk: the program itself does not trace,
                # lower or compile for this signature
                e2._bigdl_first_call = True
                raise
        dt = time.perf_counter() - t0
        self._record_compile(sig, dt, wall0, executable)
        with self._lock:
            self._executables[sig] = executable
        if executable is not None:
            return executable(*args, **kwargs)
        return out

    def _record_compile(self, sig: Tuple, seconds: float, wall0: float,
                        executable):
        from bigdl_tpu.observability import tracing
        ins = _instruments()
        sig_str = format_signature(sig)
        # the entry is fully built BEFORE it is published to the
        # instance history / global ledger: a concurrent stats() /
        # compile_stats() snapshot must never see a dict that is still
        # growing under it
        entry = {"signature": sig_str, "compile_s": round(seconds, 4)}
        if executable is not None:
            ca = _cost_analysis(executable)
            flops = ca.get("flops")
            nbytes = ca.get("bytes accessed")
            if flops:
                entry["flops"] = float(flops)
                ins["flops"].labels(fn=self.name).set(float(flops))
            if nbytes:
                entry["bytes_accessed"] = float(nbytes)
                ins["bytes"].labels(fn=self.name).set(float(nbytes))
            ma = _memory_analysis(executable)
            if ma:
                peak = (ma.get("argument_size_in_bytes", 0)
                        + ma.get("output_size_in_bytes", 0)
                        + ma.get("temp_size_in_bytes", 0)
                        - ma.get("alias_size_in_bytes", 0))
                entry["peak_hbm_bytes"] = peak
                ins["peak_hbm"].labels(fn=self.name).set(peak)
        with self._lock:
            is_recompile = self._compiles > 0
            self._compiles += 1
            self._recompiles += int(is_recompile)
            n_recompile = self._recompiles
            self._history.append(entry)
            # cap: an unbucketed shape storm must not grow host memory
            # without bound (the ledger applies the same cap)
            del self._history[:-_HISTORY_CAP]
            recent = [h["signature"] for h in self._history[-4:-1]]
        _ledger_record(self.name, entry, is_recompile)
        ins["compiles"].labels(fn=self.name).inc()
        ins["compile_seconds"].labels(fn=self.name).observe(seconds)
        if is_recompile:
            ins["recompiles"].labels(fn=self.name).inc()
            # log a bounded tail of prior signatures: during a shape
            # storm the full list would make log volume quadratic
            logger.warning(
                "RECOMPILE #%d of %s triggered by signature %s "
                "(%.2fs) — a shape/dtype drift on a hot path is a "
                "silent perf killer; recent signatures: %s",
                n_recompile, self.name, sig_str, seconds, recent)
        live = _live_buffer_bytes()
        if live is not None:
            ins["live_bytes"].set(live)
        tracing.add_complete("xla/compile", wall0, seconds, fn=self.name,
                             signature=sig_str, stage="xla",
                             recompile=is_recompile)

    def executables(self) -> List[Tuple[str, Any]]:
        """``(signature, executable)`` for every program this wrapper
        compiled ahead of time — the very executables that serve its
        calls (``executable.as_text()`` is their optimized HLO). A
        signature served by plain jit dispatch (observability off, or
        AOT unavailable) has no entry."""
        with self._lock:
            return [(format_signature(sig), exe)
                    for sig, exe in self._executables.items()
                    if exe is not None]

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            history = [dict(h) for h in self._history]
            return {"fn": self.name, "compiles": self._compiles,
                    "recompiles": self._recompiles,
                    "aot": not self._aot_broken, "history": history}


def failed_first_call(exc: BaseException) -> bool:
    """True when ``exc`` escaped the first call of a wrapped function
    for a new signature — tracing, lowering or compilation failed, so
    calling again with the same arguments fails the same way."""
    return getattr(exc, "_bigdl_first_call", False)


def compiled(fn: Callable, *, name: Optional[str] = None,
             **jit_kwargs) -> CompiledFunction:
    """``jax.jit`` plus the flight recorder. Drop-in at jit entry
    points: ``step = compiled(train_step, name="optimizer/train_step",
    donate_argnums=(0, 1, 2))``. Extra keyword args go to ``jax.jit``.
    """
    return CompiledFunction(fn, name or getattr(fn, "__name__", "fn"),
                            jit_kwargs)


def reset():
    """Clear the process-global compile ledger — test isolation only
    (live CompiledFunction instances keep their own history/cache)."""
    with _stats_lock:
        _stats.clear()


def latest_costs() -> Dict[str, Tuple[float, float]]:
    """``{fn: (flops, bytes_accessed)}`` of the most recent compile of
    each entry point that carried cost analysis — the cheap join key
    :mod:`~bigdl_tpu.observability.utilization` multiplies by measured
    dispatch wall times for live roofline attribution (a full
    :func:`compile_stats` copy per decode step would be wasteful)."""
    out: Dict[str, Tuple[float, float]] = {}
    with _stats_lock:
        for name, rec in _stats.items():
            for entry in reversed(rec["history"]):
                if "flops" in entry or "bytes_accessed" in entry:
                    out[name] = (float(entry.get("flops", 0.0)),
                                 float(entry.get("bytes_accessed", 0.0)))
                    break
    return out


def compile_stats() -> List[Dict[str, Any]]:
    """The process-wide compile ledger, per fn name — the ``compiles``
    block bench.py embeds, and the raw material for a recompile
    post-mortem (which signature, when, how long). Instances sharing a
    name (one prefill builder per length bucket, one step per optimizer
    run) merge; ``recompiles`` sums per-instance alarms, so a merged
    count stays consistent with ``bigdl_xla_recompiles_total``."""
    with _stats_lock:
        return [{"fn": rec["fn"], "compiles": rec["compiles"],
                 "recompiles": rec["recompiles"],
                 "history": [dict(h) for h in rec["history"]]}
                for name, rec in sorted(_stats.items())]
