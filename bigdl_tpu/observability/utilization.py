"""Live roofline attribution (ISSUE 16 tentpole part 2).

BENCH computes MFU/bandwidth offline once per round, but the ROADMAP
decode-optimization items are justified by "decode is HBM-bandwidth
bound" — a claim the live system must be able to observe and alarm on.
The compile recorder already holds per-program ``cost_analysis()``
flops / bytes-accessed; this module multiplies them by *measured*
per-dispatch wall times sampled in the engine and optimizer hot loops
(reusing the existing drain-fence timestamps — no new device syncs) to
derive:

- ``bigdl_device_mfu`` — achieved flops / peak dense bf16 flops over a
  rolling window of sampled dispatches;
- ``bigdl_device_hbm_bw_gbps`` — achieved HBM traffic (bytes accessed
  per second) over the same window;
- ``bigdl_device_bw_util`` — that bandwidth as a fraction of the HBM
  peak;
- a per-program roofline table attached to ``GET /metrics/snapshot``
  (``"roofline"`` key) naming, for every sampled jit entry point, its
  achieved tflops / GB/s, utilization fractions and whether it sits on
  the memory or compute side of the machine-balance line.

Peak specs come from :data:`PEAK_SPECS` (the one table in the repo,
keyed by exact PJRT ``device_kind``; ``bench.py`` reads it too) and are
overridable via ``bigdl.device.peak.tflops`` / ``bigdl.device.peak.gbps``
(``0`` = look the device up). A TPU whose kind is not in the table is
an error, not a default; a non-TPU backend has no peaks.

Gated with the flight recorder (``bigdl.observability.flight.enabled``):
disabled means :func:`observe` is one attribute check, no window, no
``bigdl_device_*`` series, no snapshot key.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from bigdl_tpu.observability import compile_recorder, flight
from bigdl_tpu.utils.conf import conf

#: ``device_kind`` -> (peak dense bf16 TFLOP/s, peak HBM GB/s) per chip.
#: Keys are the exact strings PJRT reports (the spellings are those of
#: jax._src.pallas.mosaic.tpu_info); figures are from the Google Cloud
#: TPU documentation page of each generation ("TPU v5e": 197 TFLOP/s
#: bf16, 819 GB/s HBM). Only "TPU v5 lite" has been read back from a
#: device by this repo (chip_smoke.py, PR 21).
PEAK_SPECS: Dict[str, Tuple[float, float]] = {
    "TPU v2": (45.0, 700.0),
    "TPU v3": (123.0, 900.0),
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),     # v5e
    "TPU v5p": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),    # v6e / Trillium
}

#: Gauges are derived over the most recent N sampled dispatches, so a
#: long-idle engine converges to its *current* operating point instead
#: of a lifetime average; the roofline table keeps lifetime totals.
WINDOW = 1024

_lock = threading.Lock()
_window: deque = deque(maxlen=WINDOW)          # (fn, wall_s)
_totals: Dict[str, Dict[str, float]] = {}      # fn -> calls / wall_s
_ins: Optional[Dict[str, Any]] = None


def _device_kind() -> str:
    try:
        import jax
        d = jax.devices()[0]
        return getattr(d, "device_kind", "") or d.platform
    except Exception:
        return "unknown"


def peak_spec(device) -> Optional[Tuple[float, float]]:
    """(peak dense bf16 TFLOP/s, peak HBM GB/s) of ``device`` from
    :data:`PEAK_SPECS`; None for a non-TPU device. A TPU whose
    ``device_kind`` is not in the table raises: a ratio against a
    guessed peak is worse than none."""
    if device.platform != "tpu":
        return None
    try:
        return PEAK_SPECS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak figures for TPU device_kind "
            f"{device.device_kind!r}: add it to utilization.PEAK_SPECS "
            f"with its source (known: {sorted(PEAK_SPECS)})") from None


def peaks() -> Tuple[Optional[float], Optional[float]]:
    """(peak flop/s, peak HBM GB/s) for this platform, or None per axis
    on a non-TPU backend with no conf override — the ratio gauges are
    then suppressed rather than computed against an invented roofline."""
    tf = conf.get_float("bigdl.device.peak.tflops", 0.0) or 0.0
    gb = conf.get_float("bigdl.device.peak.gbps", 0.0) or 0.0
    peak_f = tf * 1e12 if tf > 0 else None
    peak_b = gb if gb > 0 else None
    if peak_f is not None and peak_b is not None:
        return peak_f, peak_b
    import jax
    spec = peak_spec(jax.devices()[0])
    if spec is not None:
        peak_f = peak_f if peak_f is not None else spec[0] * 1e12
        peak_b = peak_b if peak_b is not None else spec[1]
    return peak_f, peak_b


def _instruments() -> Optional[Dict[str, Any]]:
    global _ins
    from bigdl_tpu import observability as obs
    if not obs.enabled():
        return None
    if _ins is None:
        _ins = {
            "mfu": obs.gauge(
                "bigdl_device_mfu",
                "Achieved flops / peak dense bf16 flops over the recent "
                "sampled-dispatch window"),
            "bw": obs.gauge(
                "bigdl_device_hbm_bw_gbps",
                "Achieved HBM traffic (cost-analysis bytes accessed per "
                "wall second) over the recent sampled-dispatch window"),
            "bw_util": obs.gauge(
                "bigdl_device_bw_util",
                "Achieved HBM bandwidth as a fraction of the platform "
                "peak — the live decode-is-bandwidth-bound alarm"),
        }
    return _ins


def observe(fn: str, wall_s: float):
    """Attribute one dispatch of jit entry point ``fn`` (a name known
    to the compile ledger) to ``wall_s`` of measured wall time. Called
    from the engine drain path and the optimizer loop; one attribute
    check when the flight gate is off."""
    if not flight.enabled or wall_s <= 0.0:
        return
    with _lock:
        t = _totals.setdefault(fn, {"calls": 0, "wall_s": 0.0})
        t["calls"] += 1
        t["wall_s"] += wall_s
        _window.append((fn, wall_s))
    _update_gauges()


def _update_gauges():
    ins = _instruments()
    if ins is None:
        return
    with _lock:
        entries = list(_window)
    if not entries:
        return
    costs = compile_recorder.latest_costs()
    wall = flops = nbytes = 0.0
    for fn, w in entries:
        c = costs.get(fn)
        if c is None:
            continue   # no cost analysis for this program: unattributable
        wall += w
        flops += c[0]
        nbytes += c[1]
    if wall <= 0.0:
        return
    gbps = nbytes / wall / 1e9
    ins["bw"].set(gbps)
    peak_f, peak_b = peaks()
    if peak_f:
        ins["mfu"].set(flops / wall / peak_f)
    if peak_b:
        ins["bw_util"].set(gbps / peak_b)


def roofline_table() -> List[Dict[str, Any]]:
    """Lifetime per-program roofline rows, busiest first."""
    with _lock:
        totals = {fn: dict(t) for fn, t in _totals.items()}
    if not totals:
        return []
    costs = compile_recorder.latest_costs()
    peak_f, peak_b = peaks()
    rows: List[Dict[str, Any]] = []
    for fn, t in totals.items():
        calls = int(t["calls"])
        wall = t["wall_s"]
        c = costs.get(fn) or (0.0, 0.0)
        flops, nbytes = c[0] * calls, c[1] * calls
        row: Dict[str, Any] = {
            "fn": fn, "calls": calls, "wall_s": round(wall, 6),
            "flops_per_call": c[0], "bytes_per_call": c[1],
            "achieved_tflops": (round(flops / wall / 1e12, 4)
                                if wall > 0 else 0.0),
            "achieved_gbps": (round(nbytes / wall / 1e9, 3)
                              if wall > 0 else 0.0),
        }
        if wall > 0 and peak_f and flops:
            row["mfu"] = round(flops / wall / peak_f, 4)
        if wall > 0 and peak_b and nbytes:
            row["bw_util"] = round(nbytes / wall / 1e9 / peak_b, 4)
        if peak_f and peak_b and c[1]:
            # machine balance: flops-per-byte the chip can sustain;
            # programs below it are memory-bound on this platform
            balance = peak_f / (peak_b * 1e9)
            row["bound"] = ("compute" if c[0] / c[1] >= balance
                            else "memory")
        rows.append(row)
    rows.sort(key=lambda r: -r["wall_s"])
    return rows


def snapshot() -> Dict[str, Any]:
    """The ``"roofline"`` document attached to /metrics/snapshot and
    the bench telemetry ``utilization`` block."""
    peak_f, peak_b = peaks()
    rows = roofline_table()
    wall = sum(r["wall_s"] for r in rows)
    flops = sum(r["flops_per_call"] * r["calls"] for r in rows)
    nbytes = sum(r["bytes_per_call"] * r["calls"] for r in rows)
    out: Dict[str, Any] = {
        "device": _device_kind(),
        "peak_tflops": round(peak_f / 1e12, 1) if peak_f else None,
        "peak_gbps": round(peak_b, 1) if peak_b else None,
        "samples": len(_window),
        "wall_s": round(wall, 6),
        "hbm_bw_gbps": (round(nbytes / wall / 1e9, 3)
                        if wall > 0 else 0.0),
        "programs": rows,
    }
    if wall > 0 and peak_f and flops:
        out["mfu"] = round(flops / wall / peak_f, 4)
    if wall > 0 and peak_b and nbytes:
        out["bw_util"] = round(nbytes / wall / 1e9 / peak_b, 4)
    return out


def reset():
    """Clear samples and cached instruments — test isolation (wired
    into ``obs.reset()``)."""
    global _ins
    with _lock:
        _window.clear()
        _totals.clear()
        _ins = None


__all__ = [
    "PEAK_SPECS", "WINDOW", "observe", "peak_spec", "peaks", "reset",
    "roofline_table", "snapshot",
]
