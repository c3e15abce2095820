"""From the profiler's ``.xplane.pb`` to numbers (``ProfileData``, no
TensorBoard). Part of the yardstick: every PR reduces a trace the same
way.

A TPU device plane (``/device:TPU:<n>``) has the lines ``XLA Modules``
(one event per execution of a compiled program, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per HLO op,
named by its whole HLO line; a ``while`` or ``fusion`` event spans the
ops nested in it). The host plane (``/host:CPU``) has one line per
thread; ``bench/slice``, a ``TraceAnnotation`` the harness puts around
the traced slice, bounds what is reduced.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SLICE_SPAN = "bench/slice"
_HEAD = re.compile(r"^%?([^\s=]+)\s*=\s*\(*\s*([a-z0-9]+)\[([^\]]*)\]")
_NOISE = re.compile(r"\.(\d+|remat\d*|clone)(?=\.|$)")


def short_op(hlo: str) -> str:
    """``%_int4_matmul_jit.34 = f32[16,28672]{...} custom-call(...)`` ->
    ``_int4_matmul_jit[16x28672]``: the op's name without its numeric,
    ``.remat`` and ``.clone`` suffixes plus its (first) output shape."""
    m = _HEAD.match(hlo)
    if not m:
        return hlo.split(" ")[0].lstrip("%")[:64]
    name = m.group(1)
    while True:
        cut = _NOISE.sub("", name)
        if cut == name:
            break
        name = cut
    return f"{name}[{m.group(3).replace(',', 'x')}]"


def module_name(event_name: str) -> str:
    """``jit_step(5602396268051664368)`` -> ``jit_step``."""
    return event_name.split("(")[0]


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals (nanoseconds in,
    seconds out)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def idle_gaps(intervals: List[Tuple[float, float]], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The gaps of [lo, hi) that no interval covers."""
    gaps, edge = [], lo
    for s, e in sorted(intervals):
        if s > edge:
            gaps.append((edge, min(s, hi)))
        edge = max(edge, e)
        if edge >= hi:
            break
    if edge < hi:
        gaps.append((edge, hi))
    return gaps


def self_times(events: List[Tuple[float, float, str]]
               ) -> Dict[int, float]:
    """Exclusive nanoseconds of each event of one line, by index into
    ``events`` (start, end, name): its duration minus the events nested
    directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = {i: events[i][1] - events[i][0] for i in order}
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


@contextlib.contextmanager
def record():
    """Trace what runs inside the ``with`` block into a new directory
    under ``TMPDIR`` (yielded), with the ``bench/slice`` span around it.
    The Python tracer is off: it slows the host and the reduction does
    not read it."""
    import jax
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(SLICE_SPAN):
            yield tdir
    finally:
        jax.profiler.stop_trace()


def collect(tdir: str, n_devices: int) -> dict:
    """Reduce the trace :func:`record` left in ``tdir`` and remove it."""
    try:
        return reduce(find_xplane(tdir), n_devices=n_devices)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce(path: str, n_devices: Optional[int] = None) -> dict:
    """Reduce one ``.xplane.pb``. Returns::

        {"window_s": seconds of the bench/slice span (or of the device
                     events when there is none),
         "busy_s": union of device-op intervals, mean over devices,
         "devices": [{"busy_s", "modules": {name: [executions, seconds]},
                      "ops": {"<module>:<short op>": exclusive seconds},
                      "gaps": [[what the host did, seconds], ...]}]}

    Only events that START inside the slice count; ``n_devices`` keeps
    the first n device planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = {p.name: p for p in pd.planes}
    host_events: List[Tuple[float, float, str]] = []
    lo = hi = None
    host = planes.get("/host:CPU")
    if host is not None:
        for line in host.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == SLICE_SPAN:
                    lo, hi = s, e
                else:
                    host_events.append((s, e, ev.name))
    dev_names = sorted((n for n in planes if n.startswith("/device:TPU:")),
                       key=lambda n: int(n.rsplit(":", 1)[1]))
    if n_devices is not None:
        dev_names = dev_names[:n_devices]
    devices = []
    for name in dev_names:
        lines = {ln.name: ln for ln in planes[name].lines}
        mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in lines["XLA Ops"].events] \
            if "XLA Ops" in lines else []
        devices.append((mods, ops))
    if lo is None:
        starts = [s for mods, ops in devices for s, _, _ in mods + ops]
        ends = [e for mods, ops in devices for _, e, _ in mods + ops]
        if not starts:
            return {"window_s": 0.0, "busy_s": 0.0, "devices": []}
        lo, hi = min(starts), max(ends)
    host_events.sort()
    host_starts = [s for s, _, _ in host_events]
    out = []
    for mods, ops in devices:
        mods = sorted(m for m in mods if lo <= m[0] < hi)
        ops = [o for o in ops if lo <= o[0] < hi]
        spans = [(s, min(e, hi)) for s, e, _ in (ops or mods)]
        mod_acc: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        for s, e, n in mods:
            acc = mod_acc[module_name(n)]
            acc[0] += 1
            acc[1] += (e - s) / 1e9
        mod_starts = [m[0] for m in mods]
        op_acc: Dict[str, float] = defaultdict(float)
        for i, ns in self_times(ops).items():
            s, _, n = ops[i]
            k = bisect.bisect_right(mod_starts, s) - 1
            inside = k >= 0 and s < mods[k][1]
            prog = module_name(mods[k][2]) if inside else "-"
            op_acc[f"{prog}:{short_op(n)}"] += ns / 1e9
        gaps = sorted(idle_gaps(spans, lo, hi),
                      key=lambda g: g[0] - g[1])[:10]
        named = []
        for gs, ge in gaps:
            # the host event that overlaps this gap the longest
            best, best_ov = "no host event", 0.0
            k = bisect.bisect_left(host_starts, gs)
            for s, e, n in host_events[max(0, k - 64):]:
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > best_ov:
                    best, best_ov = n, ov
            named.append([best[:80], (ge - gs) / 1e9])
        out.append({"busy_s": union_seconds(spans),
                    "modules": {k: v for k, v in mod_acc.items()},
                    "ops": dict(op_acc), "gaps": named})
    busy = sum(d["busy_s"] for d in out) / len(out) if out else 0.0
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy, "devices": out}


def breakdown(reduced: dict) -> dict:
    """The ``breakdown`` of the result line, from device 0: the ten ops
    with most exclusive device time, and the ten longest idle gaps."""
    if not reduced["devices"]:
        return {"device_ops": [], "idle_gaps": []}
    d0 = reduced["devices"][0]
    top = sorted(d0["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": d0["gaps"]}
