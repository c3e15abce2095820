#!/usr/bin/env python
"""Bench regression diff — compare the latest two north-star records.

The driver appends one ``BENCH_r<N>.json`` per round whose ``tail``
field holds the JSON lines ``bench.py`` printed (the full record first,
the compact ``northstar_summary`` record last — the tail may be
truncated from the HEAD, which is exactly why the compact record is
printed last). This tool parses the newest two rounds, flattens every
numeric metric it can find, and prints per-metric deltas, warning when a
move exceeds the threshold (default 10%) — a throughput cliff between
rounds should be a red line in the log, not something a human spots by
eyeballing two JSON blobs.

CLI:
    python tools/bench_regress.py                 # ./BENCH_r*.json
    python tools/bench_regress.py --dir path --warn-pct 5 --json
    python tools/bench_regress.py --progress      # append one summary
                                                  # line to PROGRESS.jsonl

Library: ``compare_latest(dir)`` is embedded by ``bench.py`` as the
optional ``regress`` block of its output record.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional


def _bench_files(directory: str) -> List[str]:
    files = glob.glob(os.path.join(directory, "BENCH_r*.json"))

    def round_no(path: str) -> int:
        m = re.search(r"BENCH_r(\d+)\.json$", os.path.basename(path))
        return int(m.group(1)) if m else -1

    return sorted((f for f in files if round_no(f) >= 0), key=round_no)


def _json_objects(tail: str) -> List[dict]:
    """Every parseable JSON object among the tail's lines. Head
    truncation can leave the first line unparseable — skipped; a salvage
    pass then recovers the embedded ``{"metric": ...}`` sub-records
    (rounds before the compact tail record exist only in that form)."""
    out = []
    for line in tail.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict):
            out.append(obj)
    if not out:
        decoder = json.JSONDecoder()
        for m in re.finditer(r'\{"metric"', tail):
            try:
                obj, _ = decoder.raw_decode(tail, m.start())
            except ValueError:
                continue
            if isinstance(obj, dict):
                out.append(obj)
    return out


def _flatten_northstar(ns: dict) -> Dict[str, float]:
    flat: Dict[str, float] = {}
    for key, val in ns.items():
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[key] = float(val)
        elif isinstance(val, dict):
            for sub, sv in val.items():
                if sub in ("unit", "error"):
                    continue
                if isinstance(sv, (int, float)) \
                        and not isinstance(sv, bool):
                    name = key if sub == "v" else f"{key}.{sub}"
                    flat[name] = float(sv)
    return flat


# full-record metric names → the compact northstar keys, so rounds that
# predate the compact tail record (or whose compact line was truncated
# away) still diff against newer ones in one namespace
_ALIASES = {
    "resnet50_imagenet_train_throughput": "resnet_img_s",
    "bert_base_finetune_throughput": "bert",
    "llama2_7b_int4_prefill_4k": "prefill_4k",
    "lenet_convergence_top1": "lenet_top1",
    "cifar_resnet20_convergence_top1": "cifar_top1",
    "llama2_7b_int4_decode_throughput": "llama_b1",
    "llama_7b_paged_decode_step": "paged_b8",
}


def _canon(metric: str, extra: Optional[dict]) -> str:
    if metric == "llama2_7b_int4_decode_throughput" and \
            isinstance(extra, dict) and extra.get("batch") == 8:
        return "llama_b8"
    return _ALIASES.get(metric, metric)


# latency fields lifted out of each record's ``extra`` into their own
# ``<name>.<field>`` metrics: throughput can hold steady while per-step
# latency (ISSUE 4) or time-to-first-token (ISSUE 5's prefix cache)
# regresses, so the diff tracks them explicitly. (The prefix bench's
# TTFT pair rides the telemetry block, lifted separately below; this
# generic lift covers records that carry the field directly.)
_EXTRA_FIELDS = ("step_ms", "ttft_ms")


def _extra_field(extra: Optional[dict], field: str) -> Optional[float]:
    val = (extra or {}).get(field)
    return float(val) if isinstance(val, (int, float)) \
        and not isinstance(val, bool) else None


def _flatten_full(rec: dict) -> Dict[str, float]:
    """Top-level + embedded sub-record values, PLUS each record's
    ``extra.step_ms``/``extra.ttft_ms`` under ``<name>.<field>``."""
    flat: Dict[str, float] = {}
    if isinstance(rec.get("value"), (int, float)):
        name = _canon(rec.get("metric", "value"), rec.get("extra"))
        flat[name] = float(rec["value"])
        for field in _EXTRA_FIELDS:
            val = _extra_field(rec.get("extra"), field)
            if val is not None:
                flat[f"{name}.{field}"] = val
    for key, sub in (rec.get("extra") or {}).items():
        if isinstance(sub, dict) and \
                isinstance(sub.get("value"), (int, float)):
            name = _canon(sub.get("metric", key), sub.get("extra"))
            flat[name] = float(sub["value"])
            for field in _EXTRA_FIELDS:
                val = _extra_field(sub.get("extra"), field)
                if val is not None:
                    flat[f"{name}.{field}"] = val
    # ISSUE 5: the prefix microbench's TTFT pair lives in the full
    # record's telemetry block, not in a metric sub-record — lift it so
    # rounds diff TTFT even when the compact northstar line (which
    # carries the same pair as prefix_cache.ttft_*) was truncated away
    mb = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("microbench_prefix") or {})
    for mode in ("cache_off", "cache_on"):
        val = _extra_field(mb.get(mode), "ttft_ms")
        if val is not None:
            flat[f"prefix_{mode}.ttft_ms"] = val
    # ISSUE 6: the tier microbench's replay pair + the savings number —
    # a tier that silently stops fetching would show up as
    # tier_tokens_saved collapsing toward zero between rounds
    tb = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("microbench_tier") or {})
    for mode in ("tier_off", "tier_on"):
        val = _extra_field(tb.get(mode), "ttft_ms")
        if val is not None:
            flat[f"{mode}.ttft_ms"] = val
    for field in ("prefill_tokens_saved_vs_off", "ttft_speedup"):
        val = tb.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"tier.{field}"] = float(val)
    # ISSUE 13: the static-analysis gate's per-pass finding counts — a
    # pass whose total creeps up between rounds means new baselined (or
    # worse, about-to-be-baselined) findings; surface the drift next to
    # the perf metrics instead of inside a JSON blob nobody diffs
    sa = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("static_analysis") or {})
    for p, n in (sa.get("by_pass") or {}).items():
        if isinstance(n, (int, float)) and not isinstance(n, bool):
            flat[f"analysis.findings.{p}"] = float(n)
    for field in ("new", "suppressed", "stale_baseline"):
        val = sa.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"analysis.{field}"] = float(val)
    # ISSUE 14: the mixed-load microbench — the decode stream's ITL
    # p99 and the long admission's TTFT, split vs unified dispatch.
    # The headline keys (mixed.itl_p99_ms / mixed.ttft_ms) carry the
    # ON mode — the number serving actually pays once the gate ships —
    # and the off/on pairs keep the delta visible round over round
    xb = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("mixed_dispatch") or {})
    for mode in ("mixed_off", "mixed_on"):
        for field in ("itl_p99_ms", "ttft_ms"):
            val = _extra_field(xb.get(mode), field)
            if val is not None:
                flat[f"{mode}.{field}"] = val
    for field in ("itl_p99_ms", "ttft_ms"):
        val = _extra_field(xb.get("mixed_on"), field)
        if val is not None:
            flat[f"mixed.{field}"] = val
    # ISSUE 19: the self-speculative decode microbench — the headline
    # keys (spec.tokens_per_s / spec.accepted_per_tick / spec.speedup)
    # carry the ON mode and the on/off ratio; accept_rate drifting down
    # round over round means the proposer stopped matching (workload or
    # adaptive-k regression) even if tok/s hasn't moved yet
    sb = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("spec_decode") or {})
    for mode in ("spec_off", "spec_on"):
        for field in ("tokens_per_s", "itl_p99_ms"):
            val = _extra_field(sb.get(mode), field)
            if val is not None:
                flat[f"{mode}.{field}"] = val
    val = _extra_field(sb.get("spec_on"), "tokens_per_s")
    if val is not None:
        flat["spec.tokens_per_s"] = val
    for field, key in (("accepted_tokens_per_tick", "accepted_per_tick"),
                       ("accept_rate", "accept_rate"),
                       ("tokens_per_s_ratio", "speedup")):
        val = sb.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"spec.{key}"] = float(val)
    # ISSUE 12: the fleet telemetry plane's merged sketch percentiles —
    # client-visible tail latency through the federated router. A
    # regression in p99 TTFT or inter-token latency between rounds is
    # exactly the number the serving PRs are judged on, so it diffs
    # like any throughput metric (±10% warn, same alias machinery)
    fb = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("fleet") or {})
    for field in ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                  "itl_p99_ms"):
        val = fb.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"fleet.{field}"] = float(val)
    # ISSUE 15: the elastic-fleet soak — tail latency paid WHILE the
    # pool scales, plus the robustness invariants (requests_lost must
    # pin at 0; scale-event counts drifting to 0 means the autoscaler
    # stopped reacting)
    fe = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("fleet_elastic") or {})
    for field in ("ttft_p99_ms", "itl_p99_ms", "latency_p99_ms",
                  "requests_lost", "scale_outs", "scale_ins"):
        val = fe.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"fleet_elastic.{field}"] = float(val)
    # ISSUE 17: the priority-storm chaos pass — the TTFT pair is the
    # headline (interactive latency with the scheduler on vs the FIFO
    # baseline of the SAME storm; the on-number creeping toward the
    # off-number means preemption stopped buying anything), and the
    # robustness invariants pin at their contract values (lost 0,
    # parked 0, resumes == preemptions)
    pb = ((((rec.get("extra") or {}).get("telemetry") or {})
          .get("chaos_all") or {}).get("preempt") or {})
    for field, key in (("interactive_ttft_on_ms", "ttft_on_ms"),
                       ("interactive_ttft_off_ms", "ttft_off_ms"),
                       ("preemptions", "preemptions"),
                       ("resumes", "resumes"),
                       ("lost_requests", "lost_requests"),
                       ("parked", "parked")):
        val = pb.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"priority.{key}"] = float(val)
    # ISSUE 18: the time-series plane — windowed-store sampling cost
    # over the live post-bench registry (creeping up means snapshot
    # cost or metric cardinality regressed) and the alert transitions
    # the built-in burn-rate rules saw (nonzero means the bench round
    # itself tripped an SLO page)
    ab = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("alerts") or {})
    for field, key in (("sample_overhead_us", "ts.sample_overhead_us"),
                       ("transitions", "alerts.transitions")):
        val = ab.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[key] = float(val)
    # ISSUE 20: the OpenAI gateway — client-visible streaming TTFT
    # through the SSE leg and the gateway's translation+framing
    # overhead vs the native stream on the same prompts; the mismatch
    # tally drifting off 0 means the gateway stopped being a faithful
    # view of the engine
    ob = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("openai_api") or {})
    for field in ("ttft_direct_p50_ms", "ttft_gateway_p50_ms",
                  "gateway_overhead_ms", "output_mismatches"):
        val = ob.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"api.{field}"] = float(val)
    # ISSUE 16: the live roofline gauges sampled while the serving
    # microbenches ran — MFU or achieved HBM bandwidth drifting down
    # between rounds is a dispatch-efficiency regression even when
    # raw tok/s still sits inside the noise band
    ub = (((rec.get("extra") or {}).get("telemetry") or {})
          .get("utilization") or {})
    for field in ("mfu", "hbm_bw_gbps", "bw_util"):
        val = ub.get(field)
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            flat[f"util.{field}"] = float(val)
    return flat


def load_metrics(path: str) -> Dict[str, float]:
    """Flat {metric: value} from one BENCH_r*.json (or a raw bench.py
    output file). Prefers the compact northstar record (survives tail
    truncation); falls back to the full record's top-level values."""
    with open(path) as f:
        doc = json.load(f)
    objs = _json_objects(doc["tail"]) if isinstance(doc, dict) \
        and isinstance(doc.get("tail"), str) else \
        [doc] if isinstance(doc, dict) else []
    # union of BOTH name spaces: the full record's metric names (the
    # only form in pre-compact rounds / salvaged truncated tails) and
    # the compact northstar keys — the diff intersects whatever the two
    # rounds share
    flat: Dict[str, float] = {}
    for obj in objs:
        if "metric" in obj:
            flat.update(_flatten_full(obj))
    for obj in objs:
        ns = (obj.get("extra") or {}).get("northstar_summary")
        if isinstance(ns, dict):
            flat.update(_flatten_northstar(ns))
    return flat


def compare(base_path: str, head_path: str,
            warn_pct: float = 10.0) -> Dict[str, Any]:
    base = load_metrics(base_path)
    head = load_metrics(head_path)
    deltas: Dict[str, dict] = {}
    warned: List[str] = []
    for name in sorted(set(base) & set(head)):
        b, h = base[name], head[name]
        pct = (h - b) / abs(b) * 100.0 if b else None
        # a zero base has no percentage, but 0 -> N is never noise: a
        # pass gaining its first findings (analysis.findings.*), dense
        # staging reappearing from 0 — exactly the regressions the
        # zero-valued metrics exist to catch
        warn = (pct is not None and abs(pct) >= warn_pct) or \
            (b == 0 and h != 0)
        deltas[name] = {"base": b, "head": h,
                        "pct": round(pct, 2) if pct is not None else None,
                        "warn": warn}
        if warn:
            warned.append(name)
    return {"base": os.path.basename(base_path),
            "head": os.path.basename(head_path),
            "warn_pct": warn_pct, "deltas": deltas, "warned": warned,
            "only_base": sorted(set(base) - set(head)),
            "only_head": sorted(set(head) - set(base))}


def compare_latest(directory: str = ".", warn_pct: float = 10.0,
                   progress_path: Optional[str] = None
                   ) -> Optional[Dict[str, Any]]:
    """Diff the newest two rounds; None when fewer than two exist. When
    ``progress_path`` is given, one compact summary line is appended
    there (the PROGRESS.jsonl breadcrumb the ISSUE asks for)."""
    files = _bench_files(directory)
    if len(files) < 2:
        return None
    out = compare(files[-2], files[-1], warn_pct)
    if progress_path:
        line = {"ts": time.time(), "kind": "bench_regress",
                "base": out["base"], "head": out["head"],
                "metrics": len(out["deltas"]),
                "warned": out["warned"]}
        try:
            with open(progress_path, "a") as f:
                f.write(json.dumps(line) + "\n")
        except OSError:
            pass   # a read-only checkout must not fail the bench
    return out


def _print(out: Dict[str, Any]):
    print(f"bench regress: {out['base']} -> {out['head']} "
          f"(warn at ±{out['warn_pct']:g}%)")
    if not out["deltas"]:
        print("  no shared metrics")
        return
    name_w = max(len(n) for n in out["deltas"])
    for name, d in out["deltas"].items():
        pct = f"{d['pct']:+.1f}%" if d["pct"] is not None else "n/a"
        flag = "  << WARN" if d["warn"] else ""
        print(f"  {name:<{name_w}}  {d['base']:>12.4g} -> "
              f"{d['head']:>12.4g}  {pct:>8}{flag}")
    for name in out["only_head"]:
        print(f"  {name:<{name_w}}  (new in {out['head']})")
    for name in out["only_base"]:
        print(f"  {name:<{name_w}}  (gone since {out['base']})")


def _flag_value(argv: List[str], flag: str) -> Optional[str]:
    if flag not in argv:
        return None
    i = argv.index(flag)
    if i + 1 >= len(argv):
        print(f"{flag} needs a value", file=sys.stderr)
        raise SystemExit(2)
    return argv[i + 1]


def main(argv: List[str]) -> int:
    directory = _flag_value(argv, "--dir") or "."
    warn = _flag_value(argv, "--warn-pct")
    warn_pct = float(warn) if warn is not None else 10.0
    progress = os.path.join(directory, "PROGRESS.jsonl") \
        if "--progress" in argv else None
    out = compare_latest(directory, warn_pct, progress_path=progress)
    if out is None:
        print("need at least two BENCH_r*.json rounds to diff",
              file=sys.stderr)
        return 1
    if "--json" in argv:
        print(json.dumps(out))
    else:
        _print(out)
    return 2 if out["warned"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
