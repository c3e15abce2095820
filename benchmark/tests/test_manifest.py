"""CPU checks of the benchmark's own files: the manifest's strings and
cross-references, the traffic generator, the metric arithmetic and the
trace reduction. ``python3 -m pytest benchmark/tests -q`` (seconds)."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark import stats, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
MAN = mf.load()
E2E = {m["name"]: m for m in MAN["end_to_end"]}
CELLS = [w["name"] for w in MAN["workloads"]]


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert isinstance(MAN["run_seconds"], int) and 10 <= MAN["run_seconds"] <= 51
    assert MAN["paths"] == ["benchmark"]
    assert all(_line(w) for w in MAN["command"]) and len(MAN["command"]) <= 32
    assert len(json.dumps(MAN)) < 64 * 1024


def test_identifiers_are_name_safe():
    names = []
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names += [m["layer"], m["moves"]]
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert all(c in CELLS for c in m.get("workloads", CELLS)), m
    bad = [n for n in names if not NAME.match(n)]
    assert not bad, bad
    for group in ("configs", "workloads"):
        got = [e["name"] for e in MAN[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_files_are_found_by_name():
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "drivers", conf["driver"] + ".py"))
    for w in MAN["workloads"]:
        assert os.path.exists(os.path.join(ROOT, mf.traffic_path(w["traffic"])))
        mf.config_of(MAN, w)
    for m in MAN["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, mf.reader_path(m["name"])))
        assert callable(mf.reader_of(m["name"]).read)


def test_every_cell_reports_enough_and_moves_resolve():
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    for cell in CELLS:
        e2e = {m["name"] for m in mf.metrics_for(MAN, "end_to_end", cell)}
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert mf.metrics_for(MAN, "per_layer", cell), cell
    for m in MAN["per_layer"]:
        assert m["moves"] in E2E and m["moves"] != "setup_s", m
        target = E2E[m["moves"]].get("workloads", CELLS)
        assert all(c in target for c in m.get("workloads", CELLS)), m
    layers = {}
    for m in MAN["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_chips():
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MAN["workloads"]) // 4)


# --- traffic ---------------------------------------------------------------

OPEN = {"loop": "open", "rate_per_s": 2.0, "lead_in_s": 10,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                   "min": 32, "max": 1536},
        "output": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                   "min": 16, "max": 256}}


def test_traffic_is_deterministic_in_the_seed():
    big = 2 ** 31 + 12345
    a = traffic.requests(OPEN, big, 40, 32000)
    b = traffic.requests(OPEN, big, 40, 32000)
    c = traffic.requests(OPEN, 7, 40, 32000)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new"] == y["max_new"] and x["due"] == y["due"]
               for x, y in zip(a, b)) and len(a) == len(b)
    assert any(len(x["prompt"]) != len(y["prompt"]) for x, y in zip(a, c))
    assert not np.array_equal(a[0]["prompt"][:16], c[0]["prompt"][:16])
    # every seed: the same sizes and the same gaps in another order, the
    # window [lead_in, lead_in + seconds) holding rate * seconds of them
    lead = OPEN["lead_in_s"]
    win = lambda rs: [r for r in rs if r["due"] >= lead]
    assert len(win(a)) == len(win(c)) == 80
    assert len(a) == len(c) == 100
    assert win(a)[0]["due"] == lead and a[0]["due"] == 0
    assert all(0 <= r["due"] < lead + 40 for r in a)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    for part in (win, lambda rs: [r for r in rs if r["due"] < lead]):
        end = lead + 40 if part is win else lead
        prompts = lambda rs: sorted(len(r["prompt"]) for r in part(rs))
        outputs = lambda rs: sorted(r["max_new"] for r in part(rs))
        gaps = lambda rs: sorted(np.round(np.diff(
            [r["due"] for r in part(rs)] + [end]), 9))
        assert prompts(a) == prompts(c) and outputs(a) == outputs(c)
        assert gaps(a) == gaps(c)


def test_sizes_follow_the_file():
    g = traffic.quantile_grid(OPEN["prompt"], 1000)
    assert g.min() == 32 and g.max() == 1536
    assert abs(np.median(g) - 256) <= 2
    u = traffic.quantile_grid({"dist": "uniform", "min": 1024, "max": 1920},
                              100)
    assert 1024 <= u.min() and u.max() <= 1920 and abs(u.mean() - 1472) < 2
    assert traffic.prefill_buckets(OPEN, 16) == [32, 64, 128, 256, 512,
                                                 1024, 2048]
    gaps = traffic.exponential_gaps(100, 50.0)
    assert abs(gaps.sum() - 50.0) < 1e-9 and gaps.min() > 0
    with pytest.raises(ValueError):
        traffic.requests({**OPEN, "loop": "closed"}, 1, 10, 100)


# --- arithmetic ------------------------------------------------------------

def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_metrics_by_name():
    s = {"ttft": [30.0, 10.0, 20.0, 40.0], "itl": list(range(1, 101))}
    assert stats.named("ttft_p50_ms", s) == 20.0
    assert stats.named("ttft_p90_ms", s) == 40.0
    assert stats.named("ttft_mean_ms", s) == 25.0
    assert stats.named("itl_p95_ms", s) == 95
    for bad in ("served_tok_s", "ttft_p100_ms", "e2e_p50_ms", "ttft_p50"):
        with pytest.raises(KeyError):
            stats.named(bad, s)


def test_gaps_count_when_they_close_inside_the_window():
    stamps = [0.9, 1.0, 1.5, 1.5, 2.1, 3.5]
    assert stats.gaps_in_window(stamps, 1.0, 3.0) == \
        pytest.approx([0.1, 0.5, 0.0, 0.6])
    assert stats.gaps_in_window([1.2], 1.0, 3.0) == []


# --- trace reduction -------------------------------------------------------

def test_short_op_names():
    f = trace_reduce.short_op
    assert f("%_int4_matmul_jit.34 = f32[16,28672]{1,0:T(8,128)S(1)} "
             "custom-call(bf16[16,2048]{1,0} %bitcast.255)") == \
        "_int4_matmul_jit[16x28672]"
    assert f("%copy.125 = bf16[32,2049,8,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
             "copy(bf16[32,2049,8,16,128]{4,2,3,1,0} %fusion.15)") == \
        "copy[32x2049x8x16x128]"
    assert f("%dynamic-slice_bitcast_fusion.24.remat2 = u8[2048,28672]{1,0} "
             "fusion(u8[32,2048,28672]{2,1,0} %p)") == \
        "dynamic-slice_bitcast_fusion[2048x28672]"
    assert f("%while.3 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1}) "
             "while(%tuple)") == "while[]"
    assert trace_reduce.module_name("jit_step(5602396268051664368)") == \
        "jit_step"


def test_interval_arithmetic():
    iv = [(0, 10), (5, 20), (30, 40), (32, 35)]
    assert trace_reduce.union_seconds(iv) == pytest.approx(30e-9)
    assert trace_reduce.idle_gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    # a while spanning two ops: its exclusive time is what they leave
    ev = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"), (100, 120, "c")]
    own = trace_reduce.self_times(ev)
    assert [own[i] for i in range(4)] == [30, 30, 40, 20]


def test_reduce_the_recorded_trace():
    """``recorded.xplane.pb``: a short slice of mistral7b_chat_steady on
    one v5e (PR 24), kept small. Busy time can never pass the slice, the
    decode program is in it, and exclusive op times add up to busy."""
    path = os.path.join(os.path.dirname(__file__), "recorded.xplane.pb")
    r = trace_reduce.reduce(path, n_devices=1)
    assert 0 < r["busy_s"] <= r["window_s"]
    d0 = r["devices"][0]
    assert any(k.startswith("jit_") for k in d0["modules"])
    assert sum(d0["ops"].values()) == pytest.approx(d0["busy_s"], rel=0.02)
    b = trace_reduce.breakdown(r)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and s >= 0 for n, s in b["device_ops"])
