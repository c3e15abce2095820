"""CPU checks of what the ``mimo_v25_mixedlen_steady`` cell adds to the
benchmark: the byte functions against ISSUE 31's table made by hand from
the published widths, ``reduced`` / ``published`` against the catalog's
values, the five readers on hand-built runs, the traffic file's
quantiles, the planted faults by name and at the rehearsal widths, and
one rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check_mimo, faults_mimo  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark import peaks_mimo as pk  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.drivers import serve_deepseek, serve_mimo  # noqa: E402

CELL = "mimo_v25_mixedlen_steady"
NEW = ("full_decode_roofline", "window_decode_roofline",
       "held_experts_ffn_roofline", "hybrid_decode_step_roofline.itl",
       "window_pages_per_row.itl")
MAN = mf.load()
CONFIG = mf.config_of(MAN, mf.cell(MAN, CELL))
MODEL = serve_mimo.model_config(CONFIG, {})
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_bytes_are_the_published_widths_by_hand():
    # ISSUE 31's table. Full attention: q 4096 x 12288, k 4096 x 768,
    # v 4096 x 512, o 8192 x 4096 = 89.1 M; window: k and v twice that
    assert pk.attention_bytes(MODEL, 0) == 2 * (
        50_331_648 + 3_145_728 + 2_097_152 + 33_554_432) == 178_257_920
    assert pk.attention_bytes(MODEL, 1) == 2 * (
        50_331_648 + 6_291_456 + 4_194_304 + 33_554_432) == 188_743_680
    assert pk.expert_bytes(MODEL) == 2 * 3 * 4096 * 2048 == 50_331_648
    assert pk.router_bytes(MODEL) == 2 * 4096 * 256 + 4 * 256 == 2_098_176
    assert pk.dense_ffn_bytes(MODEL) == 2 * 3 * 4096 * 16384 == 402_653_184
    assert pk.head_bytes(MODEL) == 2 * 152_576 * 4096 == 1_249_902_592
    assert pk.cached_token_bytes(MODEL, 0) == 2 * 4 * 320 == 2_560
    assert pk.cached_token_bytes(MODEL, 1) == 2 * 8 * 320 == 5_120
    # attention linears 1.30 GB, layer 0's FFN 0.40, the head 1.25
    assert pk.fixed_step_bytes(MODEL) == (
        2 * 178_257_920 + 5 * 188_743_680 + 402_653_184 + 1_249_902_592)
    # the issue's step: 16 rows of 8k cached tokens, 6.5 of 16 experts
    # touched in each of the 6 expert layers: about 5.6 GB
    step = pk.decode_steps_bytes(MODEL, 1, 6 * 6.5, 16 * 8000, 16 * 128)
    by_hand = (pk.fixed_step_bytes(MODEL) + 39 * 50_331_648
               + 6 * 2_098_176 + 128_000 * 2 * 2_560 + 2_048 * 5 * 5_120)
    assert step == by_hand and 5.5e9 < step < 5.7e9
    # what the chip holds: 9.05 GB of weights
    assert 9.04e9 < pk.held_weight_bytes(MODEL) < 9.06e9
    # 32k tokens of one request: 168 MB of full-class rows, 3.7 MB of
    # window-class rows if bounded, 839 MB if each window layer owned a
    # full-length chain
    assert pk.class_read_bytes(MODEL, 0, 32_768) == 167_772_160
    assert 3.6e6 < pk.class_read_bytes(MODEL, 1, 144) < 3.8e6
    assert 838e6 < pk.class_read_bytes(MODEL, 1, 32_768) < 840e6


def test_reduced_and_published_are_the_catalogs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    pub = row["config"]
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                                 "moe_layer_freq", "n_routed_experts"]
    assert CONFIG["published"] == {k: pub[k] for k in CONFIG["reduced"]}
    for k, v in pub.items():
        if k not in CONFIG["reduced"]:
            assert CONFIG[k] == v, k
    # the cut: layer 0 and the whole period that follows it, 16 of 256
    assert CONFIG["hybrid_layer_pattern"] == pub["hybrid_layer_pattern"][:7]
    assert CONFIG["moe_layer_freq"] == pub["moe_layer_freq"][:7]
    assert (MODEL.num_hidden_layers, MODEL.n_routed_experts,
            MODEL.experts_held, MODEL.first_expert) == (7, 256, 16, 0)
    assert (MODEL.hidden_size, MODEL.num_attention_heads,
            MODEL.num_key_value_heads, MODEL.swa_num_key_value_heads,
            MODEL.head_dim, MODEL.v_head_dim, MODEL.sliding_window,
            MODEL.rotary_dim, MODEL.rope_theta, MODEL.swa_rope_theta,
            MODEL.attention_value_scale, MODEL.intermediate_size,
            MODEL.moe_intermediate_size, MODEL.num_experts_per_tok,
            MODEL.vocab_size) == (
        4096, 64, 4, 8, 192, 128, 128, 64, 1e7, 1e4, 0.707, 16384, 2048, 8,
        152576)
    assert "16 chips" in CONFIG["assumed"]["deployment"]
    assert "stages of 7 layers" in CONFIG["assumed"]["deployment"]
    # the pools the engine builds hold what the file says
    full, window = (c for c in __import__(
        "bigdl_tpu.llm.models.mimo", fromlist=["x"]).page_classes(MODEL))
    e = CONFIG["engine"]
    assert (full.layers, full.kv_heads, full.k_width) == (2, 4, 384)
    assert (window.layers, window.kv_heads, window.keeps) == (5, 8, 128)
    assert 4.2e9 < 2 * e["num_pages"] * 4 * 16 * 384 * 2 < 4.3e9


def _run(ops=None, counters=None, steps=100, step_s=0.012):
    """A hand-built run: ``steps`` decode steps of ``step_s`` device
    seconds in a 4 s slice, 16 rows of 8k cached tokens, 6.5 experts
    touched an expert layer."""
    slice_counters = {"moe_layer_steps_total": 6 * steps,
                      "moe_experts_touched_total": 39 * steps,
                      "full_ctx_tokens_total": steps * 16 * 8000,
                      "window_ctx_tokens_total": steps * 16 * 128}
    if counters is not None:
        slice_counters = counters
    trace = {"window_s": 4.0, "busy_s": 3.9, "slice_counters": slice_counters,
             "devices": [{"busy_s": 3.9, "gaps": [],
                          "modules": {"jit_step": [steps, steps * step_s],
                                      "jit_build": [2, 0.9]},
                          "ops": ops if ops is not None else {
                              "jit_step:moe_expert_ffn[400x4096]":
                                  steps * 0.0030,
                              "jit_step:full_attention_decode_stats"
                              "[32x4x16x128]": steps * 0.0020,
                              "jit_step:window_attention_decode_stats"
                              "[32x8x8x128]": steps * 0.0004,
                              "jit_build:full_attention_prefill"
                              "[1x4x16384x128]": 0.2,
                              "jit_step:fusion[32x152576]": steps * 0.002}}]}
    return {"trace": trace, "model": MODEL, "config": CONFIG,
            "programs": {"decode": ["jit_step"],
                         "prefill_ragged": ["jit_build"]},
            "device": {"kind": "TPU v5 lite"},
            "counters": {"moe_layer_steps_total": 6000,
                         "moe_experts_touched_total": 6000 * 6.4,
                         "decode_rows_total": 16_000,
                         "window_pages_held_total": 256_000,
                         "passes": 1000, "host_seconds": 3.0}}


def test_readers_on_a_hand_built_run():
    run = _run()
    read = {n: mf.reader_of(n).read(run, n) for n in NEW}
    # 128,000 tokens x 2 layers x 2,560 B = 0.655 GB in 2 ms
    assert read["full_decode_roofline"] == pytest.approx(
        100 * 128_000 * 2 * 2560 / 819e9 / 0.002)
    assert 39 < read["full_decode_roofline"] < 41
    # 2,048 tokens x 5 layers x 5,120 B = 52 MB in 0.4 ms
    assert read["window_decode_roofline"] == pytest.approx(
        100 * 2048 * 5 * 5120 / 819e9 / 0.0004)
    assert 15 < read["window_decode_roofline"] < 17
    # 39 experts x 50.3 MB + 6 routers x 2.1 MB = 1.98 GB in 3 ms
    assert read["held_experts_ffn_roofline"] == pytest.approx(
        100 * (39 * 50_331_648 + 6 * 2_098_176) / 819e9 / 0.003)
    assert 80 < read["held_experts_ffn_roofline"] < 81
    # 5.64 GB in a 12 ms step
    assert read["hybrid_decode_step_roofline.itl"] == pytest.approx(
        100 * pk.decode_steps_bytes(MODEL, 1, 39, 128_000, 2048)
        / 819e9 / 0.012)
    assert 57 < read["hybrid_decode_step_roofline.itl"] < 58
    assert read["window_pages_per_row.itl"] == pytest.approx(16.0)
    assert all(0 < v <= 100 for n, v in read.items() if "roofline" in n)
    # the accepted readers the cell is appended to read this run too
    assert mf.reader_of("moe_experts_touched.itl").read(
        run, "moe_experts_touched.itl") == pytest.approx(6.4)
    assert mf.reader_of("decode_step_dev_ms.itl").read(
        run, "decode_step_dev_ms.itl") == pytest.approx(12.0)


@pytest.mark.parametrize("name", NEW[:4])
def test_roofline_readers_return_nothing_without_their_source(name):
    reader = mf.reader_of(name)
    assert reader.read(_run(ops={}), name) is None or name == NEW[3]
    assert reader.read(_run(counters={}), name) is None
    assert reader.read({**_run(), "trace": None}, name) is None
    # a program that is not this family's: Kanana's model and counters,
    # as on the parent commit and in its cell
    theirs = serve_deepseek.model_config(
        mf.config_of(MAN, mf.cell(MAN, "kanana2_longgen_steady")), {})
    other = {**_run(ops={"jit_step:moe_expert_ffn[2336x2048]": 1.0},
                    counters={"moe_layer_steps_total": 700,
                              "moe_experts_touched_total": 70_000,
                              "latent_ctx_tokens_total": 8_000_000}),
             "model": theirs}
    assert reader.read(other, name) is None


def test_pages_reader_returns_nothing_without_its_counters():
    run = _run()
    run["counters"] = {"passes": 10, "host_seconds": 1.0}
    assert mf.reader_of(NEW[4]).read(run, NEW[4]) is None


def test_manifest_entries_of_the_cell():
    cell = mf.cell(MAN, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "mixedlen_steady"
    assert len(MAN["workloads"]) == 3 and len(MAN["configs"]) == 3
    assert [m["name"] for m in mf.metrics_for(MAN, "end_to_end", CELL)] \
        == ["itl_p95_ms", "setup_s"]
    mine = [m["name"] for m in mf.metrics_for(MAN, "per_layer", CELL)]
    assert len(mine) == 13 and set(NEW) <= set(mine)
    assert not {"int4_decode_roofline", "moe_ffn_roofline",
                "mla_decode_roofline", "decode_step_roofline.itl"} & set(mine)
    assert tuple(m["name"] for m in MAN["per_layer"][-5:]) == NEW
    for m in MAN["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    entry = MAN["configs"][-1]
    assert entry["name"] == "mimo_v25_bf16_ep16"
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert all(len(x["why"]) <= 200 for x in (entry, cell))


def test_the_traffic_files_quantiles_and_order():
    mix = mf.traffic_of(mf.cell(MAN, CELL))
    assert mix["lead_in_s"] == 30 and mix["loop"] == "open"
    assert isinstance(mix["rate_per_s"], float) and "sweep" in mix["rate_why"]
    grid = traffic.quantile_grid(mix["prompt"], 1000)
    # a tenth under 1.2k, the median 4,096, a tenth over 14k
    assert 1100 < np.percentile(grid, 10) < 1200
    assert np.percentile(grid, 50) == pytest.approx(4096, rel=0.01)
    assert 14_000 < np.percentile(grid, 90) < 15_000
    assert grid.min() == 256 and grid.max() == 32_768
    outs = traffic.quantile_grid(mix["output"], 1000)
    assert outs.min() == 192 and outs.max() == 2048
    assert np.percentile(outs, 50) == pytest.approx(768, rel=0.01)
    assert traffic.prefill_buckets(mix, 16) == [
        256, 512, 1024, 2048, 4096, 8192, 16384, 32768]
    # one order for every seed; the seed draws the ids
    a, b = (serve_deepseek.scheduled_requests(mix, seed, 51, 152_576, 1.0)
            for seed in (3, 2 ** 31 + 7))
    for key in ("max_new", "due"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert not any((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # every request fits the engine's context and a pool's budget
    e = CONFIG["engine"]
    assert max(len(r["prompt"]) + r["max_new"] for r in a) <= \
        e["max_seq_len"] == 32_768 + 2_048
    assert max(len(r["prompt"]) for r in a) == 32_768


def test_the_planted_faults_are_the_issues():
    assert set(faults_mimo.FAULTS) == {
        "no_sink", "window_127", "window_129", "swa_theta_full",
        "rotary_all", "no_value_scale", "kv_heads_4_for_8",
        "window_row_next_slot", "ring_one_page_short", "experts_next_share",
        "renorm_over_held", "top7", "router_bf16"}
    assert len(faults_mimo.FAULTS) == 13
    with pytest.raises(ValueError, match="unknown fault"):
        with faults_mimo.planted("nothing", MODEL):
            pass


@pytest.fixture(scope="module")
def served():
    """``check_mimo.served_phase`` at the rehearsal widths: the driver's
    own check on a fresh engine, clean and with every fault of
    ``faults_mimo`` planted in the served program."""
    reh = CONFIG["rehearse"]
    cfg = serve_mimo.model_config(CONFIG, reh["model"])
    return check_mimo.served_phase(
        cfg, CONFIG, {**CONFIG["reference_check"], **reh["reference_check"]},
        {**CONFIG["engine"], **reh["engine"]}, 1, faults_mimo.FAULTS)


def test_the_clean_engine_passes_the_drivers_check(served):
    clean = served["clean"][0]
    assert clean["failed"] == [] and clean["rows_live_min"] >= 2
    assert clean["ctx_counters_agree"]
    assert clean["probe_distance_max"] < CONFIG["probe_distance_max"]


@pytest.mark.parametrize("fault,check", [
    ("no_sink", "d"), ("window_127", "d"), ("window_129", "d"),
    ("kv_heads_4_for_8", "d"), ("window_row_next_slot", "d"),
    ("ring_one_page_short", "d"), ("swa_theta_full", "a"),
    ("rotary_all", "a"), ("no_value_scale", "a"),
    ("experts_next_share", "a"), ("renorm_over_held", "a"), ("top7", "b"),
    ("router_bf16", "c")])
def test_a_planted_fault_fails_the_check_it_should(served, fault, check):
    """What lives only in the engine's path shows in (d): the probe
    through the served kernels, the cached rows read back, the logits
    rows. The program's arithmetic shows against the reference (a): the
    cached keys and values for the rotary's and the value scale, the
    logits rows for the experts. A missing assignment shows in the
    counters (b), a rounded router on the reference's inputs (c)."""
    assert check in served["faults_in_the_served_program"][fault]["failed"]
    assert served["ok"]


def test_rejudging_kept_readings_gives_the_same_verdicts(served):
    for got in [served["clean"][0],
                *served["faults_in_the_served_program"].values()]:
        verdict = serve_mimo.judge(got, CONFIG)
        assert [k for k in "dabc" if not verdict[k]] == got["failed"]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_cell(trace):
    """``--rehearse`` on the CPU at the tiny widths of the
    configuration's ``rehearse`` block: the same files and control
    flow, ``correct: true`` through the four checks, and no device
    value."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("BIGDL_TPU_OBSERVABILITY_ENABLED", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--trace", trace,
         "--seconds", "4", "--seed", str(2 ** 31 + 27)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in line["metrics"].values())
    if trace == "0":
        assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    else:       # no device trace on the CPU: the counter readers only
        assert {"moe_experts_touched.itl", "window_pages_per_row.itl"} \
            <= set(line["metrics"])
        assert not any("roofline" in n for n in line["metrics"])
    said = "\n".join(lines)
    assert "-> ok" in said and "FAILED" not in said
