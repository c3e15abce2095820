"""CPU checks of what the ``kanana2_longgen_steady`` cell adds to the
benchmark: the byte functions against sums made by hand from the
published widths, the four readers on hand-built runs, the traffic
file, and one rehearsal of the cell end to end."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check_deepseek, faults_deepseek  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark import peaks_deepseek as pk  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.drivers import serve_deepseek  # noqa: E402

CELL = "kanana2_longgen_steady"
NEW = ("moe_ffn_roofline", "mla_decode_roofline",
       "decode_step_roofline.itl", "moe_experts_touched.itl")
MAN = mf.load()
CONFIG = mf.config_of(MAN, mf.cell(MAN, CELL))
MODEL = serve_deepseek.model_config(CONFIG, {})


def test_bytes_are_the_published_widths_by_hand():
    # q 2048x6144, kv_a 2048x576, kv_b 512x8192, o 4096x2048: 26.35 M
    assert pk.mla_bytes(MODEL) == 2 * (
        12_582_912 + 1_179_648 + 4_194_304 + 8_388_608) == 52_690_944
    assert pk.expert_bytes(MODEL) == 2 * 3 * 2048 * 768 == 9_437_184
    assert pk.shared_expert_bytes(MODEL) == 2 * 3 * 2048 * 1536
    assert pk.router_bytes(MODEL) == 2 * 2048 * 128 + 4 * 128
    assert pk.dense_ffn_bytes(MODEL) == 2 * 3 * 2048 * 6144 == 75_497_472
    assert pk.head_bytes(MODEL) == 2 * 128_256 * 2048 == 525_336_576
    assert pk.latent_row_bytes(MODEL) == 1152
    # ISSUE 27's step: 32 rows of 2,500 cached tokens, 100 of 128
    # experts touched in each of the 7 expert layers: 8.4 to 8.6 GB
    step = pk.decode_steps_bytes(MODEL, 1, 7 * 100, 32 * 2500)
    by_hand = (8 * 52_690_944 + 75_497_472 + 525_336_576
               + 700 * 9_437_184 + 7 * (18_874_368 + 524_800)
               + 80_000 * 8 * 1152)
    assert step == by_hand and 8.4e9 < step < 8.6e9
    # what the issue's table holds here: 10.14 GB of weights
    held = (2 * 525_336_576 + 8 * 52_690_944 + 75_497_472
            + 7 * (128 * 9_437_184 + 18_874_368 + 524_288))
    assert 10.13e9 < held < 10.15e9


def _run(ops=None, counters=None, steps=100, step_s=0.020):
    """A hand-built run: ``steps`` decode steps of ``step_s`` device
    seconds in a 4 s slice."""
    slice_counters = {"moe_layer_steps_total": 7 * steps,
                      "moe_experts_touched_total": 7 * steps * 100,
                      "latent_ctx_tokens_total": steps * 32 * 2500}
    if counters is not None:
        slice_counters = counters
    trace = {"window_s": 4.0, "busy_s": 3.9, "slice_counters": slice_counters,
             "devices": [{"busy_s": 3.9, "gaps": [],
                          "modules": {"jit_step": [steps, steps * step_s],
                                      "jit_build": [2, 0.9]},
                          "ops": ops if ops is not None else {
                              "jit_step:moe_expert_ffn[2336x2048]":
                                  steps * 0.0105,
                              "jit_step:latent_attention_decode_stats"
                              "[32x32x512]": steps * 0.002,
                              "jit_build:moe_expert_ffn[41088x2048]": 0.2,
                              "jit_step:fusion[32x128256]": steps * 0.001}}]}
    return {"trace": trace, "model": MODEL, "config": CONFIG,
            "programs": {"decode": ["jit_step"],
                         "prefill_ragged": ["jit_build"]},
            "device": {"kind": "TPU v5 lite"},
            "counters": {"moe_layer_steps_total": 7000,
                         "moe_experts_touched_total": 7000 * 96.5,
                         "passes": 1000, "host_seconds": 3.0}}


def test_readers_on_a_hand_built_run():
    run = _run()
    read = {n: mf.reader_of(n).read(run, n) for n in NEW}
    # a step's experts: 700 x 9.437 MB + 7 x 19.4 MB = 6.742 GB in
    # 10.5 ms of kernel time: 8.23 ms at 819 GB/s
    assert read["moe_ffn_roofline"] == pytest.approx(
        100 * (700 * 9_437_184 + 7 * 19_399_168) / 819e9 / 0.0105)
    assert 78 < read["moe_ffn_roofline"] < 79
    # 80,000 tokens x 8 layers x 1,152 B = 0.737 GB in 2 ms
    assert read["mla_decode_roofline"] == pytest.approx(
        100 * 80_000 * 8 * 1152 / 819e9 / 0.002)
    assert 44 < read["mla_decode_roofline"] < 46
    # 8.50 GB in a 20 ms step
    assert read["decode_step_roofline.itl"] == pytest.approx(
        100 * pk.decode_steps_bytes(MODEL, 1, 700, 80_000) / 819e9 / 0.020)
    assert 51 < read["decode_step_roofline.itl"] < 53
    assert read["moe_experts_touched.itl"] == pytest.approx(96.5)
    assert all(0 < v <= 100 for n, v in read.items() if "roofline" in n)


@pytest.mark.parametrize("name", NEW[:3])
def test_roofline_readers_return_nothing_without_their_source(name):
    reader = mf.reader_of(name)
    assert reader.read(_run(ops={}), name) is None or name == NEW[2]
    assert reader.read(_run(counters={}), name) is None
    no_trace = {**_run(), "trace": None}
    assert reader.read(no_trace, name) is None
    # a program that is not this family's: another model object and no
    # such counters or ops, as on the parent commit
    other = _run(ops={"jit_step:_int4_matmul_jit[16x4096]": 1.0},
                 counters={"passes": 10, "host_seconds": 1.0})
    assert reader.read(other, name) is None


def test_touched_reader_returns_nothing_without_its_counters():
    run = _run()
    run["counters"] = {"passes": 10, "host_seconds": 1.0}
    assert mf.reader_of(NEW[3]).read(run, NEW[3]) is None


def test_manifest_entries_of_the_cell():
    cell = mf.cell(MAN, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longgen_steady"
    assert [m["name"] for m in mf.metrics_for(MAN, "end_to_end", CELL)] \
        == ["itl_p95_ms", "setup_s"]
    mine = [m["name"] for m in mf.metrics_for(MAN, "per_layer", CELL)]
    assert len(mine) == 11 and set(NEW) <= set(mine)
    assert "int4_decode_roofline" not in mine
    for m in MAN["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    entry = next(c for c in MAN["configs"]
                 if c["name"] == "kanana2_30b_a3b_bf16")
    assert entry["reduced"] == CONFIG["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"]
    assert (MODEL.num_hidden_layers, MODEL.n_routed_experts,
            MODEL.vocab_size) == (8, 128, 128256)


def test_the_window_holds_the_same_requests_for_every_seed():
    mix = mf.traffic_of(mf.cell(MAN, CELL))
    assert mix["lead_in_s"] == 30 and mix["loop"] == "open"
    a, b = (traffic.requests(mix, seed, 51, 128256)
            for seed in (3, 2 ** 31 + 7))
    n_lead = round(mix["rate_per_s"] * 30)
    assert len(a) == len(b) == n_lead + round(mix["rate_per_s"] * 51)
    for part in (slice(0, n_lead), slice(n_lead, None)):
        for key in ("max_new",):
            assert sorted(r[key] for r in a[part]) == \
                sorted(r[key] for r in b[part])
        assert sorted(len(r["prompt"]) for r in a[part]) == \
            sorted(len(r["prompt"]) for r in b[part])
    lens = [len(r["prompt"]) for r in a]
    outs = [r["max_new"] for r in a]
    assert 128 <= min(lens) and max(lens) <= 4096
    assert 256 <= min(outs) and max(outs) <= 2048
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]
    # (no prompt of the quantile grid falls in the 128 bucket)
    assert traffic.prefill_buckets(mix, 16) == [256, 512, 1024, 2048, 4096]
    # every request fits the engine's context
    assert max(len(r["prompt"]) + r["max_new"] for r in a) <= \
        CONFIG["engine"]["max_seq_len"]


def test_the_seed_draws_ids_and_not_the_order():
    """``order_seed`` fixes which request meets which; ``--seed`` draws
    the token ids (and, in the driver, the weights)."""
    mix = mf.traffic_of(mf.cell(MAN, CELL))
    a, b = (serve_deepseek.scheduled_requests(mix, seed, 51, 128256, 1.0)
            for seed in (3, 2 ** 31 + 7))
    for key in ("max_new", "due"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert not any((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    again = serve_deepseek.scheduled_requests(mix, 3, 51, 128256, 1.0)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))
    # the order is the generator's own shuffle of the file's number
    dealt = traffic.requests(mix, mix["order_seed"], 51, 128256)
    assert [r["max_new"] for r in dealt] == [r["max_new"] for r in a]
    # a mix without the key is dealt by --seed, as serve.py deals it
    free = {k: v for k, v in mix.items() if k != "order_seed"}
    c = serve_deepseek.scheduled_requests(free, 3, 51, 128256, 1.0)
    d = traffic.requests(free, 3, 51, 128256)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(c, d))


def test_row_distance_is_in_units_of_the_rows_spread():
    import numpy as np
    want = np.random.RandomState(0).randn(3, 1000) * 4.0
    assert serve_deepseek.row_distance(want, want).max() == 0
    off = want + 0.4 * np.random.RandomState(1).randn(3, 1000)
    assert np.allclose(serve_deepseek.row_distance(off, want), 0.1,
                       atol=0.01)


@pytest.fixture(scope="module")
def served():
    """``check_deepseek.served_phase`` at the rehearsal widths: the
    driver's own check on a fresh engine, clean and with every fault of
    ``faults_deepseek`` planted in the served program."""
    reh = CONFIG["rehearse"]
    cfg = serve_deepseek.model_config(CONFIG, reh["model"])
    return check_deepseek.served_phase(
        cfg, CONFIG, {**CONFIG["reference_check"], **reh["reference_check"]},
        {**CONFIG["engine"], **reh["engine"]},
        float(CONFIG["weights_back_gain"]), 1)


def test_the_clean_engine_passes_the_drivers_check(served):
    clean = served["clean"][0]
    assert clean["failed"] == [] and clean["rows_live_min"] >= 2
    assert clean["served_distance_median"] < \
        CONFIG["served_distance_median_max"]


@pytest.mark.parametrize("fault,check", [
    ("router_bf16", "c"), ("top5", "b"), ("weights_from_s_plus_b", "c"),
    ("latent_value_columns", "d"), ("latent_scale_padded", "d"),
    ("write_kv_next_slot", "d")])
def test_a_planted_fault_fails_the_check_it_should(served, fault, check):
    """The router's faults show in (c) router for router, a missing
    assignment in the counters (b), and what lives only in the engine's
    path in (d), served against dense: the kernel's value columns and
    scale in the logits rows, a row written one slot on in the cached
    rows read back."""
    assert check in served["faults_in_the_served_program"][fault]["failed"]


@pytest.mark.parametrize("fault", faults_deepseek.FAULTS)
def test_every_planted_fault_comes_out_not_correct(served, fault):
    """Even at these widths (8 experts, 3 layers) the driver's check,
    under the limits of the published widths, calls each of the nine
    ``correct: false``."""
    got = served["faults_in_the_served_program"][fault]
    assert got["failed"], got
    assert served["ok"]


def test_rejudging_kept_readings_gives_the_same_verdicts(served):
    for got in [served["clean"][0],
                *served["faults_in_the_served_program"].values()]:
        verdict = serve_deepseek.judge(got, CONFIG)
        assert [k for k in "dabc" if not verdict[k]] == got["failed"]
    looser = {**CONFIG, "served_distance_median_max": 10.0,
              "cached_row_distance_max": 10.0}
    got = served["faults_in_the_served_program"]["write_kv_next_slot"]
    assert serve_deepseek.judge(got, looser)["d"]


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
    assert line["device"]["memory_peak_bytes"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    if trace == "0":
        assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    else:       # no device trace on the CPU: the counter readers only
        assert "moe_experts_touched.itl" in line["metrics"]
        assert not any("roofline" in n for n in line["metrics"])
    said = "\n".join(lines)
    assert "-> ok" in said and "FAILED" not in said
