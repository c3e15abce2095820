"""CPU checks of what the ``brumby14b_reasoning_steady`` cell adds to the
benchmark: the byte and operation functions against ISSUE 33's sizing
made by hand from the published widths, ``reduced`` / ``published``
against the catalog's values, the four readers on hand-built runs, the
traffic file's quantiles, the planted faults by name and at the
rehearsal widths, and one rehearsal of the cell end to end. Every entry
of the manifest is looked up by its name, never by its place."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import check_brumby, faults_brumby  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark import peaks_brumby as pk  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.drivers import serve_brumby, serve_deepseek  # noqa: E402

CELL = "brumby14b_reasoning_steady"
CONFIG_NAME = "brumby14b_bf16_pp5"
NEW = ("retention_decode_roofline", "retention_prefill_roofline",
       "retention_decode_step_roofline.itl", "state_slots_per_row.itl")
SHARED = ("engine_host_ms.itl", "decode_step_dev_ms.itl",
          "prefill_dev_tok_s", "device_idle_pct.itl",
          "engine_pass_host_ms.itl", "engine_admit_ms.itl",
          "engine_itl_p95_ms.itl")
MAN = mf.load()
CONFIG = mf.config_of(MAN, mf.cell(MAN, CELL))
MODEL = serve_brumby.model_config(CONFIG, {})
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_bytes_are_the_published_widths_by_hand():
    # ISSUE 33's sizing. q 5120 x 5120, k and v 5120 x 1024, o 5120 x
    # 5120, the FFN 3 x 5120 x 17408 (bfloat16); the gate 5120 x 8 and
    # its bias (float32); two norms of 5120 and two of 128
    assert pk.layer_bytes(MODEL) == 2 * (
        26_214_400 + 2 * 5_242_880 + 26_214_400 + 267_386_880
        + 2 * 5120 + 2 * 128) + 4 * (40_960 + 8) == 660_787_744
    assert pk.head_bytes(MODEL) == 2 * 151_936 * 5120 == 1_555_824_640
    # the state: 8 heads x (128 + 1) x 8,320 float32, 34.3 MB a row and
    # layer one way (8,256 products a head's row, held in 65 tiles)
    assert pk.state_width(MODEL) == 8320 == 128 * 129 // 2 + 64
    assert pk.state_bytes_a_row_layer(MODEL) == 8 * 129 * 8320 * 4 \
        == 34_344_960
    assert pk.state_bytes_moved(MODEL, 1, 1) == 68_689_920
    # a step: 8 layers 5.29 GB + the head 1.56 GB = 6.84 GB fixed
    assert pk.fixed_step_bytes(MODEL) == 8 * 660_787_744 + 1_555_824_640
    assert 6.83e9 < pk.fixed_step_bytes(MODEL) < 6.85e9
    # at 15 rows the state is 8.24 of 15.1 GB: 55 % of the step
    step = pk.decode_steps_bytes(MODEL, 1, 15)
    assert step == pk.fixed_step_bytes(MODEL) + 15 * 8 * 68_689_920
    assert 0.54 < 15 * 8 * 68_689_920 / step < 0.56
    # what the chip holds: 8.40 GB of weights, 5.77 GB of state arrays
    # at 20 slots and the trash row
    assert 8.39e9 < pk.held_weight_bytes(MODEL) < 8.41e9
    assert pk.state_held_bytes(MODEL, 20) == 21 * 8 * 34_344_960
    assert 5.76e9 < pk.state_held_bytes(MODEL, 20) < 5.78e9
    # the engine's own count is the same function of the same widths
    from bigdl_tpu.llm.models import brumby
    assert brumby.state_bytes_a_row(MODEL) == pk.state_bytes_moved(MODEL, 1)
    assert brumby.host_step_stats(MODEL, np.zeros(15))[
        "state_bytes_moved_total"] == pk.state_bytes_moved(MODEL, 15)
    # a prefill chunk of 1,024: the band and its values 2 x 256 a pair,
    # the state read for 5 query heads, the keys folded: 110 GFLOP
    flops = pk.prefill_chunk_flops(MODEL, 1024)
    assert flops == 2 * 8 * (5 * 1024 * 256 * 256
                             + 5 * 1024 * 8320 * 128 + 1024 * 8320 * 128)
    assert 1.09e11 < flops < 1.11e11
    from bigdl_tpu.llm.kernels import retention
    assert retention.decode_bytes(15, 8, 128, 128) \
        == pk.state_bytes_moved(MODEL, 15, 1)


def test_reduced_and_published_are_the_catalogs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    pub = row["config"]
    assert CONFIG["source"] == row["source_url"]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 40} \
        == {k: pub[k] for k in CONFIG["reduced"]}
    for k, v in pub.items():
        if k not in CONFIG["reduced"]:
            assert CONFIG[k] == v, k
    assert (MODEL.num_hidden_layers, MODEL.hidden_size,
            MODEL.num_attention_heads, MODEL.num_key_value_heads,
            MODEL.head_dim, MODEL.intermediate_size, MODEL.vocab_size,
            MODEL.rope_theta, MODEL.rms_norm_eps,
            MODEL.max_position_embeddings, MODEL.group) == (
        8, 5120, 40, 8, 128, 17408, 151936, 1e6, 1e-6, 32768, 5)
    assumed = CONFIG["assumed"]
    assert "five pipeline stages of eight layers" in assumed["deployment"]
    for item in ("power", "gate", "normaliser", "qk_norm_and_rotary",
                 "state", "weights"):
        assert item in assumed
    assert "8,320" in assumed["state"] and "float32" in assumed["state"]
    # the state class the engine builds holds what the file says
    (state,) = __import__("bigdl_tpu.llm.models.brumby",
                          fromlist=["x"]).page_classes(MODEL)
    assert (state.layers, state.heads, state.rows, state.width,
            state.dtype) == (8, 8, 8320, 128, "float32")
    e = CONFIG["engine"]
    assert e == {"max_batch": 20, "max_seq_len": 32768, "page_size": 16}
    assert state.slot_bytes * (1 + e["max_batch"]) \
        == pk.state_held_bytes(MODEL, 20)


def _run(ops=None, counters=None, steps=100, step_s=0.025, rows=15):
    """A hand-built run: ``steps`` decode steps of ``step_s`` device
    seconds in a 6 s slice, ``rows`` rows live, and 3 prefill chunks a
    layer."""
    slice_counters = {
        "state_layer_steps_total": 8 * steps,
        "state_rows_total": rows * steps,
        "state_bytes_moved_total": rows * steps * 8 * 68_689_920,
        "prefill_state_chunks_total": 3 * 8,
        "prefill_state_positions_total": 3 * 8 * 1024,
        "prefill_tokens": 3000}
    if counters is not None:
        slice_counters = counters
    trace = {"window_s": 6.0, "busy_s": 5.9, "slice_counters": slice_counters,
             "devices": [{"busy_s": 5.9, "gaps": [],
                          "modules": {"jit_step": [steps, steps * step_s],
                                      "jit_build": [2, 0.9]},
                          "ops": ops if ops is not None else {
                              "jit_step:retention_decode[20x8x8x128]":
                                  steps * 0.0135,
                              "jit_build:retention_prefill_chunk"
                              "[8x5x1024x128]": 24 * 0.003,
                              "jit_step:fusion[20x151936]": steps * 0.002}}]}
    return {"trace": trace, "model": MODEL, "config": CONFIG,
            "programs": {"decode": ["jit_step"],
                         "prefill_ragged": ["jit_build"]},
            "device": {"kind": "TPU v5 lite"},
            "counters": {"decode_rows_total": 30_000,
                         "state_slots_held_total": 30_040,
                         "passes": 2000, "host_seconds": 3.0}}


def test_readers_on_a_hand_built_run():
    run = _run()
    read = {n: mf.reader_of(n).read(run, n) for n in NEW}
    # 15 rows x 8 layers x 68.7 MB = 8.24 GB in 13.5 ms
    assert read["retention_decode_roofline"] == pytest.approx(
        100 * 15 * 8 * 68_689_920 / 819e9 / 0.0135)
    assert 74 < read["retention_decode_roofline"] < 75
    # 24 chunk-layers x 110 GFLOP in 72 ms
    assert read["retention_prefill_roofline"] == pytest.approx(
        100 * 24 * pk.prefill_chunk_flops(MODEL, 1024) / 197e12 / 0.072)
    assert 18 < read["retention_prefill_roofline"] < 19
    # 15.1 GB in a 25 ms step
    assert read["retention_decode_step_roofline.itl"] == pytest.approx(
        100 * pk.decode_steps_bytes(MODEL, 1, 15) / 819e9 / 0.025)
    assert 73 < read["retention_decode_step_roofline.itl"] < 74
    assert read["state_slots_per_row.itl"] == pytest.approx(30_040 / 30_000)
    assert all(0 < v <= 100 for n, v in read.items() if "roofline" in n)
    # the accepted readers the cell is appended to read this run too
    assert mf.reader_of("decode_step_dev_ms.itl").read(
        run, "decode_step_dev_ms.itl") == pytest.approx(25.0)
    assert mf.reader_of("prefill_dev_tok_s").read(
        run, "prefill_dev_tok_s") == pytest.approx(3000 / 0.9)


@pytest.mark.parametrize("name", NEW[:3])
def test_roofline_readers_return_nothing_without_their_source(name):
    reader = mf.reader_of(name)
    assert reader.read(_run(ops={}), name) is None or name == NEW[2]
    assert reader.read(_run(counters={}), name) is None
    assert reader.read({**_run(), "trace": None}, name) is None
    # a program that is not this family's: Kanana's model and counters,
    # as in its cell (and a parent that has no state counters at all)
    theirs = serve_deepseek.model_config(
        mf.config_of(MAN, mf.cell(MAN, "kanana2_longgen_steady")), {})
    other = {**_run(ops={"jit_step:moe_expert_ffn[2336x2048]": 1.0},
                    counters={"moe_layer_steps_total": 700,
                              "moe_experts_touched_total": 70_000,
                              "latent_ctx_tokens_total": 8_000_000}),
             "model": theirs}
    assert reader.read(other, name) is None


def test_slots_reader_returns_nothing_without_its_counters():
    run = _run()
    run["counters"] = {"passes": 10, "host_seconds": 1.0,
                       "decode_rows_total": 5}
    assert mf.reader_of(NEW[3]).read(run, NEW[3]) is None


def test_manifest_entries_of_the_cell():
    cell = mf.cell(MAN, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "reasoning_steady"
    assert cell["config"] == CONFIG_NAME
    assert [w["name"] for w in MAN["workloads"]
            if w["config"] == CONFIG_NAME] == [CELL]
    assert [m["name"] for m in mf.metrics_for(MAN, "end_to_end", CELL)] \
        == ["itl_p95_ms", "setup_s"]
    mine = [m["name"] for m in mf.metrics_for(MAN, "per_layer", CELL)]
    assert sorted(mine) == sorted(NEW + SHARED)
    for name in NEW:
        m = _named(MAN["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
        assert os.path.exists(os.path.join(ROOT, mf.reader_path(name)))
    assert {_named(MAN["per_layer"], n)["layer"] for n in NEW[:2]} \
        == {"kernels"}
    assert _named(MAN["per_layer"], NEW[2])["layer"] == "programs"
    assert _named(MAN["per_layer"], NEW[3])["layer"] == "engine"
    assert _named(MAN["per_layer"], NEW[3])["source"] == "program_counter"
    for name in SHARED:
        assert CELL in _named(MAN["per_layer"], name)["workloads"]
    entry = _named(MAN["configs"], CONFIG_NAME)
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/brumby14b_bf16_pp5.json"
    assert all(len(x["why"]) <= 200 for x in (entry, cell))
    assert CONFIG["driver"] == "serve_brumby"
    # every limit of the comparison stands in the file with its reason
    for limit in serve_brumby.LIMITS:
        assert isinstance(CONFIG[limit], float), limit
        why = limit.replace("_max", "").replace("_sigma", "") + "_why"
        assert "chip runs" in CONFIG[why], why


def test_the_traffic_files_quantiles_and_order():
    mix = mf.traffic_of(mf.cell(MAN, CELL))
    assert mix["lead_in_s"] == 30 and mix["loop"] == "open"
    assert isinstance(mix["rate_per_s"], float) and "sweep" in mix["rate_why"]
    grid = traffic.quantile_grid(mix["prompt"], 1000)
    # a tenth under 750, the median 2,048, a tenth over 5.7k
    assert 700 < np.percentile(grid, 10) < 760
    assert np.percentile(grid, 50) == pytest.approx(2048, rel=0.01)
    assert 5600 < np.percentile(grid, 90) < 5800
    assert grid.min() == 256 and grid.max() == 16_384
    outs = traffic.quantile_grid(mix["output"], 1000)
    assert outs.min() == 256 and outs.max() == 3072
    assert np.percentile(outs, 50) == pytest.approx(1024, rel=0.01)
    assert 1100 < outs.mean() < 1160            # 1,130 tokens a mean answer
    assert traffic.prefill_buckets(mix, 16) == [
        256, 512, 1024, 2048, 4096, 8192, 16384]
    # one order for every seed; the seed draws the ids
    a, b = (serve_deepseek.scheduled_requests(mix, seed, 51, 151_936, 1.0)
            for seed in (3, 2 ** 31 + 7))
    for key in ("max_new", "due"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert not any((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert max(int(r["prompt"].max()) for r in a) > 150_000  # whole vocab
    # every request fits the model's context; no request needs a page
    assert max(len(r["prompt"]) + r["max_new"] for r in a) <= \
        CONFIG["engine"]["max_seq_len"] == 32_768
    # the traced slice holds a prefill (prefill_dev_tok_s reads it)
    t = CONFIG["trace"]
    opens = mix["lead_in_s"] + t["start_s"]
    assert sum(opens <= r["due"] < opens + t["slice_s"] for r in a) >= 2


def test_the_planted_faults_are_the_issues():
    assert set(faults_brumby.FAULTS) == {
        "no_gate", "power_1", "no_normaliser", "state_bf16",
        "slot_not_zeroed", "z_not_decayed", "no_rotary", "no_sqrt2"}
    assert len(faults_brumby.FAULTS) == 8
    with pytest.raises(ValueError, match="unknown fault"):
        with faults_brumby.planted("nothing", MODEL):
            pass


@pytest.fixture(scope="module")
def served():
    """``check_brumby.served_phase`` at the rehearsal widths: the
    driver's own check on a fresh engine, clean and with every fault of
    ``faults_brumby`` planted in the served program."""
    reh = CONFIG["rehearse"]
    cfg = serve_brumby.model_config(CONFIG, reh["model"])
    return check_brumby.served_phase(
        cfg, CONFIG, {**CONFIG["reference_check"], **reh["reference_check"]},
        {**CONFIG["engine"], **reh["engine"]}, 1, faults_brumby.FAULTS)


def test_the_clean_engine_passes_the_drivers_check(served):
    clean = served["clean"][0]
    assert clean["failed"] == [] and clean["rows_live_min"] > clean["company"]
    assert clean["counters_agree"] and clean["slot_seatings"] >= 2
    assert clean["probe_distance"] < CONFIG["probe_distance_max"]


@pytest.mark.parametrize("fault,check", [
    ("no_gate", "b"), ("power_1", "a"), ("no_normaliser", "a"),
    ("slot_not_zeroed", "b"), ("z_not_decayed", "b"), ("no_rotary", "b"),
    ("no_sqrt2", "b")])
def test_a_planted_fault_fails_the_check_it_should(served, fault, check):
    """What changes the sum the state holds shows in (b), the state
    against the one built from the reference's keys, values and gates
    (the gate, a slot's last occupant, the normaliser's decay, the
    rotary of the keys, the cross terms' weight); what changes how it
    is read shows against the reference's logits (a). (The bfloat16
    state does not show in a dozen positions at these widths: its test
    is the chip's, ``check_brumby.py``.)"""
    assert check in served["faults_in_the_served_program"][fault]["failed"]


def test_rejudging_kept_readings_gives_the_same_verdicts(served):
    for got in [served["clean"][0],
                *served["faults_in_the_served_program"].values()]:
        verdict = serve_brumby.judge(got, CONFIG)
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
        assert "state_slots_per_row.itl" in line["metrics"]
        assert not any("roofline" in n for n in line["metrics"])
    said = "\n".join(lines)
    assert "-> ok" in said and "FAILED" not in said
