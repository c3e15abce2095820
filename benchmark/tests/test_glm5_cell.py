"""CPU checks of what the ``glm5_longctx_steady`` cell adds to the
benchmark: the configuration's bytes by hand from the published widths
(7.82 GB of weights and 7,680 B a token), ``reduced`` /
``published`` against the source's values, the five readers on
hand-built runs, the traffic file, the planted faults by name, and one
rehearsal of the cell end to end. Every entry of the manifest is looked
up by its name, never by its place, and the cell is not held to a count
of metrics."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import faults_glm5  # noqa: E402
from benchmark import manifest as mf  # noqa: E402
from benchmark import peaks_glm5 as pk  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.drivers import serve_deepseek  # noqa: E402
from benchmark.drivers import serve_glm5 as drv  # noqa: E402
from benchmark.layers import _sparse_slice  # noqa: E402

CELL = "glm5_longctx_steady"
CONFIG_NAME = "glm5_bf16_ep16"
NEW = ("dsa_index_decode_roofline", "sparse_latent_decode_roofline",
       "dsa_select_dev_ms.itl", "dsa_decode_step_roofline.itl",
       "dsa_selected_pct.itl")
APPENDED = ("engine_host_ms.itl", "decode_step_dev_ms.itl",
            "prefill_dev_tok_s", "device_idle_pct.itl",
            "engine_pass_host_ms.itl", "engine_admit_ms.itl",
            "engine_itl_p95_ms.itl", "engine_stage_ms.itl",
            "engine_prefill_finish_ms.itl", "gaps_behind_prefill_pct.itl",
            "py_gc_ms.itl", "moe_experts_touched.itl", "moe_ffn_roofline")
MAN = mf.load()
CONFIG = mf.config_of(MAN, mf.cell(MAN, CELL))
MODEL = drv.model_config(CONFIG, {})
# GLM-5's published config.json (the configuration's ``source``): the
# values the cut changed, and the widths that no cut may change
PUBLISHED = {"num_hidden_layers": 78, "first_k_dense_replace": 3,
             "n_routed_experts": 256, "vocab_size": 154_880}
WIDTHS = {"hidden_size": 6144, "num_attention_heads": 64,
          "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
          "qk_rope_head_dim": 64, "v_head_dim": 256, "index_topk": 2048,
          "index_n_heads": 32, "index_head_dim": 128,
          "intermediate_size": 12288, "moe_intermediate_size": 2048,
          "num_experts_per_tok": 8, "n_shared_experts": 1,
          "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-05,
          "max_position_embeddings": 202_752, "model_type": "glm_moe_dsa",
          "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def test_bytes_are_the_published_widths_by_hand():
    # MLA: q_a 6,144 x 2,048, q_b 2,048 x 64 x 256, kv_a 6,144 x 576,
    # kv_b 512 x 64 x 448, o 16,384 x 6,144: 165.0 M parameters
    mla = 6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 64 * 448 \
        + 16384 * 6144
    assert mla == 165_019_648 and pk.mla_bytes(MODEL) == 2 * mla
    # the indexer: W_qI 2,048 x 4,096, W_kI 6,144 x 128, W_w 6,144 x 32
    idx = 2048 * 4096 + 6144 * 128 + 6144 * 32
    assert idx == 9_371_648 and pk.indexer_bytes(MODEL) == 2 * idx
    # the router 6,144 x 256 (1.57 M), an expert 3 x 6,144 x 2,048
    assert pk.router_bytes(MODEL) == 2 * 6144 * 256 + 4 * 256
    assert pk.expert_bytes(MODEL) == 2 * 3 * 6144 * 2048 == 75_497_472
    # 213.7 M outside the experts an expert layer, a dense layer 400.9 M
    outside = mla + idx + 6144 * 256 + 3 * 6144 * 2048
    assert 213.6e6 < outside < 213.8e6
    assert 400.8e6 < mla + idx + 3 * 6144 * 12288 < 401.0e6
    # the chip: layer 0, 4 expert layers of 16 held + 1 shared, 19,360
    # rows of the vocabulary twice: 0.802 + 4 x 1.635 + 0.476 = 7.82 GB
    w = pk.weight_bytes(MODEL)
    assert 7.81e9 < w < 7.83e9
    # what the engine draws is that, to the byte
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models import deepseek as ds
    shapes = jax.eval_shape(lambda: ds.init_params(MODEL, 0, jnp.bfloat16))
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(shapes)) == w
    # a token: 5 layers x (a 640-wide latent row + a 128-wide key) x 2 B
    assert pk.token_bytes(MODEL) == 5 * (1280 + 256) == 7680
    e = CONFIG["engine"]
    assert e == {"max_batch": 24, "max_seq_len": 53_248, "page_size": 16,
                 "num_pages": 48_001}
    pools = e["num_pages"] * 16 * pk.token_bytes(MODEL)
    assert 5.89e9 < pools < 5.91e9
    # fixed weights a step 2.75 GB; at ~18 rows of ~22k tokens, 7
    # experts touched a layer: index keys 0.51, selected rows 0.24 GB
    fixed = pk.fixed_step_bytes(MODEL)
    assert 2.74e9 < fixed < 2.76e9
    keys = 18 * 22_000 * 5 * 128 * 2
    rows = 18 * 2048 * 5 * 576 * 2
    assert 0.50e9 < keys < 0.52e9 and 0.20e9 < rows < 0.22e9
    step = pk.decode_steps_bytes(MODEL, 1, 4 * 7, keys, rows)
    assert step == fixed + 28 * 75_497_472 + keys + rows
    # the engine's counters are the same functions of the same widths
    stats = ds.host_step_stats(MODEL, np.full(18, 22_000, np.int64))
    assert stats["index_key_bytes_total"] == keys
    assert stats["sparse_latent_bytes_total"] == rows
    assert stats["index_ctx_tokens_total"] == 18 * 22_001 * 5
    assert stats["sparse_selected_tokens_total"] == 18 * 2048 * 5


def test_reduced_and_published_are_the_sources():
    assert CONFIG["source"] == _named(MAN["configs"], CONFIG_NAME)[
        "source"] == "https://huggingface.co/zai-org/GLM-5/blob/main/" \
        "config.json"
    assert CONFIG["reduced"] == _named(MAN["configs"], CONFIG_NAME)[
        "reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size"]
    assert CONFIG["published"] == PUBLISHED
    for k, v in WIDTHS.items():
        assert CONFIG[k] == v, k
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["n_routed_experts"], CONFIG["vocab_size"]) \
        == (5, 1, 16, 154_880 // 8)
    assert (MODEL.hidden_size, MODEL.num_attention_heads, MODEL.q_lora_rank,
            MODEL.kv_lora_rank, MODEL.qk_nope_head_dim,
            MODEL.qk_rope_head_dim, MODEL.v_head_dim, MODEL.index_topk,
            MODEL.index_n_heads, MODEL.index_head_dim,
            MODEL.n_routed_experts, MODEL.held_experts, MODEL.first_expert,
            MODEL.num_experts_per_tok, MODEL.n_shared_experts,
            MODEL.moe_intermediate_size, MODEL.intermediate_size,
            MODEL.routed_scaling_factor, MODEL.rope_theta,
            MODEL.rms_norm_eps) == (
        6144, 64, 2048, 512, 192, 64, 256, 2048, 32, 128, 256, 16, 0, 8, 1,
        2048, 12288, 2.5, 1e6, 1e-5)
    for item in ("indexer", "attention", "routing", "deployment",
                 "left_out", "cache", "weights"):
        assert item in CONFIG["assumed"]
    assert "Hadamard" in CONFIG["assumed"]["indexer"] \
        and "FP8" in CONFIG["assumed"]["indexer"]
    assert "multi-token-prediction" in CONFIG["assumed"]["left_out"]
    assert "16 chips" in CONFIG["assumed"]["deployment"]


def _stats(rows):
    from bigdl_tpu.llm.models import deepseek as ds
    s = ds.host_step_stats(MODEL, np.full(rows, 22_000, np.int64))
    return {**s, "moe_experts_touched_total": 4 * 7,
            "moe_assignments_total": rows * 4 // 2}


def _run(ops=None, counters=None, steps=200, step_s=0.015, rows=18):
    """A hand-built run: ``steps`` decode steps of ``step_s`` device
    seconds in a 6 s slice, ``rows`` rows live with contexts of 22,000,
    7 experts touched a layer."""
    stats = {k: v * steps for k, v in _stats(rows).items()} \
        if counters is None else counters
    trace = {"window_s": 6.0, "busy_s": 5.9, "slice_counters": stats,
             "devices": [{"busy_s": 5.9, "gaps": [],
                          "modules": {"jit_step": [steps, steps * step_s],
                                      "jit_build": [3, 2.0]},
                          "ops": ops if ops is not None else {
                              "jit_step:index_scores_decode[24x1x53248]":
                                  steps * 0.0012,
                              "jit_step:sparse_latent_attention[24x64x512]":
                                  steps * 0.0006,
                              "jit_step:sort[24x53248]": steps * 0.004,
                              "jit_step:sort[24x256]": steps * 0.0001,
                              "jit_step:sort[496]": steps * 0.0001,
                              "jit_step:moe_expert_ffn[496x6144]":
                                  steps * 0.004}}]}
    return {"trace": trace, "model": MODEL, "config": CONFIG,
            "programs": {"decode": ["jit_step"],
                         "prefill_ragged": ["jit_build"]},
            "device": {"kind": "TPU v5 lite"},
            "counters": {k: 51 / 6 * v for k, v in stats.items()}}


def test_readers_on_a_hand_built_run():
    run = _run()
    read = {m: mf.reader_of(m).read(run, m) for m in NEW}
    hbm = 819e9
    keys = 18 * 22_000 * 5 * 256
    rows = 18 * 2048 * 5 * 1152
    assert read["dsa_index_decode_roofline"] == pytest.approx(
        100 * keys / hbm / 0.0012)
    assert read["sparse_latent_decode_roofline"] == pytest.approx(
        100 * rows / hbm / 0.0006)
    # the selection's sort alone: not the routers' nor the dispatch's
    assert read["dsa_select_dev_ms.itl"] == pytest.approx(4.0)
    step = pk.fixed_step_bytes(MODEL) + 28 * pk.expert_bytes(MODEL) \
        + keys + rows
    assert read["dsa_decode_step_roofline.itl"] == pytest.approx(
        100 * step / hbm / 0.015)
    assert read["dsa_selected_pct.itl"] == pytest.approx(
        100 * 2048 / 22_001)
    # the expert layer (Kanana's reader): the held experts with a token,
    # 7 a layer, and a layer's shared expert and router over all 256, a
    # step of the kernel's 4 ms
    read["moe_ffn_roofline"] = mf.reader_of("moe_ffn_roofline").read(
        run, "moe_ffn_roofline")
    experts = 28 * 75_497_472 + 4 * (75_497_472 + 2 * 6144 * 256 + 4 * 256)
    assert read["moe_ffn_roofline"] == pytest.approx(
        100 * experts / hbm / 0.004)
    for v in read.values():
        assert 0 < v < 100


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_without_their_source(name):
    reader = mf.reader_of(name)
    assert reader.read(_run(counters={}), name) is None
    if name != "dsa_selected_pct.itl":
        assert reader.read({**_run(), "trace": None}, name) is None
    # a parent or a configuration without the selection
    import dataclasses
    other = {**_run(), "model": dataclasses.replace(MODEL, index_topk=0)}
    if name != "dsa_selected_pct.itl":
        assert reader.read(other, name) is None


def test_selection_ops_by_shape():
    assert _sparse_slice.is_selection("jit_step:sort[24x53248]", 2048)
    assert not _sparse_slice.is_selection("jit_step:sort[24x256]", 2048)
    assert not _sparse_slice.is_selection("jit_step:sort[496]", 2048)
    assert not _sparse_slice.is_selection("jit_step:fusion[24x53248]", 2048)


def test_manifest_entries_of_the_cell():
    cell = mf.cell(MAN, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "longctx_steady", 1)
    assert len(cell["why"]) <= 200
    assert CELL in _named(MAN["end_to_end"], "itl_p95_ms")["workloads"]
    assert "workloads" not in _named(MAN["end_to_end"], "setup_s")
    for name in APPENDED:
        assert CELL in _named(MAN["per_layer"], name)["workloads"], name
    layers = {"dsa_index_decode_roofline": "kernels",
              "sparse_latent_decode_roofline": "kernels",
              "dsa_select_dev_ms.itl": "programs",
              "dsa_decode_step_roofline.itl": "programs",
              "dsa_selected_pct.itl": "engine"}
    for name in NEW:
        m = _named(MAN["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
        assert m["layer"] == layers[name]
        assert os.path.exists(os.path.join(ROOT, mf.reader_path(name)))
    mine = [m["name"] for m in mf.metrics_for(MAN, "per_layer", CELL)]
    assert set(NEW + APPENDED) <= set(mine)


def test_the_traffic_file():
    mix = json.load(open(os.path.join(ROOT, mf.traffic_path(
        "longctx_steady"))))
    assert (mix["loop"], mix["lead_in_s"]) == ("open", 40)
    assert mix["prompt"] == {"dist": "lognormal", "median": 16384,
                             "sigma": 0.7, "min": 4096, "max": 49152}
    assert mix["output"] == {"dist": "lognormal", "median": 1536,
                             "sigma": 0.5, "min": 512, "max": 4096}
    lens = traffic.quantile_grid(mix["prompt"], 256)
    # every prompt lies past the 2,048 positions a query keeps
    assert lens.min() >= 4096 >= 2 * MODEL.index_topk
    assert traffic.prefill_buckets(mix, 16) == [4096, 8192, 16384, 32768,
                                                65536]
    top = int(lens.max()) + int(traffic.quantile_grid(mix["output"],
                                                      256).max())
    assert top <= CONFIG["engine"]["max_seq_len"]


def test_the_traced_slice_outlasts_the_prefill_the_window_opens_with():
    """A module counts only if it starts inside the traced slice, and one
    prefill holds the chip: a slice inside it reads no decode step. The
    order deals a prompt of 45,694 tokens at the window's opening; by the
    prefill time the order was picked with (40 ms + tokens / 50,000 s +
    9.45e-6 tokens^2 ms, which over-counts the chip's 16.5 s for it) the
    slice ends long after, and holds arrivals whose prefills it reads."""
    mix = json.load(open(os.path.join(ROOT, mf.traffic_path(
        "longctx_steady"))))
    reqs = serve_deepseek.scheduled_requests(mix, 725585098, 51, 19_360, 1.0)
    lead, t = mix["lead_in_s"], CONFIG["trace"]
    start = min(t["start_s"], 51 / 4)
    end = start + min(t["slice_s"], 51 / 2)
    first = next(r for r in reqs if r["due"] >= lead)
    n = len(first["prompt"])
    assert first["due"] == lead and n > 40_000
    prefill_s = 0.040 + n / 50_000 + 9.45e-9 * n * n
    assert end >= prefill_s + 10
    assert sum(lead <= r["due"] < lead + end for r in reqs) >= 4


def test_the_planted_faults_are_the_named_ones():
    assert faults_glm5.FAULTS == (
        "current_never_selected", "top2047", "relu_dropped",
        "index_key_unrotated", "latent_row_wrong_page",
        "index_scores_bf16", "router_bf16")
    with pytest.raises(ValueError):
        with faults_glm5.planted("nothing"):
            pass


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_of_the_cell(trace):
    """``--rehearse`` on the CPU at the tiny widths of the
    configuration's ``rehearse`` block: the same files and control
    flow, ``correct`` from the cell's whole check, no device value."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--trace", trace,
         "--seconds", "8", "--seed", "3000000019"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stdout[-4000:]
    assert all(v["value"] is None for v in line["metrics"].values())
    if trace == "1":
        assert "dsa_selected_pct.itl" in line["metrics"]
