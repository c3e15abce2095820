"""Deliberate faults planted in the SERVED program of the ``deepseek_v3``
family, to show that the driver's comparison (``drivers/serve_deepseek.
reference_check``) comes out ``correct: false`` for each. Never for a
result: ``benchmark/check_deepseek.py --served`` (chip, published
widths) and ``benchmark/tests/test_deepseek_cell.py`` (CPU, rehearsal
widths) are the only users.

A fault replaces one function of ``bigdl_tpu.llm.models.deepseek``, of
its kernels or of the page writers while an ``LLMServer`` is built and
driven, and is taken out again. The first five are ISSUE 27's (the
program's arithmetic is wrong wherever it runs, dense forward included,
so the reference has to catch them); the last four live only in what
the engine serves (the dense forward stays right, so the served rows
have to be held to it):

- ``router_bf16``: the router's scores in bfloat16;
- ``top5``: one expert fewer than ``num_experts_per_tok``;
- ``no_shared``: the shared expert left out;
- ``weights_from_s_plus_b``: routed weights from the biased scores;
- ``k_rope_unrotated``: ``k_r`` cached before its rotation;
- ``latent_value_columns``: the latent kernel's value read a lane block
  to the side of the first ``kv_lora_rank`` columns;
- ``latent_scale_padded``: the latent kernel scaling by ``1/sqrt`` of
  the padded row width, as the per-head kernel does;
- ``write_kv_next_slot``: a decode step's latent row written one slot
  on in its page;
- ``decode_tile_next_group``: the expert product at decode tiles
  fetching the group after the one its tile belongs to.
"""

from __future__ import annotations

import contextlib
from unittest import mock

REFERENCE_FAULTS = ("router_bf16", "top5", "no_shared",
                    "weights_from_s_plus_b", "k_rope_unrotated")
SERVED_ONLY_FAULTS = ("latent_value_columns", "latent_scale_padded",
                      "write_kv_next_slot", "decode_tile_next_group")
FAULTS = REFERENCE_FAULTS + SERVED_ONLY_FAULTS


def _route(fault: str):
    import jax
    import jax.numpy as jnp

    def route(router, h, cfg):
        w, x = router["w"].astype(jnp.float32), h.astype(jnp.float32)
        if fault == "router_bf16":
            w, x = w.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        s = jax.nn.sigmoid(jnp.dot(
            x, w.T, precision=jax.lax.Precision.HIGHEST)
            .astype(jnp.float32))
        biased = s + router["bias"]
        k = cfg.num_experts_per_tok - (fault == "top5")
        _, idx = jax.lax.top_k(biased, k)
        wts = jnp.take_along_axis(
            biased if fault == "weights_from_s_plus_b" else s, idx, -1)
        if cfg.norm_topk_prob:
            wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), wts * cfg.routed_scaling_factor
    return route


@contextlib.contextmanager
def planted(fault: str, cfg):
    """The program with ``fault`` in it; every compiled engine program
    is dropped on the way in and out, since the engine caches them by
    shape and not by what they compute."""
    import jax.numpy as jnp

    from bigdl_tpu.llm import serving
    from bigdl_tpu.llm.kernels import moe, paged_attention
    from bigdl_tpu.llm.kvcache import write
    from bigdl_tpu.llm.models import deepseek

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault in ("router_bf16", "top5", "weights_from_s_plus_b"):
        patch = mock.patch.object(deepseek, "route", _route(fault))
    elif fault == "no_shared":
        inner = moe.grouped_ffn

        def grouped_ffn(x, groups_of, weights, *a, **k):
            routed = groups_of < cfg.n_routed_experts
            return inner(x, groups_of, jnp.where(routed, weights, 0.0),
                         *a, **k)
        patch = mock.patch.object(moe, "grouped_ffn", grouped_ffn)
    elif fault == "k_rope_unrotated":
        inner = deepseek.mla_project

        def mla_project(lp, h, positions, cfg_):
            q_nope, q_rope, c, _ = inner(lp, h, positions, cfg_)
            raw = deepseek._linear(lp["kv_a_proj"], h)[
                ..., cfg_.kv_lora_rank:]
            k_r = jnp.concatenate([raw[..., 0::2], raw[..., 1::2]], -1)
            return q_nope, q_rope, c, k_r
        patch = mock.patch.object(deepseek, "mla_project", mla_project)
    elif fault in ("latent_value_columns", "latent_scale_padded"):
        inner = paged_attention.latent_attention_stats

        def latent_attention_stats(q, kv_pages, bt, lens, *, scale, dv,
                                   **k):
            if fault == "latent_scale_padded":
                scale = float(q.shape[-1]) ** -0.5
            acc, m, l = inner(q, kv_pages, bt, lens, scale=scale, dv=dv,
                              **k)
            if fault == "latent_value_columns":
                acc = jnp.roll(acc, min(128, dv // 4), axis=-1)
            return acc, m, l
        patch = mock.patch.object(paged_attention, "latent_attention_stats",
                                  latent_attention_stats)
    elif fault == "write_kv_next_slot":
        inner = write.write_kv

        def write_kv(pool, phys, slots, rows):
            return inner(pool, phys, (slots + 1) % pool.shape[3], rows)
        patch = mock.patch.object(write, "write_kv", write_kv)
    else:                               # decode_tile_next_group
        inner = moe.dispatch

        def dispatch(groups_of, live, n_groups, tm):
            d = inner(groups_of, live, n_groups, tm)
            if tm >= 128:               # the dense forward and prefills
                return d
            return d._replace(tile_group=(d.tile_group + 1) % n_groups)
        patch = mock.patch.object(moe, "dispatch", dispatch)
    serving._PAGED_STEP_CACHE.clear()
    try:
        with patch:
            yield
    finally:
        serving._PAGED_STEP_CACHE.clear()
