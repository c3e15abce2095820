"""Deliberate faults planted in the SERVED program of a ``glm_moe_dsa``
configuration, to show that the cell's comparison
(``drivers/serve_glm5``) comes out ``correct: false`` for each. Never
for a result: ``benchmark/check_glm5.py`` (chip, published widths; CPU
with ``--rehearse``) and ``benchmark/tests/test_glm5_cell.py`` are the
only users.

A fault replaces one function of ``bigdl_tpu.llm.models.deepseek`` or of
``kernels.sparse_attention`` while an ``LLMServer`` is built and driven
and the check's probe runs, and is taken out again. Six in the sparse
attention, then one for the router's precision:

- ``current_never_selected``: the token a query is at never competes
  (decode: its score ``-inf`` beside the cached ones; prefill: the
  diagonal left out of the mask);
- ``top2047``: one position fewer than ``index_topk`` kept;
- ``relu_dropped``: the indexer's head sum over ``q . k`` and not over
  ``relu(q . k)``;
- ``index_key_unrotated``: the index keys cached (and scored) without
  their RoPE;
- ``latent_row_wrong_page``: the selected latent rows gathered from the
  same slot of the pool's next page;
- ``index_scores_bf16``: the index scores rounded to bfloat16 where the
  program keeps float32 (a lower precision than the configuration
  states must fail one check);
- ``router_bf16``: the router's scores in bfloat16.
"""

from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("current_never_selected", "top2047", "relu_dropped",
          "index_key_unrotated", "latent_row_wrong_page",
          "index_scores_bf16", "router_bf16")


@contextlib.contextmanager
def planted(fault: str, cfg=None):
    """The program with ``fault`` in it; every compiled engine program
    is dropped on the way in and out, since the engine caches them by
    shape and not by what they compute."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm import serving
    from bigdl_tpu.llm.kernels import sparse_attention as sa
    from bigdl_tpu.llm.models import deepseek as ds

    del cfg
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    patch = contextlib.ExitStack()
    select, mask_of = sa.dsa_select, ds.dsa_mask
    scores_of, rows_of, blocked = (sa.index_scores, sa.index_score_rows,
                                   ds.index_scores_blocked)
    if fault == "current_never_selected":
        patch.enter_context(mock.patch.object(
            sa, "dsa_select", lambda s, cur, lens, what=None, *, k: select(
                s, jnp.full_like(cur, -jnp.inf), lens, what, k=k)))

        def off_diagonal(scores, seen, k, n_blocks, kb):
            q, s = seen.shape
            # the chunk's queries sit at the last positions they may see
            last = jnp.max(jnp.where(seen, jnp.arange(s)[None], -1), 1)
            seen = seen & (jnp.arange(s)[None] != last[:, None])
            return mask_of(scores, seen, k, n_blocks, kb)
        patch.enter_context(mock.patch.object(ds, "dsa_mask", off_diagonal))
    elif fault == "top2047":
        def fewer(s, cur, lens, what=None, *, k):
            # the lowest-scored of the k kept is dropped: the shapes the
            # kernels are compiled for stay
            rows, ok = select(s, cur, lens, what, k=k)
            return rows, ok.at[:, k - 1].set(False)
        patch.enter_context(mock.patch.object(sa, "dsa_select", fewer))
        patch.enter_context(mock.patch.object(
            ds, "dsa_mask", lambda sc, seen, k, n, kb: mask_of(
                sc, seen, k - 1, n, kb)))
    elif fault in ("relu_dropped", "index_scores_bf16"):
        relu = fault != "relu_dropped"
        cast = (lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)) \
            if fault == "index_scores_bf16" else (lambda a: a)

        def scores(q, w, k_pages, block_tables, lengths, **kw):
            if relu:
                return cast(scores_of(q, w, k_pages, block_tables, lengths,
                                      **kw))
            b, (page, d) = q.shape[0], k_pages.shape[2:]
            s_max = block_tables.shape[1] * page
            k = k_pages[block_tables][:, :, 0].reshape(b, s_max, d)
            s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                           k.astype(jnp.float32))
            return jnp.einsum("bhs,bh->bs", s, w.astype(jnp.float32))

        def rows(q, w, keys):
            if relu:
                return cast(rows_of(q, w, keys))
            s = jnp.einsum("...hd,...sd->...hs", q.astype(jnp.float32),
                           keys.astype(jnp.float32))
            return jnp.einsum("...hs,...h->...s", s, w.astype(jnp.float32))

        def prefill(qi, wi, keys, n_blocks, kb):
            if relu:
                return cast(blocked(qi, wi, keys, n_blocks, kb))
            q, s = qi.shape[0], keys.shape[0]

            def one(j, out):
                k = jax.lax.dynamic_slice_in_dim(keys, j * kb, kb)
                sc = jnp.einsum("qhd,kd->qhk", qi.astype(jnp.float32),
                                k.astype(jnp.float32))
                sc = jnp.einsum("qhk,qh->qk", sc, wi)
                return jax.lax.dynamic_update_slice_in_dim(out, sc,
                                                           j * kb, 1)
            return jax.lax.fori_loop(0, n_blocks, one, jnp.full(
                (q, s), -jnp.inf, jnp.float32))
        patch.enter_context(mock.patch.object(sa, "index_scores", scores))
        patch.enter_context(mock.patch.object(sa, "index_score_rows", rows))
        patch.enter_context(mock.patch.object(ds, "index_scores_blocked",
                                              prefill))
    elif fault == "index_key_unrotated":
        inner = ds.indexer_project

        def unrotated(lp, h, c_q, positions, cfg_, dtype):
            q, _, w = inner(lp, h, c_q, positions, cfg_, dtype)
            _, k, _ = inner(lp, h, c_q, jnp.zeros_like(positions), cfg_,
                            dtype)
            return q, k, w
        patch.enter_context(mock.patch.object(ds, "indexer_project",
                                              unrotated))
    elif fault == "latent_row_wrong_page":
        inner = sa.gather_selected

        def next_page(kv_rows, rows):
            # the same slot of the pool's next page (pages of 16)
            page = 16
            return inner(kv_rows, jnp.where(rows >= 0, jnp.minimum(
                rows + page, kv_rows.shape[0] - 1), rows))
        patch.enter_context(mock.patch.object(sa, "gather_selected",
                                              next_page))
    else:           # router_bf16
        def route(router, h, cfg_):
            s = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.bfloat16), router["w"].astype(jnp.bfloat16).T)
                .astype(jnp.float32))
            _, idx = jax.lax.top_k(s + router["bias"],
                                   cfg_.num_experts_per_tok)
            w = jnp.take_along_axis(s, idx, -1)
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
            return idx.astype(jnp.int32), w * cfg_.routed_scaling_factor
        patch.enter_context(mock.patch.object(ds, "route", route))
    # the kernels' own jits remember what they traced, too
    serving._PAGED_STEP_CACHE.clear()
    jax.clear_caches()
    try:
        with patch:
            yield
    finally:
        serving._PAGED_STEP_CACHE.clear()
        jax.clear_caches()
