"""Attention over the page pool and the programs composed from a
family's two (ISSUE 5/8/14/19).

When admission finds a cached prefix, only the uncached suffix must run
through the model — but the suffix's attention still needs the prefix's
K/V. Each family's ``paged_prefill_ragged`` composes the shared closures
here (:func:`ragged_prefill_attend`, :func:`fork_tail_pages`,
:func:`scatter_suffix_kv`) with its own layer math: the suffix attends
the prefix pages WHERE THEY SIT via the Mosaic ragged kernel
(llm/kernels/ragged_prefill.py), the COW tail fork is one page-to-page
copy inside the same dispatch, and after the scan the suffix K/V is
written into the request's pages in place, page by page
(llm/kvcache/write.py). No dense temp cache, and the prefix page count
is runtime block-table data — the compile grid is O(suffix-buckets)
only. A full prompt is the offset-0 case of the same program.

:func:`paged_attend` is the decode step's counterpart (one token a row
over the pool), and :func:`make_mixed_step` / :func:`make_spec_step`
lift a family's ``(paged_decode_step, paged_prefill_ragged)`` pair into
the engine's unified and speculative steps, zero per-family math here.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# ragged in-place prefill (ISSUE 8): shared closures for the per-family
# ``paged_prefill_ragged`` entry points
# ---------------------------------------------------------------------------

def fork_tail_pages(k_pages, v_pages, fork_dst, fork_src):
    """COW tail fork, fused into the prefill dispatch: copy the adopted
    partial tail page (``fork_src``, shared — never written in place)
    into the page the request owns (``fork_dst``). Runs BEFORE the
    layer scan so the ragged kernel reads the forked slots through the
    request's own block table; the suffix scatter then overwrites the
    slots from ``offset`` on. With no tail both ids are 0 — a trash-
    page self-copy, semantically a no-op."""
    k_pages = k_pages.at[:, fork_dst].set(k_pages[:, fork_src])
    v_pages = v_pages.at[:, fork_dst].set(v_pages[:, fork_src])
    return k_pages, v_pages


def paged_attend(k_pages, v_pages, bt, lens, *, page: int,
                 sliding_window: Optional[int] = None):
    """Shared paged-attention closure for every family's decode step.

    Owns the divergence-prone conventions in ONE place (review r5):
    the pools are viewed as one flat ``(L·P, H, page, D)`` page array
    (a ``pool[l]`` slice would copy 2·pool_bytes/L per layer), block
    tables are offset by ``l·P`` inside the layer scan (layer ``l``'s
    trash page is ``l·P``), the kernel sees lengths EXCLUDING the
    current token with the window shrunk by one, and the token's own
    K/V is folded in with the flash combine. Returns
    ``attend(l, q, k, v) -> (B, Hq, D)`` for head-shaped ``(B, 1, H*,
    D)`` current-token projections."""
    from bigdl_tpu.llm.kernels.paged_attention import (
        merge_attention_partial, paged_attention_stats)
    L_times_P = k_pages.shape[0] * k_pages.shape[1]
    num_pages = k_pages.shape[1]
    kp_flat = k_pages.reshape((L_times_P,) + k_pages.shape[2:])
    vp_flat = v_pages.reshape((L_times_P,) + v_pages.shape[2:])
    win_excl = (None if sliding_window is None
                else max(sliding_window - 1, 0))

    def attend(l, q, k, v):
        acc, m, lsum = paged_attention_stats(
            q[:, 0], kp_flat, vp_flat, bt + l * num_pages, lens,
            page_size=page, sliding_window=win_excl)
        return merge_attention_partial(acc, m, lsum, q[:, 0], k[:, 0],
                                       v[:, 0])

    return attend


def ragged_prefill_attend(k_pages, v_pages, bt_row, offset, seq_len, *,
                          page: int,
                          sliding_window: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Shared ragged-attention closure for every family's prefill.

    Mirrors :func:`paged_attend`'s conventions: the pools are
    viewed as one flat ``(L·P, H, page, D)`` page array, the block
    table is offset by ``l·P`` inside the layer scan (layer ``l``'s
    trash page is ``l·P``), and the kernel reads only prefix positions
    ``< offset`` (the suffix's own K/V rides in densely — it is not in
    the pool until the post-scan scatter). Returns
    ``attend(l, q, k, v) -> (1, Tq, Hq, D) f32`` for suffix-shaped
    ``(1, Tq, H*, D)`` projections."""
    from bigdl_tpu.llm.kernels.ragged_prefill import ragged_prefill
    L, P = k_pages.shape[0], k_pages.shape[1]
    kp_flat = k_pages.reshape((L * P,) + k_pages.shape[2:])
    vp_flat = v_pages.reshape((L * P,) + v_pages.shape[2:])
    bt = bt_row.reshape(1, -1)
    offs = jnp.reshape(offset, (1,)).astype(jnp.int32)
    lens = jnp.reshape(seq_len, (1,)).astype(jnp.int32)

    def attend(l, q, k, v):
        return ragged_prefill(q, k, v, kp_flat, vp_flat, bt + l * P,
                              offs, lens, page_size=page,
                              sliding_window=sliding_window,
                              interpret=interpret)

    return attend


def scatter_suffix_kv(k_pages, v_pages, phys, slots, k_new, v_new):
    """Every layer's suffix K/V into the (donated) pools, in place and
    page by page (:func:`kvcache.write.write_kv_run`). ``k_new``/
    ``v_new`` are the layer-scan ys ``(L, Tq, Hkv, D)``; token ``j``
    lands in ``(phys[j], slots[j])``, consecutive positions of one
    sequence (the run's tail that the request must not write routes to
    trash page 0)."""
    from bigdl_tpu.llm.kvcache.write import write_kv_run
    return (write_kv_run(k_pages, phys, slots, k_new),
            write_kv_run(v_pages, phys, slots, v_new))


def make_mixed_step(fam_step, fam_ragged):
    """Lift a family ``(paged_decode_step, paged_prefill_ragged)`` pair
    into the engine's UNIFIED mixed prefill+decode step (ISSUE 14).

    The split engine compiles prefill and decode as separate programs,
    so a long admission stalls every in-flight decode for a whole pass.
    The lifted step fuses both legs into ONE compiled program per
    chunk-suffix bucket — the Ragged-Paged-Attention batch shape (one
    dispatch serving rows with suffix length 1 and rows with a chunk of
    suffix tokens) realized by composition of the two proven per-family
    bodies, so each leg's math is BIT-IDENTICAL to the program the
    split engine would have run:

    - the **chunk leg** runs first: exactly the family's
      ``paged_prefill_ragged`` over the ``(1, bucket)`` chunk — COW
      tail fork fused ahead of its layer scan, attention reading the
      cached prefix (and earlier chunks) in place via the ragged
      kernel, one post-scan scatter of the chunk's K/V into its own
      pages. Its page writes are disjoint from every decode row's
      (shared radix pages are never decode-written; the chunk's own
      pages belong to no decode row), so leg order cannot change any
      row's result;
    - the **decode leg** is exactly the family's
      ``make_sampled_step`` body: sample every active row's next token
      from ``last`` on device, one token of forward+attend per row,
      one post-scan scatter, lengths advanced for active rows. The
      chunk's slot rides this leg MASKED INACTIVE (trash-page dummy
      write), exactly like an empty slot in the split engine.

    Returns ``(out, logits, k_pages, v_pages, new_lens, key, clast)``
    — the sampled-ids ‖ fence vector (the fence data-depends on the
    pools AFTER both legs' scatters, so one drain fetch bounds the
    whole pass), the decode logits, and ``clast``: the chunk's
    last-true-token logits, which the engine scatters into its ``last``
    row when the final chunk completes the prompt (mid-prompt chunks
    discard it). Compile-relevant shapes: the decode batch width and
    the chunk bucket ``ctoks.shape[1]`` only — offsets, block tables
    and scatter targets are runtime data, so the grid stays
    O(suffix-buckets).
    """
    from bigdl_tpu.llm.kernels.sampling import make_sampled_step
    sampled = make_sampled_step(fam_step)

    def mixed_step(params, cfg, k_pages, v_pages, bt, lens, last,
                   active, temperature, key, ctoks, clen, coff, cbt_row,
                   cphys, cslots, fork_dst, fork_src, *, page: int,
                   do_sample: bool = False, top_k: int = 0):
        k_pages, v_pages, clast = fam_ragged(
            params, cfg, k_pages, v_pages, ctoks, clen, coff, cbt_row,
            cphys, cslots, fork_dst, fork_src, page=page)
        out, logits, k_pages, v_pages, new_lens, key = sampled(
            params, cfg, k_pages, v_pages, bt, lens, last, active,
            temperature, key, page=page, do_sample=do_sample,
            top_k=top_k)
        return out, logits, k_pages, v_pages, new_lens, key, clast

    return mixed_step


def make_spec_step(fam_step, fam_ragged):
    """Lift a family ``(paged_decode_step, paged_prefill_ragged)`` pair
    into the engine's SPECULATIVE verify step (ISSUE 19).

    The verify chunk is the mixed step's chunk leg re-aimed at decode:
    instead of prompt tokens, the ``(1, W)`` chunk carries the row's
    next greedy token followed by the host's n-gram drafts, run at the
    row's position offset — logits for all chunk positions come back
    from ONE dispatch, and :func:`kernels.sampling.spec_accept` keeps
    the prefix greedy decode would have produced anyway. Structure:

    - **chunk token 0 is computed on device**: ``g0 = argmax(last
      [srow])`` — exactly the token the sampled decode leg would have
      emitted for the row. With every draft rejected the step therefore
      degenerates to a plain decode step for the row (emit ``g0``,
      whose K/V the chunk leg wrote at position ``lens[srow]``, carry
      ``chunk_logits[0]``), preserving the engine invariant that every
      emitted token has its K/V in the pool and ``last`` predicts the
      next position;
    - the **chunk leg** is the family's ``paged_prefill_ragged``
      VERBATIM (``full_logits=True``) at offset ``lens[srow]`` over the
      row's own block table — no COW fork (a decode row's tail pages
      are private by the admission contract), padding past
      ``n_draft + 1`` routed to trash page 0 by the host's scatter
      targets;
    - the **decode leg** is the family's ``make_sampled_step`` body
      with the spec row masked INACTIVE (trash-page dummy write, like
      an empty slot) — every other active row advances exactly as in a
      plain pass;
    - the accepted length then advances the spec row's device length by
      ``n_acc`` and splices ``chunk_logits[n_acc - 1]`` into the
      ``last`` carry. K/V written for the REJECTED tail positions is
      rolled back by length bookkeeping alone: attention reads only
      positions ``< lens``, and later steps overwrite the garbage slots
      as the row advances (docs/KVCACHE.md "Speculative charging").

    Returns ``(out, logits, k_pages, v_pages, new_lens, key)`` where
    ``out`` is ``(B + 1 + W + 1,)`` int32: the decode rows' sampled ids
    (the spec row's lane is garbage — the host skips it), ``n_acc``,
    the W chunk tokens (the host needs ``g0`` back — it was never on
    the host), and one :func:`kernels.sampling.fence_token` bounding
    both legs' pool writes. Compile-relevant shapes: batch width and
    the chunk bucket ``ctoks.shape[1]`` only — ``srow``, ``n_draft``,
    offsets and scatter targets are runtime data, so speculation adds
    O(k-buckets) programs total.
    """
    from bigdl_tpu.llm.kernels.sampling import (fence_token,
                                                make_sampled_step,
                                                spec_accept)
    sampled = make_sampled_step(fam_step)

    def spec_step(params, cfg, k_pages, v_pages, bt, lens, last, active,
                  temperature, key, srow, ctoks, n_draft, cbt_row,
                  cphys, cslots, *, page: int, do_sample: bool = False,
                  top_k: int = 0):
        b = lens.shape[0]
        rows = jnp.arange(b, dtype=jnp.int32)
        onehot = rows == srow
        slast = jnp.take(last, srow, axis=0)                    # (V,)
        g0 = jnp.argmax(slast).astype(jnp.int32)
        ctoks = ctoks.at[0, 0].set(g0)
        clen = (n_draft + 1).astype(jnp.int32)
        coff = jnp.take(lens, srow).astype(jnp.int32)
        k_pages, v_pages, chunk_logits = fam_ragged(
            params, cfg, k_pages, v_pages, ctoks, clen, coff, cbt_row,
            cphys, cslots, jnp.int32(0), jnp.int32(0), page=page,
            full_logits=True)
        n_acc, new_slast = spec_accept(ctoks[0], chunk_logits, n_draft)
        out, logits, k_pages, v_pages, new_lens, key = sampled(
            params, cfg, k_pages, v_pages, bt, lens, last,
            active & ~onehot, temperature, key, page=page,
            do_sample=do_sample, top_k=top_k)
        new_lens = new_lens + jnp.where(onehot, n_acc,
                                        0).astype(new_lens.dtype)
        logits = jnp.where(onehot[:, None], new_slast[None, :], logits)
        out = jnp.concatenate(
            [out[:b], n_acc[None], ctoks[0],
             fence_token(k_pages, v_pages, logits)])
        return out, logits, k_pages, v_pages, new_lens, key

    return spec_step
