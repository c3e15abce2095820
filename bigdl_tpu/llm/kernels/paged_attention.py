"""Paged KV-cache attention — the serving-side ragged-attention kernel.

Reference counterpart: the vLLM PagedAttention integration in bigdl-llm's
serving stack (SURVEY.md §2.2 ggml row "ragged paged attention for
serving"; §2.8 llm serving row). The reference binds vLLM's CUDA paged
kernels; on TPU the design is rebuilt for Mosaic:

- the KV cache is a **page pool** ``(num_pages, H_kv, page_size, D)`` per
  layer; a request owns ``ceil(tokens/page_size)`` pages named by a
  **block table** ``(B, pages_max)`` of physical page ids. HBM in use is
  proportional to tokens in flight, not ``B × max_seq_len`` (the r3
  slot-static cache's bound — VERDICT r3 missing #1).
- the decode kernel runs one grid step per ``(batch row, kv head,
  page block)`` and **async-copies ``ppb = 128 // page_size`` pages** by
  physical id (scalar-prefetched block tables) into one VMEM buffer, so
  the score tile is ``(G, 128)``, full lane width; blocks past a row's
  length are not copied. The latent kernel (end of the file) has one
  grid step a ROW and walks the row's live blocks of 512 tokens itself,
  fetching the next block, or the next row's first, into a second
  buffer slot while it scores this one: no grid step past a length.
- online softmax (flash-style running max/sum) accumulates across page
  blocks in VMEM scratch; GQA query groups ride the sublane dim padded
  to 8 (``Gp``).

The XLA fallback (:func:`paged_attention_reference`) is the same math as
a gather + masked attention — it is both the CPU-test golden and the
non-TPU execution path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128          # score-tile lane width: pages per block × page_size


def _paged_decode_kernel(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
                         *, page: int, ppb: int, pages_max: int,
                         scale: float, window: Optional[int] = None,
                         m_out=None, l_out=None):
    """One (batch row b, kv head h, page block blk) step.

    len_ref: (B,) lengths INCLUDING the current token; bt_ref:
    (B * pages_max,) flattened block tables; q (1, 1, Gp, D) VMEM;
    k/v_hbm: (P, Hkv, page, D) stay in HBM, pages DMA'd by id.
    """
    b = pl.program_id(0)
    h = pl.program_id(1)
    blk = pl.program_id(2)
    nblk = pl.num_programs(2)

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq = len_ref[b]
    base_tok = blk * (ppb * page)

    @pl.when(base_tok < seq)
    def _compute():
        copies = []
        for i in range(ppb):                    # static unroll
            pid = bt_ref[b * pages_max + blk * ppb + i]
            ck = pltpu.make_async_copy(k_hbm.at[pid, h], kbuf.at[i], sem)
            cv = pltpu.make_async_copy(v_hbm.at[pid, h], vbuf.at[i], sem)
            ck.start()
            cv.start()
            copies += [ck, cv]
        for c in copies:
            c.wait()
        gp, d = q_ref.shape[2], q_ref.shape[3]
        q = q_ref[0, 0].astype(jnp.float32)               # (Gp, D)
        k = kbuf[...].reshape(ppb * page, d).astype(jnp.float32)
        v = vbuf[...].reshape(ppb * page, d).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (Gp, LANE)
        pos = base_tok + jax.lax.broadcasted_iota(
            jnp.int32, (gp, ppb * page), 1)
        valid = pos < seq
        if window is not None:
            valid &= pos >= seq - window
        s = jnp.where(valid, s, -1e30)
        m_prev = m_ref[...]                               # (Gp, LANE)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)         # (Gp, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])     # (Gp, 1)
        p_ = jnp.exp(s - m_new[:, :1])                    # (Gp, LANE)
        l_new = alpha * l_prev[:, :1] + jnp.sum(p_, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (Gp, D)
        m_ref[...] = m_new
        l_ref[...] = jnp.broadcast_to(l_new, l_prev.shape)

    @pl.when(blk == nblk - 1)
    def _finish():
        if m_out is None:
            o_ref[0, 0] = (acc_ref[...]
                           / jnp.maximum(l_ref[:, :1], 1e-30)).astype(
                               o_ref.dtype)
        else:
            # stats mode: UNNORMALIZED accumulator + running (max, sum),
            # so the caller can merge further tokens (e.g. the current
            # decode token, written to its page only after attention)
            # with the flash-style combine rule
            o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)
            m_out[0, 0] = m_ref[...]
            l_out[0, 0] = l_ref[...]


def _paged_decode_kernel_stats(len_ref, bt_ref, q_ref, k_hbm, v_hbm,
                               o_ref, mo_ref, lo_ref, kbuf, vbuf, sem,
                               acc_ref, m_ref, l_ref, *, page: int,
                               ppb: int, pages_max: int, scale: float,
                               window: Optional[int] = None):
    _paged_decode_kernel(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
                         kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
                         page=page, ppb=ppb, pages_max=pages_max,
                         scale=scale, window=window,
                         m_out=mo_ref, l_out=lo_ref)


def _paged_decode_kernel_pm(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
                            kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
                            *, page: int, ppb: int, pages_max: int,
                            hkv: int, scale: float,
                            window: Optional[int] = None,
                            m_out=None, l_out=None):
    """PAGE-MAJOR variant: one (batch row b, page block blk) step copies
    each page ACROSS ALL KV HEADS in a single contiguous DMA.

    The head-minor kernel above issues ``2·ppb`` DMAs of one head-page
    (page·D·2 bytes ≈ 4 KB) per grid cell over a (B, Hkv, nblk) grid —
    at 7B decode that is ~16k 4 KB copies per layer, and the measured
    cost is DMA-issue-bound: attention was 27.8 ms of the 55 ms paged
    step (a one-off experiment, round 5) vs ~17 ms for the dense cache path.
    Here the grid is (B, nblk) and each cell copies ``2·ppb`` blocks of
    ``(Hkv, page, D)`` (≈128 KB contiguous at 7B) — 32× fewer, 32×
    larger DMAs — then statically loops the Hkv heads in-register.
    Measured effect on the full 7B b8/ctx256 serving decode step:
    54.2 → 37.0 ms (147.7 → 216.3 tok/s), taking the paged path ~21%
    PAST the dense fused-scan step (~44.7 ms) — the page pool's DMA
    pattern is now cheaper than XLA's dense cache attention.

    Measured alternative, rejected: DOUBLE-BUFFERING the page stream
    (two (ppb, Hkv, page, D) buffer/semaphore slots, next block's
    copies started during the current block's compute, static-slot
    pl.when duplication) passed on-chip parity but measured 211.2
    tok/s vs 215.7-216.3 for this synchronous version across repeated
    runs — the ~128 KB contiguous copies already complete within the
    32-head compute window, so pipelining buys nothing and costs 2×
    scratch VMEM. Kept simple on purpose.

    len_ref: (B,) lengths; bt_ref: (B·pages_max,) flat tables; q_ref
    (1, hkv, gp, D) VMEM; k/v_hbm (P, Hkv, page, D) in ANY space;
    o_ref (1, hkv, gp, D); kbuf/vbuf (ppb, Hkv, page, D) VMEM scratch;
    acc (hkv·gp, D) f32; m/l (hkv·gp, LANE) f32 running stats."""
    b = pl.program_id(0)
    blk = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq = len_ref[b]
    base_tok = blk * (ppb * page)

    @pl.when(base_tok < seq)
    def _compute():
        copies = []
        for i in range(ppb):                    # static unroll
            pid = bt_ref[b * pages_max + blk * ppb + i]
            ck = pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[i], sem)
            cv = pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[i], sem)
            ck.start()
            cv.start()
            copies += [ck, cv]
        for c in copies:
            c.wait()
        gp, d = q_ref.shape[2], q_ref.shape[3]
        pos = base_tok + jax.lax.broadcasted_iota(
            jnp.int32, (gp, ppb * page), 1)
        valid = pos < seq
        if window is not None:
            valid &= pos >= seq - window
        for h in range(hkv):                    # static unroll over heads
            q = q_ref[0, h].astype(jnp.float32)               # (gp, D)
            k = kbuf[:, h].reshape(ppb * page, d).astype(jnp.float32)
            v = vbuf[:, h].reshape(ppb * page, d).astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (gp, LANE)
            s = jnp.where(valid, s, -1e30)
            # static-slice loads/stores on the scratch refs per head
            # (functional .at[].set on a value lowers to scatter, which
            # Mosaic does not implement)
            r0 = h * gp
            m_prev = m_ref[r0:r0 + gp]
            l_prev = l_ref[r0:r0 + gp]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur,
                                                         m_prev.shape))
            alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
            p_ = jnp.exp(s - m_new[:, :1])
            l_new = (alpha * l_prev[:, :1]
                     + jnp.sum(p_, axis=1, keepdims=True))
            acc_ref[r0:r0 + gp] = (
                acc_ref[r0:r0 + gp] * alpha + jax.lax.dot_general(
                    p_, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            m_ref[r0:r0 + gp] = m_new
            l_ref[r0:r0 + gp] = jnp.broadcast_to(l_new, l_prev.shape)

    @pl.when(blk == nblk - 1)
    def _finish():
        gp, d = q_ref.shape[2], q_ref.shape[3]
        if m_out is None:
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[:, :1], 1e-30)).reshape(
                            hkv, gp, d).astype(o_ref.dtype)
        else:
            o_ref[0] = acc_ref[...].reshape(hkv, gp, d).astype(o_ref.dtype)
            m_out[0] = m_ref[...].reshape(hkv, gp, LANE)
            l_out[0] = l_ref[...].reshape(hkv, gp, LANE)


def _paged_decode_kernel_pm_stats(len_ref, bt_ref, q_ref, k_hbm, v_hbm,
                                  o_ref, mo_ref, lo_ref, kbuf, vbuf, sem,
                                  acc_ref, m_ref, l_ref, *, page: int,
                                  ppb: int, pages_max: int, hkv: int,
                                  scale: float,
                                  window: Optional[int] = None):
    _paged_decode_kernel_pm(len_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref,
                            kbuf, vbuf, sem, acc_ref, m_ref, l_ref,
                            page=page, ppb=ppb, pages_max=pages_max,
                            hkv=hkv, scale=scale, window=window,
                            m_out=mo_ref, l_out=lo_ref)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret",
                                             "sliding_window",
                                             "page_major"))
def paged_attention_decode(q, k_pages, v_pages, block_tables, lengths,
                           page_size: int = 16, interpret: bool = False,
                           sliding_window: Optional[int] = None,
                           page_major: bool = True):
    """Decode-step attention over a paged KV cache.

    q: (B, Hq, D) current-token queries; k_pages/v_pages:
    (P, Hkv, page_size, D); block_tables: (B, pages_max) int32 physical
    page ids; lengths: (B,) int32 context lengths INCLUDING the current
    token (whose K/V must already be written to its page).
    Returns (B, Hq, D) in q.dtype.

    ``pages_max`` must be a multiple of ``LANE // page_size`` (the server
    buckets tables to this), and page ids must be < P (unused table
    entries may be any valid id — their tokens are masked by lengths).
    """
    b, hq, d = q.shape
    p_, hkv, page, _ = k_pages.shape
    assert page == page_size
    ppb = LANE // page_size
    pages_max = block_tables.shape[1]
    if pages_max % ppb:
        raise ValueError(f"pages_max {pages_max} not a multiple of {ppb}")
    nblk = pages_max // ppb
    g = hq // hkv
    gp = max(8, -(-g // 8) * 8)
    scale = 1.0 / float(np.sqrt(d))

    qg = q.reshape(b, hkv, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    # Mosaic page DMAs need a 128-aligned minor dim: head_dim < 128
    # (test-size models; every production Llama head is 128) is
    # zero-padded. Zero K columns leave scores unchanged; padded V
    # columns are sliced off below. The pool pad is a copy — fine for
    # tiny models, free (no-op) at d=128.
    d_orig = d
    if d % 128:
        dp = -(-d // 128) * 128
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        d = dp

    if page_major:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nblk),
            in_specs=[
                pl.BlockSpec((1, hkv, gp, d), lambda b_, k_, *_:
                             (b_, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hkv, gp, d),
                                   lambda b_, k_, *_: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((ppb, hkv, page, d), k_pages.dtype),
                pltpu.VMEM((ppb, hkv, page, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.VMEM((hkv * gp, d), jnp.float32),
                pltpu.VMEM((hkv * gp, LANE), jnp.float32),
                pltpu.VMEM((hkv * gp, LANE), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            functools.partial(_paged_decode_kernel_pm, page=page_size,
                              ppb=ppb, pages_max=pages_max, hkv=hkv,
                              scale=scale, window=sliding_window),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(lengths.astype(jnp.int32),
          block_tables.reshape(-1).astype(jnp.int32), qg, k_pages,
          v_pages)
        return (out[:, :, :g, :d_orig].reshape(b, hq, d_orig)
                .astype(q.dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, gp, d), lambda b_, h_, k_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, d),
                               lambda b_, h_, k_, *_: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((ppb, page, d), k_pages.dtype),
            pltpu.VMEM((ppb, page, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, LANE), jnp.float32),
            pltpu.VMEM((gp, LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page_size, ppb=ppb,
                          pages_max=pages_max, scale=scale,
                          window=sliding_window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      qg, k_pages, v_pages)
    return (out[:, :, :g, :d_orig].reshape(b, hq, d_orig)
            .astype(q.dtype))


@functools.partial(jax.jit, static_argnames=("page_size", "interpret",
                                             "sliding_window",
                                             "page_major"))
def paged_attention_decode_stats(q, k_pages, v_pages, block_tables,
                                 lengths, page_size: int = 16,
                                 interpret: bool = False,
                                 sliding_window: Optional[int] = None,
                                 page_major: bool = True):
    """Like :func:`paged_attention_decode` but over the first ``lengths``
    tokens WITHOUT normalizing, returning the flash-style partial state
    ``(acc (B, Hq, D) f32 unnormalized, m (B, Hq) f32, l (B, Hq) f32)``
    so the caller can fold in further key/value tokens (the current
    decode token before its page write) with the online-softmax combine.
    Rows with ``lengths == 0`` return ``(0, -1e30, 0)`` — the identity
    of the combine."""
    b, hq, d = q.shape
    p_, hkv, page, _ = k_pages.shape
    assert page == page_size
    ppb = LANE // page_size
    pages_max = block_tables.shape[1]
    if pages_max % ppb:
        raise ValueError(f"pages_max {pages_max} not a multiple of {ppb}")
    nblk = pages_max // ppb
    g = hq // hkv
    gp = max(8, -(-g // 8) * 8)
    scale = 1.0 / float(np.sqrt(d))

    qg = q.reshape(b, hkv, g, d)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    d_orig = d
    if d % 128:
        dp = -(-d // 128) * 128
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        d = dp

    if page_major:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nblk),
            in_specs=[
                pl.BlockSpec((1, hkv, gp, d), lambda b_, k_, *_:
                             (b_, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, hkv, gp, d),
                             lambda b_, k_, *_: (b_, 0, 0, 0)),
                pl.BlockSpec((1, hkv, gp, LANE),
                             lambda b_, k_, *_: (b_, 0, 0, 0)),
                pl.BlockSpec((1, hkv, gp, LANE),
                             lambda b_, k_, *_: (b_, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((ppb, hkv, page, d), k_pages.dtype),
                pltpu.VMEM((ppb, hkv, page, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.VMEM((hkv * gp, d), jnp.float32),
                pltpu.VMEM((hkv * gp, LANE), jnp.float32),
                pltpu.VMEM((hkv * gp, LANE), jnp.float32),
            ],
        )
        acc, m, l = pl.pallas_call(
            functools.partial(_paged_decode_kernel_pm_stats,
                              page=page_size, ppb=ppb,
                              pages_max=pages_max, hkv=hkv, scale=scale,
                              window=sliding_window),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, hkv, gp, d), jnp.float32),
                jax.ShapeDtypeStruct((b, hkv, gp, LANE), jnp.float32),
                jax.ShapeDtypeStruct((b, hkv, gp, LANE), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(lengths.astype(jnp.int32),
          block_tables.reshape(-1).astype(jnp.int32), qg, k_pages,
          v_pages)
        return (acc[:, :, :g, :d_orig].reshape(b, hq, d_orig),
                m[:, :, :g, 0].reshape(b, hq),
                l[:, :, :g, 0].reshape(b, hq))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nblk),
        in_specs=[
            pl.BlockSpec((1, 1, gp, d), lambda b_, h_, k_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, gp, d),
                         lambda b_, h_, k_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, gp, LANE),
                         lambda b_, h_, k_, *_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, gp, LANE),
                         lambda b_, h_, k_, *_: (b_, h_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((ppb, page, d), k_pages.dtype),
            pltpu.VMEM((ppb, page, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, LANE), jnp.float32),
            pltpu.VMEM((gp, LANE), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_paged_decode_kernel_stats, page=page_size,
                          ppb=ppb, pages_max=pages_max, scale=scale,
                          window=sliding_window),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, gp, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, gp, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, gp, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      qg, k_pages, v_pages)
    return (acc[:, :, :g, :d_orig].reshape(b, hq, d_orig),
            m[:, :, :g, 0].reshape(b, hq),
            l[:, :, :g, 0].reshape(b, hq))


def _sliced_tables(block_tables, lengths, page: int,
                   max_live_tokens: Optional[int] = None):
    """Slice the table columns to the LIVE page span before the dense
    gather. The references gather every ``pages_max × page`` slot, but
    tables are bucketed to the engine's worst case — on CPU (tier-1
    tests, the non-TPU serving path) that pads the gather with capacity
    nobody owns. When ``lengths`` is concrete (tests, tools, host-side
    callers) or the caller passes a static ``max_live_tokens`` bound,
    the gather shrinks to ``ceil(max_live / page)`` columns; under a
    jit trace with no bound, the full table is kept (shapes must stay
    static). Masking is untouched: every valid position is below the
    live span by construction."""
    pages_max = block_tables.shape[1]
    if max_live_tokens is not None:
        live = -(-int(max_live_tokens) // page)
    else:
        try:
            live = -(-int(np.max(np.asarray(lengths))) // page)
        except Exception:       # traced lengths: keep the static shape
            return block_tables
    return block_tables[:, :max(1, min(live, pages_max))]


def paged_attention_reference_stats(q, k_pages, v_pages, block_tables,
                                    lengths,
                                    sliding_window: Optional[int] = None,
                                    max_live_tokens: Optional[int] = None):
    """XLA twin of :func:`paged_attention_decode_stats` (same contract)."""
    b, hq, d = q.shape
    p_, hkv, page, _ = k_pages.shape
    g = hq // hkv
    block_tables = _sliced_tables(block_tables, lengths, page,
                                  max_live_tokens)
    pages_max = block_tables.shape[1]
    s_max = pages_max * page
    k_all = (k_pages[block_tables].transpose(0, 1, 3, 2, 4)
             .reshape(b, s_max, hkv, d))
    v_all = (v_pages[block_tables].transpose(0, 1, 3, 2, 4)
             .reshape(b, s_max, hkv, d))
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scale = 1.0 / float(np.sqrt(d))
    s = jnp.einsum("bhgd,bshd->bhgs", qg,
                   k_all.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max)[None, :]
    mask = pos < lengths[:, None]                              # (B, S)
    if sliding_window is not None:
        mask &= pos >= lengths[:, None] - sliding_window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)                                    # (B,H,G)
    # p must be 0 (not exp(0)) on masked slots of all-masked rows,
    # where m == -1e30 would make s - m == 0
    p = jnp.where(mask[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v_all.astype(jnp.float32))
    any_valid = jnp.any(mask, axis=-1)[:, None, None]          # (B,1,1)
    m = jnp.where(any_valid, m, -1e30)
    return (acc.reshape(b, hq, d), m.reshape(b, hq), l.reshape(b, hq))


def paged_attention_stats(q, k_pages, v_pages, block_tables, lengths,
                          page_size: int = 16,
                          interpret: Optional[bool] = None,
                          sliding_window: Optional[int] = None):
    """Backend dispatch for the stats variant: Mosaic kernel on TPU, XLA
    gather elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return paged_attention_reference_stats(
                q, k_pages, v_pages, block_tables, lengths,
                sliding_window=sliding_window)
        interpret = False
    return paged_attention_decode_stats(
        q, k_pages, v_pages, block_tables, lengths, page_size=page_size,
        interpret=interpret, sliding_window=sliding_window)


def merge_attention_partial(acc, m, l, q, k_new, v_new,
                            scale: Optional[float] = None, sink=None):
    """Fold one extra key/value token into a flash-style partial state.

    ``(acc, m, l)`` from :func:`paged_attention_stats` (acc (B, Hq, D)
    f32 unnormalized); ``q`` (B, Hq, D) current queries; ``k_new/v_new``
    (B, Hkv, D) the token being decoded (pre page-write). Returns the
    NORMALIZED attention output (B, Hq, D) f32 over the union — exactly
    ``paged_attention`` after writing the token, but with the pool
    untouched (what lets the serving decode scan keep the page pool
    read-only and defer all layers' page writes to one post-scan
    scatter). ``scale`` defaults to ``1/sqrt(D)``; the value may be
    narrower than the key (the latent cache: ``v_new`` the first
    columns of ``k_new``). ``sink`` (Hq,), where a model has one, is a
    learned score a query head that joins the denominator and carries
    no value: it is folded in here, once, with the last token."""
    b, hq, d = q.shape
    hkv = k_new.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    kr = jnp.repeat(k_new.astype(jnp.float32), g, axis=1)     # (B, Hq, D)
    vr = jnp.repeat(v_new.astype(jnp.float32), g, axis=1)
    s_self = jnp.sum(q.astype(jnp.float32) * kr, axis=-1) * scale
    m_new = jnp.maximum(m, s_self)
    if sink is not None:
        m_new = jnp.maximum(m_new, sink.astype(jnp.float32)[None])
    alpha = jnp.exp(m - m_new)                                # (B, Hq)
    beta = jnp.exp(s_self - m_new)
    l_new = l * alpha + beta
    if sink is not None:
        l_new = l_new + jnp.exp(sink.astype(jnp.float32)[None] - m_new)
    out = (acc * alpha[..., None] + vr * beta[..., None]) \
        / jnp.maximum(l_new, 1e-30)[..., None]
    return out


def paged_attention_reference(q, k_pages, v_pages, block_tables, lengths,
                              sliding_window: Optional[int] = None,
                              max_live_tokens: Optional[int] = None):
    """XLA gather + masked attention — golden for the kernel and the
    execution path on non-TPU backends. Same contract as
    :func:`paged_attention_decode`. The gather is sliced to the live
    page span when the lengths are concrete (see
    :func:`_sliced_tables`)."""
    b, hq, d = q.shape
    p_, hkv, page, _ = k_pages.shape
    g = hq // hkv
    block_tables = _sliced_tables(block_tables, lengths, page,
                                  max_live_tokens)
    pages_max = block_tables.shape[1]
    s_max = pages_max * page
    # gather: (B, maxp, Hkv, page, D) -> (B, S, Hkv, D)
    k_all = (k_pages[block_tables].transpose(0, 1, 3, 2, 4)
             .reshape(b, s_max, hkv, d))
    v_all = (v_pages[block_tables].transpose(0, 1, 3, 2, 4)
             .reshape(b, s_max, hkv, d))
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    scale = 1.0 / float(np.sqrt(d))
    s = jnp.einsum("bhgd,bshd->bhgs", qg,
                   k_all.astype(jnp.float32)) * scale
    pos = jnp.arange(s_max)[None, :]
    mask = pos < lengths[:, None]                              # (B, S)
    if sliding_window is not None:
        mask &= pos >= lengths[:, None] - sliding_window
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, v_all.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    page_size: int = 16, interpret: Optional[bool] = None,
                    sliding_window: Optional[int] = None):
    """Backend dispatch: Mosaic kernel on TPU, XLA gather elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return paged_attention_reference(
                q, k_pages, v_pages, block_tables, lengths,
                sliding_window=sliding_window)
        interpret = False
    return paged_attention_decode(q, k_pages, v_pages, block_tables,
                                  lengths, page_size=page_size,
                                  interpret=interpret,
                                  sliding_window=sliding_window)


# ---------------------------------------------------------------------------
# Latent (MLA) cache: one row a token, whose first columns are the value
#
# One call at Kanana's cell's shapes (q (32, 32, 640) float32, 16,385 pages
# of 16 x 640 bf16, table (32, 512), 24 live rows of 0.3-7.8k tokens, 61,076
# in all = 130 blocks of 512; 85.9 us at 819 GB/s and 576 numbers a row),
# by the slope of a loop of calls (tools/exp_latent_body.py; chip runs, PR 32):
#   (a) PR 31's kernel: grid (32, 16), a block's 32 page DMAs started
#       and awaited inside its grid step                         298.0 us
#   (b) the same, every length zero: 512 empty grid steps         25.5
#   (c) (a)'s DMAs with the arithmetic taken out                 196.7
#   (d) (a)'s arithmetic on a resident buffer, no DMA            117.4
#   the walk below (one grid step a row, two slots)              160.9
#     its DMAs alone | its arithmetic alone               123.7 | 95.4
#     no next row's first block fetched ahead                    174.9
#     blocks of 256 | 768 | 1,024 tokens          220.3 | 153.6 | 146.5
#     a block's code written once a slot (static indices)        152.4
#     the fetch ahead started after this block's wait            193.8
#     only a last block's live pages: a loop of their count |
#       static groups of 8                                248.4 | 180.0
# (c) > (d): a grid step was bound by starting and awaiting its 32 copies,
# not by its products, and the empty steps were a twelfth. The walk runs
# at 1.2 us a block where its copies alone take 0.95 (690 GB/s) and its
# arithmetic 0.73: what is left is the 32 descriptors a block, started
# before the wait and so not under the arithmetic. Larger blocks halve
# that and read more past a row's end; 512 is kept (at 28 rows of 1.4k
# tokens 1,024 gains 2 %, not 9).
# ---------------------------------------------------------------------------

def _latent_decode_kernel(len_ref, bt_ref, q_ref, kv_hbm, o_ref, mo_ref,
                          lo_ref, buf, sem, walked, *, page: int, ppb: int,
                          pages_max: int, dv: int, scale: float):
    """One batch row ``b`` of absorbed-form multi-query attention: every
    head scores the same ``(ppb·page, W)`` rows, and the value is the
    first ``dv`` columns of the rows just read, so a page is fetched
    once. The row's live blocks, ``ceil(len / (ppb·page))`` of them, are
    walked here and not by the grid: block ``j`` is scored out of one
    slot of ``buf`` while block ``j + 1``, or after the row's last block
    the first block of the next row that has any, is fetched into the
    other. ``walked[0]`` counts the blocks of the rows before this one:
    its parity is the slot this row starts in, and a row finds its first
    block on the way unless it is the first to have one. Every block
    started is awaited once, by the row that scores it. q_ref (1, Hp, W)
    VMEM; kv_hbm (P, 1, page, W) in HBM; buf (2, ppb, page, W); the
    running sums live in the row's output blocks o (1, Hp, dv), m and l
    (1, Hp, LANE)."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    n = ppb * page
    hp, w = q_ref.shape[1], q_ref.shape[2]

    @pl.when(b == 0)
    def _first_row():
        walked[0] = 0

    seq = len_ref[b]
    nblk = (seq + (n - 1)) // n
    first = walked[0]

    def fetch(row, blk, slot):
        for i in range(ppb):                    # static unroll
            # a table shorter than a whole block: the pages past its
            # end are past every length too, any valid page will do
            col = jnp.minimum(blk * ppb + i, pages_max - 1)
            pid = bt_ref[row * pages_max + col]
            pltpu.make_async_copy(kv_hbm.at[pid, 0], buf.at[slot, i],
                                  sem.at[slot]).start()

    o_ref[0] = jnp.zeros((hp, dv), jnp.float32)
    mo_ref[0] = jnp.full((hp, LANE), -1e30, jnp.float32)
    lo_ref[0] = jnp.zeros((hp, LANE), jnp.float32)

    @pl.when((nblk > 0) & (first == 0))
    def _nobody_fetched_it():
        fetch(b, 0, 0)

    # the next row with anything cached (``rows`` where there is none)
    nxt = jax.lax.while_loop(
        lambda r: (r < rows) & (len_ref[jnp.minimum(r, rows - 1)] == 0),
        lambda r: r + 1, b + 1)

    def block(j, carry):
        slot = (first + j) % 2
        more = j + 1 < nblk

        @pl.when(more | (nxt < rows))
        def _fetch_ahead():
            fetch(jnp.where(more, b, nxt), jnp.where(more, j + 1, 0),
                  1 - slot)

        for i in range(ppb):
            # a wait reads the size of its destination and the semaphore
            pltpu.make_async_copy(kv_hbm.at[0, 0], buf.at[slot, i],
                                  sem.at[slot]).wait()
        q = q_ref[0].astype(jnp.float32)                   # (Hp, W)
        kv = buf[slot].reshape(n, w).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (Hp, n)
        pos = j * n + jax.lax.broadcasted_iota(jnp.int32, (hp, n), 1)
        s = jnp.where(pos < seq, s, -1e30)
        m_prev = mo_ref[0]
        l_prev = lo_ref[0]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p_ = jnp.exp(s - m_new[:, :1])
        l_new = alpha * l_prev[:, :1] + jnp.sum(p_, axis=1, keepdims=True)
        o_ref[0] = o_ref[0] * alpha + jax.lax.dot_general(
            p_, kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (Hp, dv)
        mo_ref[0] = m_new
        lo_ref[0] = jnp.broadcast_to(l_new, l_prev.shape)
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    walked[0] = first + nblk


# cached tokens a block of the latent kernel's walk fetches and scores
LATENT_BLOCK_TOKENS = 512


@functools.partial(jax.jit, static_argnames=("page_size", "dv", "scale",
                                             "interpret"))
def latent_attention_decode_stats(q, kv_pages, block_tables, lengths, *,
                                  page_size: int, dv: int, scale: float,
                                  interpret: bool = False):
    """:func:`paged_attention_decode_stats` for a latent cache. ``q``
    (B, H, W) queries in the cache's own coordinates (for MLA the
    absorbed ``q_nope W_uk`` beside ``q_rope``, zero where the row is
    padding); ``kv_pages`` (P, 1, page, W) with ``W`` a multiple of 128
    (the pool is never padded by a copy: the engine allocates it that
    wide); the value of a row is its first ``dv`` columns; ``scale`` is
    the model's, not ``1/sqrt(W)``. Returns ``(acc (B, H, dv) float32
    unnormalised, m (B, H), l (B, H))`` over the first ``lengths``
    tokens, the identity ``(0, -1e30, 0)`` where that is none."""
    b, h, w = q.shape
    _, one, page, wp = kv_pages.shape
    if one != 1 or page != page_size or wp != w or w % LANE or dv % LANE:
        raise ValueError(
            f"latent pool {kv_pages.shape} / queries {q.shape}: want "
            f"(P, 1, {page_size}, W) and (B, H, W), W and dv multiples "
            f"of {LANE}")
    pages_max = block_tables.shape[1]
    ppb = max(1, min(LATENT_BLOCK_TOKENS // page, pages_max))
    hp = -(-h // 8) * 8
    if hp != h:
        q = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    row = lambda b_, *_: (b_, 0, 0)
    acc, m, l = pl.pallas_call(
        functools.partial(_latent_decode_kernel, page=page, ppb=ppb,
                          pages_max=pages_max, dv=dv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, hp, w), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, hp, dv), row),
                       pl.BlockSpec((1, hp, LANE), row),
                       pl.BlockSpec((1, hp, LANE), row)],
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, w), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hp, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, LANE), jnp.float32)],
        # a row hands its successor a block on the way and the slot to
        # find it in: the rows run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), q, kv_pages)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]


def latent_attention_reference_stats(q, kv_pages, block_tables, lengths,
                                     *, dv: int, scale: float,
                                     max_live_tokens: Optional[int] = None):
    """XLA twin of :func:`latent_attention_decode_stats` (same
    contract): a gather of the live pages and masked scores."""
    b, h, w = q.shape
    page = kv_pages.shape[2]
    block_tables = _sliced_tables(block_tables, lengths, page,
                                  max_live_tokens)
    s_max = block_tables.shape[1] * page
    kv = kv_pages[block_tables][:, :, 0].reshape(b, s_max, w) \
        .astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), kv) * scale
    mask = (jnp.arange(s_max)[None, :] < lengths[:, None])[:, None, :]
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bhs,bsv->bhv", p, kv[..., :dv])
    m = jnp.where(jnp.any(mask, axis=-1), m, -1e30)
    return acc, m, jnp.sum(p, axis=-1)


def latent_attention_stats(q, kv_pages, block_tables, lengths, *,
                           page_size: int, dv: int, scale: float,
                           interpret: Optional[bool] = None):
    """Backend dispatch: Mosaic kernel on TPU, XLA gather elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return latent_attention_reference_stats(
                q, kv_pages, block_tables, lengths, dv=dv, scale=scale)
        interpret = False
    return latent_attention_decode_stats(
        q, kv_pages, block_tables, lengths, page_size=page_size, dv=dv,
        scale=scale, interpret=interpret)
