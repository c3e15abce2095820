"""Ragged paged-PREFILL attention — suffix queries over in-place KV pages.

ISSUE 8 tentpole (ROADMAP open item 1; the Ragged Paged Attention line,
arXiv 2604.15464 in PAPERS.md): every prefill path used to stage the
whole context through a dense ``(1, s_temp, H, D)`` temp cache — gather
the prefix pages in, run the family forward, scatter back. That
gather/scatter is pure HBM traffic that grows with the prefix length,
exactly when the prefix cache should be saving the most, and the static
gather shape ``n_pp`` multiplied the compile grid by the prefix-page
bucket count.

This kernel removes the staging: the suffix tokens' queries attend the
cached prefix **where it sits in the page pool**, by physical page id
via a scalar-prefetched block table, while the suffix's own K/V (not yet
written to pages — the caller scatters it after the layer scan) rides in
as a dense VMEM operand. One flash-style online softmax runs over both
sources: page blocks first (DMA'd by id, masked to ``pos < offset``),
then suffix blocks (masked causally against each query's own position
``offset + j``). Because the block table and ``offset`` are runtime
data, the only compile-relevant shape is the suffix bucket — the
partial-prefill compile grid collapses from O(prefix-buckets ×
suffix-buckets) to O(suffix-buckets).

Ragged across the batch: ``offsets`` and ``seq_lens`` are per-row, so
one dispatch serves rows with different prefix and suffix lengths (rows
are padded to the bucket; padded rows produce finite garbage that
callers slice off).

:func:`ragged_prefill_reference` is the XLA twin — the CPU golden and
the non-TPU execution path — written with the same einsum/softmax
structure as the dense ``_attention`` so the serving engine's greedy
outputs stay bit-stable when it swaps the staging path for this one.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.llm.kernels.paged_attention import LANE

# query-tile cap: acc/m/l rows are hkv * qt * g fp32 vectors, so this
# bounds the accumulators (7B MHA: hkv=32, g=1 -> qt=128; GQA-8: qt=128,
# 512 rows per kv head). The resulting VMEM need is computed per call in
# ragged_prefill_attention and handed to Mosaic as its limit.
_MAX_SCRATCH_ROWS = 4096


def _ragged_prefill_kernel(off_ref, len_ref, bt_ref, q_ref, ks_ref,
                           vs_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
                           acc_ref, m_ref, l_ref, *, page: int, ppb: int,
                           pages_max: int, hkv: int, g: int, qt: int,
                           nblk_pages: int, scale: float,
                           window: Optional[int] = None):
    """One (batch row b, query block qb, kv block kb) step.

    off_ref/len_ref: (B,) prefix / suffix lengths; bt_ref:
    (B * pages_max,) flat block tables; q_ref (1, hkv, qt*g, D) VMEM
    (row = token*g + group); ks/vs_ref (1, hkv, LANE, D) the kv block's
    slice of the dense suffix K/V; k/v_hbm (P, Hkv, page, D) stay in
    HBM, pages DMA'd by id. kv blocks [0, nblk_pages) read pages
    (masked to pos < offset — the request's own pages are not written
    yet); blocks >= nblk_pages read the suffix operand at positions
    offset + local (masked causally per query row). Scratch carries the
    online-softmax state across the kv dimension; a block that is
    skipped or fully masked for some rows is self-correcting: the
    running-max rescale zeroes its contribution as soon as a real block
    lands (the same argument as the decode kernel's window masking).
    """
    b = pl.program_id(0)
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    off = off_ref[b]
    slen = len_ref[b]
    rows = qt * g
    d = q_ref.shape[-1]
    # per-row query position: row r holds token (qb*qt + r//g)
    qpos = (off + qb * qt
            + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 0) // g)

    def accum(h, k2d, v2d, valid):
        q2d = q_ref[0, h].astype(jnp.float32)              # (rows, D)
        s = jax.lax.dot_general(
            q2d, k2d, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (rows, LANE)
        s = jnp.where(valid, s, -1e30)
        r0 = h * rows
        m_prev = m_ref[r0:r0 + rows]
        l_prev = l_ref[r0:r0 + rows]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p_ = jnp.exp(s - m_new[:, :1])
        l_new = alpha * l_prev[:, :1] + jnp.sum(p_, axis=1, keepdims=True)
        acc_ref[r0:r0 + rows] = (
            acc_ref[r0:r0 + rows] * alpha + jax.lax.dot_general(
                p_, v2d, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_ref[r0:r0 + rows] = m_new
        l_ref[r0:r0 + rows] = jnp.broadcast_to(l_new, l_prev.shape)

    # ---- page blocks: prefix K/V read in place, by physical id --------
    base_tok = kb * (ppb * page)

    @pl.when((kb < nblk_pages) & (base_tok < off))
    def _pages():
        # per-page liveness gate: only pages whose first token sits
        # below the prefix end are fetched — a mid-block offset leaves
        # the trailing pages un-DMA'd (their lanes are masked below, so
        # stale buffer contents never contribute). Starts and waits run
        # under the SAME predicate, keeping the semaphore balanced.
        for i in range(ppb):                    # static unroll
            @pl.when(base_tok + i * page < off)
            def _start(i=i):
                pid = bt_ref[b * pages_max + kb * ppb + i]
                pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[i],
                                      sem).start()
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[i],
                                      sem).start()
        for i in range(ppb):
            @pl.when(base_tok + i * page < off)
            def _wait(i=i):
                pid = bt_ref[b * pages_max + kb * ppb + i]
                pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[i],
                                      sem).wait()
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[i],
                                      sem).wait()
        kvpos = base_tok + jax.lax.broadcasted_iota(
            jnp.int32, (rows, LANE), 1)
        valid = kvpos < off
        if window is not None:
            valid &= kvpos > qpos - window
        # lanes of pages the gate skipped hold UNINITIALIZED scratch
        # (NaN in interpret mode). Masked scores handle K, but a row
        # with no valid lane yet has p_ = exp(0) = 1 everywhere, so V
        # must be finite: zero the dead lanes (the rescale then wipes
        # their garbage weight exactly as before the gating)
        live = (base_tok + jax.lax.broadcasted_iota(
            jnp.int32, (ppb * page, 1), 0)) < off
        for h in range(hkv):                    # static unroll over heads
            accum(h, kbuf[:, h].reshape(ppb * page, d).astype(jnp.float32),
                  jnp.where(live,
                            vbuf[:, h].reshape(ppb * page, d),
                            0).astype(jnp.float32),
                  valid)

    # ---- suffix blocks: this dispatch's own K/V, causal ---------------
    s0 = (kb - nblk_pages) * LANE               # local suffix base

    @pl.when((kb >= nblk_pages) & (s0 < slen) & (s0 < (qb + 1) * qt))
    def _suffix():
        local = s0 + jax.lax.broadcasted_iota(jnp.int32, (rows, LANE), 1)
        kvpos = off + local
        valid = (local < slen) & (kvpos <= qpos)
        if window is not None:
            valid &= kvpos > qpos - window
        for h in range(hkv):
            accum(h, ks_ref[0, h].astype(jnp.float32),
                  vs_ref[0, h].astype(jnp.float32), valid)

    @pl.when(kb == nkv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], 1e-30)).reshape(
                        hkv, rows, d).astype(o_ref.dtype)


def _pow2_at_least(n: int, floor: int = 8) -> int:
    return max(floor, 1 << max(n - 1, 0).bit_length())


@functools.partial(jax.jit, static_argnames=("page_size", "interpret",
                                             "sliding_window"))
def ragged_prefill_attention(q, k_suf, v_suf, k_pages, v_pages,
                             block_tables, offsets, seq_lens,
                             page_size: int = 16,
                             interpret: bool = False,
                             sliding_window: Optional[int] = None):
    """Mosaic ragged paged-prefill attention.

    q: (B, Tq, Hq, D) suffix queries — row ``(b, j)`` sits at absolute
    position ``offsets[b] + j``; k_suf/v_suf: (B, Tq, Hkv, D) the
    suffix's own K/V (NOT yet in the pool — the caller scatters it into
    the request's pages after the layer scan); k_pages/v_pages:
    (P, Hkv, page_size, D); block_tables: (B, pages_max) physical page
    ids covering positions ``0 .. offsets[b]`` (entries beyond the
    prefix may be any valid id — masked); offsets/seq_lens: (B,) int32
    runtime prefix / true-suffix lengths. ``pages_max`` must be a
    multiple of ``LANE // page_size``. Query rows ``j >= seq_lens[b]``
    return finite garbage (callers slice to the true length). Returns
    (B, Tq, Hq, D) float32.
    """
    b, tq, hq, d = q.shape
    p_, hkv, page, _ = k_pages.shape
    assert page == page_size
    ppb = LANE // page_size
    pages_max = block_tables.shape[1]
    if pages_max % ppb:
        raise ValueError(f"pages_max {pages_max} not a multiple of {ppb}")
    nblk_pages = pages_max // ppb
    g = hq // hkv
    scale = 1.0 / float(np.sqrt(d))

    # pad the suffix to a pow2 tile count (padded rows masked/ignored)
    tq_pad = _pow2_at_least(tq)
    if tq_pad != tq:
        pad = ((0, 0), (0, tq_pad - tq), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k_suf = jnp.pad(k_suf, pad)
        v_suf = jnp.pad(v_suf, pad)
    # Mosaic page DMAs need a 128-aligned minor dim (same pad story as
    # the decode kernel: zero K columns leave scores unchanged, padded V
    # columns are sliced off below)
    d_orig = d
    if d % 128:
        dp = -(-d // 128) * 128
        dpad = (0, dp - d)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), dpad))
        k_suf = jnp.pad(k_suf, ((0, 0), (0, 0), (0, 0), dpad))
        v_suf = jnp.pad(v_suf, ((0, 0), (0, 0), (0, 0), dpad))
        k_pages = jnp.pad(k_pages, ((0, 0), (0, 0), (0, 0), dpad))
        v_pages = jnp.pad(v_pages, ((0, 0), (0, 0), (0, 0), dpad))
        d = dp

    # query tile: pow2, scratch rows (hkv * qt * g) bounded
    qt = tq_pad
    while qt > 8 and qt * g * hkv > _MAX_SCRATCH_ROWS:
        qt //= 2
    nqblk = tq_pad // qt
    rows = qt * g

    # row = token*g + group, so one q tile is qt contiguous tokens
    qg = (q.reshape(b, tq_pad, hkv, g, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, tq_pad * g, d))
    # suffix K/V padded to whole LANE blocks, head-major
    ts = -(-tq_pad // LANE) * LANE
    ks = k_suf.transpose(0, 2, 1, 3)                  # (B, Hkv, Tq, D)
    vs = v_suf.transpose(0, 2, 1, 3)
    if ts != tq_pad:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, ts - tq_pad), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, ts - tq_pad), (0, 0)))
    nblk_suf = ts // LANE
    nkv = nblk_pages + nblk_suf

    # VMEM this call needs. Pallas double-buffers every BlockSpec
    # operand; scratch is allocated once:
    #   q block       2 * hkv*rows*d * q.itemsize
    #   out block     2 * hkv*rows*d * 4
    #   ks, vs blocks 2 * 2 * hkv*LANE*d * kv.itemsize
    #   kbuf, vbuf    2 * hkv*LANE*d * pool.itemsize   (ppb*page = LANE)
    #   acc           hkv*rows*d * 4
    #   m, l          2 * hkv*rows*LANE * 4
    # plus values live inside the body: one head's (rows, d) / (rows,
    # LANE) f32 score, weight and product tiles, and the finish pass's
    # normalised (hkv*rows, d) f32 output — bounded here by one more
    # accumulator plus 4 MiB. At the row cap with d=128 and bf16
    # operands that is 18.0 MiB of buffers for MHA-32 (llama2_7b) and
    # 13.5 MiB for GQA-8 (mistral_7b) before the body's values: over,
    # or too close to, the 16 MiB scoped default of a v5e core, which
    # has 128 MiB. So the limit is stated, not defaulted.
    tile = hkv * rows * d
    kv_blk = hkv * LANE * d
    vmem_need = (2 * tile * q.dtype.itemsize + 2 * tile * 4
                 + 4 * kv_blk * k_suf.dtype.itemsize
                 + 2 * kv_blk * k_pages.dtype.itemsize
                 + tile * 4 + 2 * hkv * rows * LANE * 4)
    vmem_limit = vmem_need + tile * 4 + (4 << 20)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nqblk, nkv),
        in_specs=[
            pl.BlockSpec((1, hkv, rows, d),
                         lambda b_, q_, k_, *_: (b_, 0, q_, 0)),
            pl.BlockSpec((1, hkv, LANE, d),
                         lambda b_, q_, k_, *_:
                         (b_, 0, jnp.maximum(k_ - nblk_pages, 0), 0)),
            pl.BlockSpec((1, hkv, LANE, d),
                         lambda b_, q_, k_, *_:
                         (b_, 0, jnp.maximum(k_ - nblk_pages, 0), 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, rows, d),
                               lambda b_, q_, k_, *_: (b_, 0, q_, 0)),
        scratch_shapes=[
            pltpu.VMEM((ppb, hkv, page, d), k_pages.dtype),
            pltpu.VMEM((ppb, hkv, page, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.VMEM((hkv * rows, d), jnp.float32),
            pltpu.VMEM((hkv * rows, LANE), jnp.float32),
            pltpu.VMEM((hkv * rows, LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_prefill_kernel, page=page_size,
                          ppb=ppb, pages_max=pages_max, hkv=hkv, g=g,
                          qt=qt, nblk_pages=nblk_pages, scale=scale,
                          window=sliding_window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, tq_pad * g, d),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )(offsets.astype(jnp.int32), seq_lens.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), qg, ks, vs, k_pages,
      v_pages)
    out = (out.reshape(b, hkv, tq_pad, g, d)
           .transpose(0, 2, 1, 3, 4).reshape(b, tq_pad, hq, d))
    return out[:, :tq, :, :d_orig]


def ragged_prefill_reference(q, k_suf, v_suf, k_pages, v_pages,
                             block_tables, offsets, seq_lens,
                             sliding_window: Optional[int] = None):
    """XLA twin of :func:`ragged_prefill_attention` (same contract) —
    the CPU golden and the non-TPU serving path. The einsum/softmax
    structure mirrors the dense ``llama._attention`` single-block path
    so greedy outputs through the engine stay stable when the staging
    prefill is replaced by this one. The page gather is sliced to the
    live prefix span when ``offsets`` is concrete (the padded-capacity
    fix that also covers ``paged_attention_reference``)."""
    from bigdl_tpu.llm.kernels.paged_attention import _sliced_tables
    b, tq, hq, d = q.shape
    p_, hkv, page, _ = k_pages.shape
    g = hq // hkv
    block_tables = _sliced_tables(block_tables, offsets, page)
    pages_max = block_tables.shape[1]
    s_pages = pages_max * page
    k_pre = (k_pages[block_tables].transpose(0, 1, 3, 2, 4)
             .reshape(b, s_pages, hkv, d))
    v_pre = (v_pages[block_tables].transpose(0, 1, 3, 2, 4)
             .reshape(b, s_pages, hkv, d))
    k_all = jnp.concatenate([k_pre, k_suf], axis=1)    # (B, S, Hkv, D)
    v_all = jnp.concatenate([v_pre, v_suf], axis=1)
    qpos = offsets[:, None] + jnp.arange(tq)[None, :]          # (B, Tq)
    kvpos = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(s_pages)[None, :], (b, s_pages)),
         offsets[:, None] + jnp.arange(tq)[None, :]], axis=1)  # (B, S)
    valid = jnp.concatenate(
        [jnp.arange(s_pages)[None, :] < offsets[:, None],
         jnp.arange(tq)[None, :] < seq_lens[:, None]], axis=1)
    mask = valid[:, None, :] & (kvpos[:, None, :] <= qpos[:, :, None])
    if sliding_window is not None:
        mask &= kvpos[:, None, :] > qpos[:, :, None] - sliding_window
    qg = (q.reshape(b, tq, hkv, g, d).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32))                       # (B, Hkv, G, Tq, D)
    scale = 1.0 / float(np.sqrt(d))
    s = jnp.einsum("bhgtd,bshd->bhgts", qg,
                   k_all.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgts,bshd->bhgtd", p, v_all.astype(jnp.float32))
    return (out.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq, d)
            .astype(jnp.float32))


def ragged_prefill(q, k_suf, v_suf, k_pages, v_pages, block_tables,
                   offsets, seq_lens, page_size: int = 16,
                   interpret: Optional[bool] = None,
                   sliding_window: Optional[int] = None):
    """Backend dispatch: Mosaic kernel on TPU, XLA twin elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return ragged_prefill_reference(
                q, k_suf, v_suf, k_pages, v_pages, block_tables,
                offsets, seq_lens, sliding_window=sliding_window)
        interpret = False
    return ragged_prefill_attention(
        q, k_suf, v_suf, k_pages, v_pages, block_tables, offsets,
        seq_lens, page_size=page_size, interpret=interpret,
        sliding_window=sliding_window)
