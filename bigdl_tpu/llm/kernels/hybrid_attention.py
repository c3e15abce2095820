"""Attention over a cache of two page classes: layers that keep every
token beside layers that keep a window (ISSUE 31; the configuration the
chip runs is MiMo-V2.5's language model).

What differs from :mod:`paged_attention` / :mod:`ragged_prefill`, whose
kernels these are siblings of (Mistral's programs go on lowering to
exactly what they lowered to):

- **K and V of different widths** (192 / 128 published), **in ONE pool
  a class**: a cached row is ``[k | 0 | v]``, the key lane-padded to
  ``dk`` = 256 (a 192-wide minor dimension is tiled to 256 in HBM
  anyway, so the pad costs no byte it would not cost, as the latent
  pool holds 576 numbers at 640), the value in the ``dv`` = 128 columns
  after it. A page then comes in one DMA across all its KV heads, K
  and V together (half the copies of a K pool beside a V pool), and no
  call pads or copies a pool. The decode kernels walk a row's live
  blocks themselves, the next block in flight while this one is scored,
  and are bound by those copies alone: a block of 32 pages (1.57 MB)
  every 2.15 us, 733 GB/s, with the arithmetic (1.47 us) under them
  (the table in the decode section's header). Queries come padded like
  the keys (zero columns add nothing to a score).
- **``scale`` is a parameter** (``head_dim ** -0.5`` of the published
  192, not of the padded 256).
- **A window class is a ring** of ``ring_pages(window, page)`` pages a
  request: the token at position ``p`` lives in column ``(p // page) %
  ring`` of the request's ring table at slot ``p % page``, whatever the
  request's length. The kernels rebuild each slot's position from the
  number of tokens cached (:func:`ring_positions`) and mask by it, so a
  decode step over a window class walks the ring's pages and nothing
  else, and a prefill's window layers read the ring for the chunk's
  first ``window - 1`` queries only and skip the suffix blocks outside
  the band.
- **The sink** (one learned scalar a query head that joins the
  softmax's denominator and carries no value) is where the running
  state starts in prefill, ``(m, l) = (sink, 1)``: a virtual key with
  that score and a zero value. Decode returns the unnormalised state
  and :func:`paged_attention.merge_attention_partial` folds the sink in
  with the current token.
- Scores and the weighted values go through the MXU with the pools'
  own bfloat16 operands and a float32 accumulator (bf16 x bf16
  products are exact in float32); only the softmax weights are rounded
  to bfloat16 in front of ``P V``.

The XLA twins (``*_reference``) are the CPU goldens and the path off
the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.llm.kernels.paged_attention import LANE
from bigdl_tpu.llm.kernels.ragged_prefill import _pow2_at_least

# cached tokens a block of the full-class decode kernel's walk fetches and
# scores (two buffer slots of a block: 3.1 MB of VMEM)
DECODE_BLOCK_TOKENS = 512
# accumulator rows (kv heads x query tile x group) of the prefill kernel
_MAX_SCRATCH_ROWS = 4096


def ring_pages(window: int, page: int) -> int:
    """Pages of a ring that always holds the last ``window`` positions
    while a page is being filled: ``ceil((window + page) / page)``,
    rounded up to the kernels' block of ``LANE // page`` pages."""
    ppb = max(1, LANE // page)
    need = -(-(window + page) // page)
    return -(-need // ppb) * ppb


def ring_positions(cols, slots, cached, page: int, ring: int):
    """The position held at ``(cols, slots)`` of a ring table once
    positions ``0 .. cached - 1`` are written: the newest page number
    ``n <= (cached - 1) // page`` with ``n % ring == col``. Negative
    where the column was never written; a slot past ``cached - 1`` in
    the newest page reads ``>= cached`` (stale: callers mask it)."""
    cur = (cached - 1) // page
    n = cur - (cur - cols + ring) % ring
    return jnp.where((cached > 0) & (n >= 0), n * page + slots, -1)


def _flash_update(s, v2d, r0, rows, acc_ref, m_ref, l_ref):
    """One block of the online softmax for accumulator rows ``r0 ..
    r0 + rows``: ``s`` (rows, n) masked scores, ``v2d`` (n, Dv)."""
    m_prev = m_ref[r0:r0 + rows]
    l_prev = l_ref[r0:r0 + rows]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p_ = jnp.exp(s - m_new[:, :1])
    l_new = alpha * l_prev[:, :1] + jnp.sum(p_, axis=1, keepdims=True)
    acc_ref[r0:r0 + rows] = (
        acc_ref[r0:r0 + rows] * alpha + jax.lax.dot_general(
            p_.astype(v2d.dtype), v2d, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    m_ref[r0:r0 + rows] = m_new
    l_ref[r0:r0 + rows] = jnp.broadcast_to(l_new, l_prev.shape)


def _unrolled(n: int, op):
    """``op(i)`` for ``i`` in ``range(n)``, in a kernel: traced ONCE and
    unrolled where the kernel is lowered, to the module a Python loop
    gives. A decode program is traced on the serving host at every
    start, and 64 copy starts traced one by one took longer there than
    the rest of the body."""
    jax.lax.fori_loop(0, n, lambda i, c: (op(i), c)[1], 0, unroll=True)


def _scores(q2d, k2d, scale):
    return jax.lax.dot_general(
        q2d.astype(k2d.dtype), k2d, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


# ---------------------------------------------------------------------------
# decode
#
# One call at the cell's shapes (q (32, 64, 256) bf16, the flat full pool
# (86,000, 4, 16, 384) bf16, table (32, 2176), 14 live rows of 0.9-22.6k
# tokens with dead rows between them, 97,993 in all = 201 blocks of 512;
# 306.3 us at 819 GB/s and 320 numbers a row and head), by the slope of a
# loop of calls (tools/exp_hybrid_body.py; chip runs, PR 34). In brackets
# 18 live rows of 0.6-34.8k, 177,896 tokens = 356 blocks (556.1 us), the
# batch of the cell's judged steps:
#   (a) PR 31's kernel: grid (32, 68), a block's 32 page DMAs started
#       and awaited inside its grid step               862.0 us [1,462.0]
#   (b) the same, every length zero: 2,176 empty grid steps       80.0
#   (c) (a)'s DMAs with the arithmetic taken out          543.0 [934.8]
#   (d) (a)'s arithmetic on a resident buffer, no DMA     366.9 [575.9]
#   the walk below (one grid step a row, two slots)       432.9 [755.7]
#     its DMAs alone | its arithmetic alone       430.9 | 295.8 [752.5 | 513.5]
#     blocks of 768 | 1,024 tokens        435.8 | 451.2 [763.5 | 768.4]
#     no next row's first block fetched ahead                    457.7
#     a block's code written once a slot (static indices) 433.6 [754.8]
#     the fetch ahead started after this block's wait            531.1
#     two KV heads' query rows streamed through every key tile   433.3
# (c) > (d): a grid step was bound by starting and awaiting its 32 copies
# (2.3 us a block), not by its products (1.47), and the empty steps were
# a tenth. The walk runs at 2.15 us a block, which is what its copies
# alone take: 1.57 MB a block at 733 GB/s, nine tenths of the chip's
# rate; the arithmetic is under them whole, so neither the MXU's tile
# loads (the pair variant changes nothing) nor a slot's static indices
# are in the way. Rows are held 384 wide for 320 read and whole blocks
# are fetched, so 83 % of the roofline is the ceiling; the walk reads
# 70.8 % [73.6]. Larger blocks read more past a row's end: 512 is kept.
#
# The window class, (32, 64, 256) over (2,565, 8, 16, 384), a ring of 16
# pages a row, the same 14 [18] live rows (11.2 [14.4] us at 819 GB/s
# and the window's 128 rows; whole rings of 256 rows x 384 are fetched:
# 42 % is the ceiling):
#   (a) 76.4 [95.1]  (b) 6.3  (c) 42.2  (d) 41.3
#   the walk 46.3 [56.2]: its DMAs alone 36.4, its arithmetic alone 39.4;
#     no next row's ring fetched ahead 76.3; static indices 46.1 [54.1];
#     the fetch ahead started after the wait 47.2
# A window row is a walk of length one: all it gains is the next live
# row's ring in flight while this one is scored, and that is two fifths
# of the call. What is left is the ring's 16 pages fetched for the 9 a
# window of 128 needs, and 8 KV heads of 8 query rows on the MXU.
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, bt_ref, q_ref, kv_hbm, o_ref, mo_ref, lo_ref,
                   buf, sem, walked, *, page: int, ppb: int, pages_max: int,
                   hkv: int, scale: float, window: Optional[int]):
    """One batch row ``b``, page-major: a page comes across all its KV
    heads, keys and values, in one DMA. The row's live blocks of ``ppb``
    pages, ``ceil(len / (ppb·page))`` of them (with ``window`` the table
    is a ring and the one block is all of it), are walked here and not
    by the grid: block ``j`` is scored out of one slot of ``buf`` while
    block ``j + 1``, or after the row's last block the first block of
    the next row that has any, is fetched into the other. ``walked[0]``
    counts the blocks of the rows before this one: its parity is the
    slot this row starts in, and a row finds its first block on the way
    unless it is the first to have one. Every block started is awaited
    once, by the row that scores it. q_ref (1, hkv, gp, Dk) VMEM; kv_hbm
    (P, hkv, page, Dk + Dv) in HBM; buf (2, ppb, hkv, page, Dk + Dv);
    the running sums live in the row's output blocks o (1, hkv, gp, Dv),
    m and l (1, hkv, gp, LANE)."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    n = ppb * page
    gp, dk = q_ref.shape[2], q_ref.shape[3]

    @pl.when(b == 0)
    def _first_row():
        walked[0] = 0

    seq = len_ref[b]
    nblk = (seq + (n - 1)) // n if window is None else jnp.minimum(seq, 1)
    first = walked[0]

    def fetch(row, blk, slot):
        def start(i):
            # a table shorter than a whole block: the pages past its
            # end are past every length too, any valid page will do
            col = jnp.minimum(blk * ppb + i, pages_max - 1)
            pid = bt_ref[row * pages_max + col]
            pltpu.make_async_copy(kv_hbm.at[pid], buf.at[slot, i],
                                  sem.at[slot]).start()
        _unrolled(ppb, start)

    o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)
    mo_ref[0] = jnp.full(mo_ref.shape[1:], -1e30, jnp.float32)
    lo_ref[0] = jnp.zeros(lo_ref.shape[1:], jnp.float32)

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

        # started BEFORE this block's wait: after it, the chain wait ->
        # start -> transfer is serial (+23 % on the chip)
        @pl.when(more | (nxt < rows))
        def _fetch_ahead():
            fetch(jnp.where(more, b, nxt), jnp.where(more, j + 1, 0),
                  1 - slot)

        # a wait reads the size of its destination and the semaphore
        _unrolled(ppb, lambda i: pltpu.make_async_copy(
            kv_hbm.at[0], buf.at[slot, i], sem.at[slot]).wait())
        idx = jax.lax.broadcasted_iota(jnp.int32, (gp, n), 1)
        if window is None:
            valid = j * n + idx < seq
        else:
            pos = ring_positions(idx // page, idx % page, seq, page,
                                 pages_max)
            valid = (pos >= 0) & (pos < seq) & (pos > seq - window)

        def head(h):
            kv = buf[slot, :, h].reshape(n, buf.shape[-1])
            s = _scores(q_ref[0, h], kv[:, :dk], scale)
            _flash_update(jnp.where(valid, s, -1e30), kv[:, dk:], 0, gp,
                          o_ref.at[0, h], mo_ref.at[0, h], lo_ref.at[0, h])
        _unrolled(hkv, head)
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    walked[0] = first + nblk


def _decode_stats(q, kv_pages, block_tables, lengths, *, page_size: int,
                  scale: float, window: Optional[int], interpret: bool,
                  name: str):
    b, hq, dk = q.shape
    _, hkv, page, width = kv_pages.shape
    dv = width - dk
    if page != page_size or dv <= 0 or dk % LANE or dv % LANE:
        raise ValueError(
            f"pool {kv_pages.shape}, queries {q.shape}: want (P, Hkv, "
            f"{page_size}, Dk + Dv) and (B, Hq, Dk), Dk and Dv "
            f"multiples of {LANE}")
    pages_max = block_tables.shape[1]
    if window is None:
        ppb = max(1, min(DECODE_BLOCK_TOKENS // page, pages_max))
    else:
        ppb = pages_max                         # the whole ring, once
    g = hq // hkv
    gp = max(8, -(-g // 8) * 8)
    qg = q.reshape(b, hkv, g, dk)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    row = lambda b_, *_: (b_, 0, 0, 0)
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, page=page, ppb=ppb,
                          pages_max=pages_max, hkv=hkv, scale=scale,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, hkv, gp, dk), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, hkv, gp, dv), row),
                       pl.BlockSpec((1, hkv, gp, LANE), row),
                       pl.BlockSpec((1, hkv, gp, LANE), row)],
            scratch_shapes=[
                pltpu.VMEM((2, ppb, hkv, page, width), kv_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, gp, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, gp, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, gp, LANE), jnp.float32)],
        # a row hands its successor a block on the way and the slot to
        # find it in: the rows run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(lengths.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), qg, kv_pages)
    return (acc[:, :, :g].reshape(b, hq, dv),
            m[:, :, :g, 0].reshape(b, hq), l[:, :, :g, 0].reshape(b, hq))


@functools.partial(jax.jit, static_argnames=("page_size", "scale",
                                             "interpret"))
def full_attention_decode_stats(q, kv_pages, block_tables, lengths, *,
                                page_size: int, scale: float,
                                interpret: bool = False):
    """Decode attention over a class that keeps every token. ``q`` (B,
    Hq, Dk); ``kv_pages`` (P, Hkv, page, Dk + Dv) rows ``[key | value]``,
    both widths multiples of 128; ``block_tables`` (B, pages_max),
    column ``c`` the page of positions ``c·page ..``; ``lengths`` (B,)
    tokens cached. Returns the flash-style partial state ``(acc (B, Hq,
    Dv) float32 unnormalised, m (B, Hq), l (B, Hq))`` over them, the
    identity ``(0, -1e30, 0)`` where that is none."""
    return _decode_stats(q, kv_pages, block_tables, lengths,
                         page_size=page_size, scale=scale, window=None,
                         interpret=interpret,
                         name="full_attention_decode")


@functools.partial(jax.jit, static_argnames=("page_size", "scale",
                                             "window", "interpret"))
def window_attention_decode_stats(q, kv_pages, ring_tables, lengths, *,
                                  page_size: int, scale: float,
                                  window: int, interpret: bool = False):
    """:func:`full_attention_decode_stats` over a class that keeps a
    window: ``ring_tables`` (B, ring) as :func:`ring_positions` reads
    them; of the ``lengths`` tokens cached the query (at position
    ``lengths``) sees those at ``lengths - window + 1`` and after. One
    grid step a row: the ring's pages and no other."""
    return _decode_stats(q, kv_pages, ring_tables, lengths,
                         page_size=page_size, scale=scale, window=window,
                         interpret=interpret,
                         name="window_attention_decode")


def _gather(pages, tables):
    """(P, Hkv, page, D) through (B, C) -> (B, C·page, Hkv, D)."""
    b, c = tables.shape
    _, hkv, page, d = pages.shape
    return pages[tables].transpose(0, 1, 3, 2, 4).reshape(
        b, c * page, hkv, d)


def _table_positions(tables, cached, page: int, window: Optional[int]):
    """(B, C·page) position of every slot a table names (negative: none)
    and whether a query at position ``cached`` may see it."""
    b, c = tables.shape
    idx = jnp.arange(c * page, dtype=jnp.int32)[None, :]
    cached = cached.astype(jnp.int32)[:, None]
    if window is None:
        pos = jnp.broadcast_to(idx, (b, c * page))
        return pos, pos < cached
    pos = ring_positions(idx // page, idx % page, cached, page, c)
    return pos, (pos >= 0) & (pos < cached)


def attention_decode_reference_stats(q, kv_pages, block_tables, lengths,
                                     *, scale: float,
                                     window: Optional[int] = None):
    """XLA twin of the two decode kernels (``window`` None: the full
    class; else the ring): a gather of every page the table names and
    masked scores in float32."""
    b, hq, dk = q.shape
    _, hkv, page, _ = kv_pages.shape
    g = hq // hkv
    kv_all = _gather(kv_pages, block_tables).astype(jnp.float32)
    k_all, v_all = kv_all[..., :dk], kv_all[..., dk:]
    pos, mask = _table_positions(block_tables, lengths, page, window)
    if window is not None:
        mask &= pos > lengths[:, None] - window
    qg = q.reshape(b, hkv, g, dk).astype(jnp.float32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, k_all) * scale
    mask = mask[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bhgs,bshd->bhgd", p, v_all)
    m = jnp.where(jnp.any(mask, axis=-1), m, -1e30)
    return (acc.reshape(b, hq, -1), m.reshape(b, hq),
            jnp.sum(p, axis=-1).reshape(b, hq))


def attention_decode_stats(q, kv_pages, block_tables, lengths, *,
                           page_size: int, scale: float,
                           window: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Backend dispatch: the Mosaic kernels on TPU, the gather
    elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return attention_decode_reference_stats(
                q, kv_pages, block_tables, lengths, scale=scale,
                window=window)
        interpret = False
    if window is None:
        return full_attention_decode_stats(
            q, kv_pages, block_tables, lengths, page_size=page_size,
            scale=scale, interpret=interpret)
    return window_attention_decode_stats(
        q, kv_pages, block_tables, lengths, page_size=page_size,
        scale=scale, window=window, interpret=interpret)


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _prefill_kernel(off_ref, len_ref, bt_ref, q_ref, ks_ref, vs_ref, *rest,
                    page: int, ppb: int, pages_max: int, hkv: int, g: int,
                    qt: int, nblk_pages: int, scale: float,
                    window: Optional[int], has_sink: bool):
    """One (batch row b, query block qb, kv block kb) step of
    :func:`ragged_prefill._ragged_prefill_kernel`'s plan: kv blocks
    ``[0, nblk_pages)`` read what is cached (positions below ``off``)
    through the table, the others the chunk's own K/V. With ``window``
    the table is a ring, read by the query blocks that still see below
    ``off``, and suffix blocks outside a query block's band are skipped.
    ``sink_ref`` (hkv·rows, LANE) is where the running maximum starts
    (and the running sum at 1)."""
    if has_sink:
        sink_ref, kv_hbm, o_ref, buf, sem, acc_ref, m_ref, l_ref = rest
    else:
        kv_hbm, o_ref, buf, sem, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if has_sink:
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)

    off = off_ref[b]
    slen = len_ref[b]
    rows = qt * g
    n = ppb * page
    dk = q_ref.shape[-1]
    dv = vs_ref.shape[-1]
    # per-row query position: row r holds token (qb*qt + r//g)
    qpos = (off + qb * qt
            + jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0) // g)
    idx = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)

    def accum(h, k2d, v2d, valid):
        s = _scores(q_ref[0, h], k2d, scale)
        _flash_update(jnp.where(valid, s, -1e30), v2d, h * rows, rows,
                      acc_ref, m_ref, l_ref)

    base_tok = kb * n
    if window is None:
        read_pages = (kb < nblk_pages) & (base_tok < off)
    else:
        read_pages = (kb < nblk_pages) & (off > 0) & \
            (qb * qt < window - 1)

    @pl.when(read_pages)
    def _pages():
        # the block's pages whole (a table entry past the row's own
        # pages names the trash page: finite, and masked below)
        copies = []
        for i in range(ppb):                    # static unroll
            col = jnp.minimum(kb * ppb + i, pages_max - 1)
            pid = bt_ref[b * pages_max + col]
            c = pltpu.make_async_copy(kv_hbm.at[pid], buf.at[i], sem)
            c.start()
            copies.append(c)
        for c in copies:
            c.wait()
        if window is None:
            kvpos = base_tok + idx
            valid = kvpos < off
        else:
            kvpos = ring_positions(kb * ppb + idx // page, idx % page,
                                   off, page, pages_max)
            valid = (kvpos >= 0) & (kvpos < off) & (kvpos > qpos - window)
        for h in range(hkv):                    # static unroll over heads
            kv = buf[:, h].reshape(n, dk + dv)
            accum(h, kv[:, :dk], kv[:, dk:], valid)

    # ---- suffix blocks: this dispatch's own K/V, causal ---------------
    s0 = (kb - nblk_pages) * LANE               # local suffix base
    read_suffix = (kb >= nblk_pages) & (s0 < slen) & (s0 < (qb + 1) * qt)
    if window is not None:
        read_suffix &= s0 + LANE > qb * qt - window + 1

    @pl.when(read_suffix)
    def _suffix():
        local = s0 + idx
        kvpos = off + local
        valid = (local < slen) & (kvpos <= qpos)
        if window is not None:
            valid &= kvpos > qpos - window
        for h in range(hkv):
            accum(h, ks_ref[0, h], vs_ref[0, h], valid)

    @pl.when(kb == nkv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], 1e-30)).reshape(
                        hkv, rows, dv).astype(o_ref.dtype)


def _prefill(q, k_suf, v_suf, kv_pages, block_tables, offsets, seq_lens,
             sink, *, page_size: int, scale: float, window: Optional[int],
             interpret: bool, name: str):
    b, tq, hq, dk = q.shape
    _, hkv, page, width = kv_pages.shape
    dv = v_suf.shape[-1]
    ppb = LANE // page_size
    pages_max = block_tables.shape[1]
    if page != page_size or width != dk + dv or dk % LANE or dv % LANE \
            or pages_max % ppb:
        raise ValueError(
            f"pool {kv_pages.shape}, queries {q.shape}, values "
            f"{v_suf.shape}, table {block_tables.shape}: want rows of Dk "
            f"+ Dv, both multiples of {LANE}, and table columns a "
            f"multiple of {ppb}")
    nblk_pages = pages_max // ppb
    g = hq // hkv

    tq_pad = _pow2_at_least(tq)
    if tq_pad != tq:
        pad = ((0, 0), (0, tq_pad - tq), (0, 0), (0, 0))
        q, k_suf, v_suf = (jnp.pad(a, pad) for a in (q, k_suf, v_suf))
    qt = tq_pad
    while qt > 8 and qt * g * hkv > _MAX_SCRATCH_ROWS:
        qt //= 2
    nqblk = tq_pad // qt
    rows = qt * g

    # row = token*g + group, so one q tile is qt contiguous tokens
    qg = (q.reshape(b, tq_pad, hkv, g, dk).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, tq_pad * g, dk))
    ts = -(-tq_pad // LANE) * LANE
    ks = k_suf.transpose(0, 2, 1, 3)                  # (B, Hkv, Tq, Dk)
    vs = v_suf.transpose(0, 2, 1, 3)
    if ts != tq_pad:
        tail = ((0, 0), (0, 0), (0, ts - tq_pad), (0, 0))
        ks, vs = jnp.pad(ks, tail), jnp.pad(vs, tail)
    nkv = nblk_pages + ts // LANE

    suf = lambda b_, q_, k_, *_: (b_, 0, jnp.maximum(k_ - nblk_pages, 0), 0)
    tile = lambda b_, q_, k_, *_: (b_, 0, q_, 0)
    in_specs = [pl.BlockSpec((1, hkv, rows, dk), tile),
                pl.BlockSpec((1, hkv, LANE, dk), suf),
                pl.BlockSpec((1, hkv, LANE, dv), suf)]
    operands = [qg, ks, vs]
    if sink is not None:
        # accumulator row r of head h belongs to query head h*g + r % g
        start = jnp.tile(sink.astype(jnp.float32).reshape(hkv, 1, g),
                         (1, qt, 1)).reshape(hkv * rows, 1)
        operands.append(jnp.broadcast_to(start, (hkv * rows, LANE)))
        in_specs.append(pl.BlockSpec((hkv * rows, LANE),
                                     lambda b_, q_, k_, *_: (0, 0)))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    # Pallas double-buffers every BlockSpec operand; scratch is there
    # once; the body holds one head's score, weight and product tiles
    it = q.dtype.itemsize
    acc_rows = hkv * rows
    vmem = (2 * acc_rows * dk * it + 2 * acc_rows * dv * it
            + 4 * hkv * LANE * (dk + dv) * it
            + hkv * LANE * (dk + dv) * kv_pages.dtype.itemsize
            + acc_rows * dv * 4 + 2 * acc_rows * LANE * 4
            + (2 * acc_rows * LANE * 4 if sink is not None else 0)
            + acc_rows * dv * 4 + 4 * rows * LANE * 4 + (4 << 20))
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, page=page_size, ppb=ppb,
                          pages_max=pages_max, hkv=hkv, g=g, qt=qt,
                          nblk_pages=nblk_pages, scale=scale,
                          window=window, has_sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nqblk, nkv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hkv, rows, dv), tile),
            scratch_shapes=[
                pltpu.VMEM((ppb, hkv, page, width), kv_pages.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.VMEM((acc_rows, dv), jnp.float32),
                pltpu.VMEM((acc_rows, LANE), jnp.float32),
                pltpu.VMEM((acc_rows, LANE), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, tq_pad * g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret, name=name,
    )(offsets.astype(jnp.int32), seq_lens.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), *operands, kv_pages)
    out = (out.reshape(b, hkv, tq_pad, g, dv)
           .transpose(0, 2, 1, 3, 4).reshape(b, tq_pad, hq, dv))
    return out[:, :tq]


@functools.partial(jax.jit, static_argnames=("page_size", "scale",
                                             "interpret"))
def full_prefill_attention(q, k_suf, v_suf, kv_pages, block_tables,
                           offsets, seq_lens, sink=None, *,
                           page_size: int, scale: float,
                           interpret: bool = False):
    """:func:`ragged_prefill.ragged_prefill_attention` for a class that
    keeps every token, K and V of their own (lane-multiple) widths in
    one pool of ``[key | value]`` rows, an explicit ``scale`` and an
    optional ``sink`` (Hq,). q (B, Tq, Hq, Dk); k_suf (B, Tq, Hkv, Dk),
    v_suf (B, Tq, Hkv, Dv) the chunk's own K/V, not yet in the pool;
    kv_pages (P, Hkv, page, Dk + Dv); row ``(b, j)`` sits at position
    ``offsets[b] + j`` and sees the ``offsets[b]`` cached positions and
    the chunk up to itself. Returns (B, Tq, Hq, Dv) in q's dtype."""
    return _prefill(q, k_suf, v_suf, kv_pages, block_tables,
                    offsets, seq_lens, sink, page_size=page_size,
                    scale=scale, window=None, interpret=interpret,
                    name="full_attention_prefill")


@functools.partial(jax.jit, static_argnames=("page_size", "scale",
                                             "window", "interpret"))
def window_prefill_attention(q, k_suf, v_suf, kv_pages, ring_tables,
                             offsets, seq_lens, sink=None, *,
                             page_size: int, scale: float, window: int,
                             interpret: bool = False):
    """:func:`full_prefill_attention` for a class that keeps a window:
    a query at position ``t`` sees ``t - window + 1 .. t``, the cached
    ones among them in the ring ``ring_tables`` (B, ring) names. Banded
    work: a query block reads the ring only while it still sees below
    ``offsets``, and of the chunk the blocks inside its band."""
    return _prefill(q, k_suf, v_suf, kv_pages, ring_tables,
                    offsets, seq_lens, sink, page_size=page_size,
                    scale=scale, window=window, interpret=interpret,
                    name="window_attention_prefill")


def prefill_attention_reference(q, k_suf, v_suf, kv_pages, block_tables,
                                offsets, seq_lens, sink=None, *,
                                scale: float,
                                window: Optional[int] = None):
    """XLA twin of the two prefill kernels: a gather of every page the
    table names beside the chunk, a (Tq, S) mask by position, the sink
    as one more softmax column with no value. Float32."""
    b, tq, hq, dk = q.shape
    _, hkv, page, _ = kv_pages.shape
    g = hq // hkv
    cached = _gather(kv_pages, block_tables)
    k_all = jnp.concatenate([cached[..., :dk], k_suf], 1)
    v_all = jnp.concatenate([cached[..., dk:], v_suf], 1)
    pos, seen = _table_positions(block_tables, offsets, page, window)
    own = offsets[:, None] + jnp.arange(tq, dtype=jnp.int32)[None, :]
    kvpos = jnp.concatenate([pos, own], axis=1)               # (B, S)
    valid = jnp.concatenate(
        [seen, jnp.arange(tq)[None, :] < seq_lens[:, None]], axis=1)
    mask = valid[:, None, :] & (kvpos[:, None, :] <= own[:, :, None])
    if window is not None:
        mask &= kvpos[:, None, :] > own[:, :, None] - window
    qg = (q.reshape(b, tq, hkv, g, dk).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32))                       # (B, Hkv, G, Tq, Dk)
    s = jnp.einsum("bhgtd,bshd->bhgts", qg,
                   k_all.astype(jnp.float32)) * scale
    s = jnp.where(mask[:, None, None], s, -1e30)
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1),
            s.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgts,bshd->bhgtd", p, v_all.astype(jnp.float32))
    return (out.transpose(0, 3, 1, 2, 4).reshape(b, tq, hq, -1)
            .astype(q.dtype))


def prefill_attention(q, k_suf, v_suf, kv_pages, block_tables,
                      offsets, seq_lens, sink=None, *, page_size: int,
                      scale: float, window: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """Backend dispatch: the Mosaic kernels on TPU, the twin elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return prefill_attention_reference(
                q, k_suf, v_suf, kv_pages, block_tables, offsets,
                seq_lens, sink, scale=scale, window=window)
        interpret = False
    if window is None:
        return full_prefill_attention(
            q, k_suf, v_suf, kv_pages, block_tables, offsets, seq_lens,
            sink, page_size=page_size, scale=scale, interpret=interpret)
    return window_prefill_attention(
        q, k_suf, v_suf, kv_pages, block_tables, offsets, seq_lens, sink,
        page_size=page_size, scale=scale, window=window,
        interpret=interpret)
