"""Learned sparse attention over a latent cache (DeepSeek Sparse
Attention, ``glm_moe_dsa``'s): an indexer scores every cached token of a
row, an exact top-``k`` keeps ``k`` of them, and latent attention reads
those rows only.

A decode step runs three named parts a layer:

- :func:`index_scores_decode` (Pallas): walks a row's pages of index
  keys ``(P, 1, page, D)``, ``ppb`` pages a block, two buffer slots as
  ``paged_attention._latent_decode_kernel`` walks the latent pool (the
  next block, or after a row's last block the next live row's first, is
  fetched while this one is scored). A block is scored on the MXU
  ``(Hi, D) x (D, n)``, then ``ReLU`` and the weighted sum over the
  heads: ``I[s] = sum_j w_j relu(q_j . k_s)``, float32, for positions
  ``0 .. len - 1``.
- :func:`dsa_select` (a jitted top-k: a stable sort): the current token's
  score at position ``len`` beside the cached ones, everything past it
  ``-inf``, the ``k`` largest kept; a row of ``len + 1 <= k`` keeps
  everything. Ties go to the lower position, as ``lax.top_k`` breaks
  them. Exact: an approximate top-k is another result.
- :func:`sparse_latent_attention` (Pallas): absorbed-form multi-query
  attention over the selected rows, gathered from the latent pool by
  the pool rows the selection carried (:func:`pool_rows`,
  :func:`gather_selected`: an XLA gather of whole rows; a Mosaic DMA of
  one 16-bit row of a page is not aligned to the pool's tiling), scored
  a block of 512 at a time with a running softmax. Returns the
  flash-style partial ``(acc, m, l)`` over the cached rows it was
  given; the current token is folded in by the caller only where it
  was selected.

Each has an XLA twin for the CPU (``*_reference``), which the engine
runs off the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.llm.kernels.paged_attention import LANE

# cached index keys a block of the scoring walk fetches (32 pages of 16
# at 128 bfloat16 numbers: 128 KB)
INDEX_BLOCK_TOKENS = 512
# selected rows the sparse attention scores at a time
SPARSE_BLOCK_ROWS = 512


# ---------------------------------------------------------------------------
# (a) scoring
# ---------------------------------------------------------------------------

def _index_score_kernel(len_ref, bt_ref, q_ref, w_ref, k_hbm, o_ref, buf,
                        sem, walked, *, page: int, ppb: int, pages_max: int,
                        widen: bool):
    """One batch row ``b``: its ``ceil(len / n)`` blocks of ``n = ppb *
    page`` cached keys walked here (the latent kernel's two-slot walk;
    ``walked[0]`` counts the blocks of the rows before this one), each
    scored by every head and reduced to one float32 score a position,
    written to the row's output at the block's offset. Positions past
    the last block are left unwritten (the caller masks past ``len``).
    q_ref (1, Hi, D); w_ref (1, Hi, 1) float32; k_hbm (P, 1, page, D) in
    HBM; buf (2, ppb, page, D); o_ref (1, 1, S)."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    n = ppb * page
    d = q_ref.shape[2]

    @pl.when(b == 0)
    def _first_row():
        walked[0] = 0

    seq = len_ref[b]
    nblk = (seq + (n - 1)) // n
    first = walked[0]

    def fetch(row, blk, slot):
        for i in range(ppb):                    # static unroll
            col = jnp.minimum(blk * ppb + i, pages_max - 1)
            pid = bt_ref[row * pages_max + col]
            pltpu.make_async_copy(k_hbm.at[pid, 0], buf.at[slot, i],
                                  sem.at[slot]).start()

    @pl.when((nblk > 0) & (first == 0))
    def _nobody_fetched_it():
        fetch(b, 0, 0)

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
            pltpu.make_async_copy(k_hbm.at[0, 0], buf.at[slot, i],
                                  sem.at[slot]).wait()
        q = q_ref[0]                                        # (Hi, D)
        k = buf[slot].reshape(n, d)
        if widen:       # interpret mode on the CPU: no bf16 x bf16 -> f32
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                    keepdims=True)                          # (1, n)
        o_ref[0, :, pl.ds(pl.multiple_of(j * n, n), n)] = s
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    walked[0] = first + nblk


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def index_scores_decode(q, w, k_pages, block_tables, lengths, *,
                        page_size: int, interpret: bool = False):
    """``q`` (B, Hi, D) the indexer's queries (the pool's dtype), ``w``
    (B, Hi) float32 head weights, ``k_pages`` (P, 1, page, D) the index
    keys, ``block_tables`` (B, pages_max) -> (B, pages_max · page)
    float32 scores ``sum_j w_j relu(q_j . k_s)`` of positions ``s <
    lengths``; what lies past a row's last block of 512 is unwritten."""
    b, hi, d = q.shape
    _, one, page, dp = k_pages.shape
    if one != 1 or page != page_size or dp != d or d % LANE or hi % 8:
        raise ValueError(
            f"index pool {k_pages.shape} / queries {q.shape}: want "
            f"(P, 1, {page_size}, D) and (B, Hi, D), D a multiple of "
            f"{LANE}, Hi of 8")
    pages_max = block_tables.shape[1]
    ppb = max(1, min(INDEX_BLOCK_TOKENS // page, pages_max))
    # whole blocks: the output row holds every block the walk may write
    s_max = -(-pages_max // ppb) * ppb * page
    row = lambda b_, *_: (b_, 0, 0)
    out = pl.pallas_call(
        functools.partial(_index_score_kernel, page=page, ppb=ppb,
                          pages_max=pages_max, widen=bool(interpret)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, hi, d), row),
                      pl.BlockSpec((1, hi, 1), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, s_max), row),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, d), k_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, s_max), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.reshape(-1).astype(jnp.int32),
      q, w.astype(jnp.float32)[..., None], k_pages)
    return out[:, 0, :pages_max * page]


def index_scores_reference(q, w, k_pages, block_tables, lengths):
    """XLA twin of :func:`index_scores_decode`: a gather of the row's
    pages, the same sums, and zero past ``lengths``."""
    b = q.shape[0]
    page, d = k_pages.shape[2], k_pages.shape[3]
    s_max = block_tables.shape[1] * page
    k = k_pages[block_tables][:, :, 0].reshape(b, s_max, d) \
        .astype(jnp.float32)
    s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32), k)
    s = jnp.einsum("bhs,bh->bs", jnp.maximum(s, 0.0),
                   w.astype(jnp.float32))
    return jnp.where(jnp.arange(s_max)[None] < lengths[:, None], s, 0.0)


def index_scores(q, w, k_pages, block_tables, lengths, *, page_size: int,
                 interpret: Optional[bool] = None):
    """Backend dispatch: Mosaic kernel on TPU, XLA gather elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return index_scores_reference(q, w, k_pages, block_tables,
                                          lengths)
        interpret = False
    return index_scores_decode(q, w, k_pages, block_tables, lengths,
                               page_size=page_size, interpret=interpret)


def index_score_rows(q, w, keys):
    """Scores of ``keys`` (..., S, D) by the queries ``q`` (..., Hi, D)
    with head weights ``w`` (..., Hi): (..., S) float32. The plain form
    the kernel computes, for a handful of keys (the current token's)."""
    s = jnp.einsum("...hd,...sd->...hs", q.astype(jnp.float32),
                   keys.astype(jnp.float32))
    return jnp.einsum("...hs,...h->...s", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32))


# ---------------------------------------------------------------------------
# (b) selection
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def dsa_select(scores, current, lengths, payload=None, *, k: int):
    """``scores`` (B, S) of the cached positions (only ``< lengths``
    are read), ``current`` (B,) the score of the token at position
    ``lengths`` -> ``(what (B, k) int32, selected (B, k) bool)``: the
    ``min(k, len + 1)`` highest-scored positions ``<= len``, the current
    token competing like a cached one; ties go to the lower position.
    ``what`` is each selected position, or its entry of ``payload`` (B,
    S) int32 where one is given (the decode step's: the pool row a
    position is cached in), carried through the same sort so that no
    gather follows it. ``selected`` is False where a row has fewer than
    ``k``. ``lax.top_k``'s form: a stable sort of the negated scores
    with the payload beside them."""
    pos = jnp.arange(scores.shape[1], dtype=jnp.int32)[None]
    lens = lengths.astype(jnp.int32)[:, None]
    s = jnp.where(pos < lens, scores, -jnp.inf)
    s = jnp.where(pos == lens, current[:, None].astype(jnp.float32), s)
    what = jnp.broadcast_to(pos, s.shape) if payload is None \
        else payload.astype(jnp.int32)
    neg, what = jax.lax.sort((-s, what), dimension=1, is_stable=True,
                             num_keys=1)
    return what[:, :k], neg[:, :k] < jnp.inf


def select_mask(scores, k: int, *, n_blocks=None, block=None):
    """(Q, S) float32 scores, ``-inf`` where a query may not look ->
    (Q, S) bool of each query's ``k`` highest (all its finite ones
    where that is fewer); ties to the lower position, as
    :func:`dsa_select`'s. Prefill's form, for many queries at once: no
    sort. Each row's ``k``-th largest score is found exactly by
    bisection over the 32 bits of an order-preserving integer form of
    the scores, a pass a bit, counting only the first ``n_blocks`` key
    blocks of ``block`` (every score after them is ``-inf``); the ties
    at it are taken in position order up to ``k``."""
    q, s = scores.shape
    if n_blocks is None:
        n_blocks, block = 1, s
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # negatives: every bit flipped; the rest: the sign bit set
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def count(thr):
        def one(j, acc):
            blk = jax.lax.dynamic_slice_in_dim(key, j * block, block, 1)
            return acc + (blk >= thr[:, None]).sum(1, dtype=jnp.int32)
        return jax.lax.fori_loop(0, n_blocks, one, jnp.zeros(q, jnp.int32))

    def bit(i, thr):
        cand = thr | jnp.left_shift(jnp.uint32(1),
                                    (31 - i).astype(jnp.uint32))
        return jnp.where(count(cand) >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros(q, jnp.uint32))
    above = key > thr[:, None]
    tie = key == thr[:, None]
    room = k - above.sum(1, dtype=jnp.int32)
    rank = jnp.cumsum(tie.astype(jnp.int32), axis=1)
    return (above | (tie & (rank <= room[:, None]))) & (scores > -jnp.inf)


# ---------------------------------------------------------------------------
# (c) attention over the selected rows
# ---------------------------------------------------------------------------

def pool_rows(block_tables, lengths, *, page: int):
    """(B, pages_max · page) int32: the row of a layer's pool, viewed
    ``(P · page, W)``, that each position of a row is cached in (no
    gather: the table broadcast over a page's slots), and ``-1`` at the
    position ``lengths`` (the current token, not cached yet): the
    payload :func:`dsa_select` carries for the decode step."""
    b, pmax = block_tables.shape
    rows = (block_tables[:, :, None] * page
            + jnp.arange(page, dtype=jnp.int32)).reshape(b, pmax * page)
    pos = jnp.arange(pmax * page, dtype=jnp.int32)[None]
    return jnp.where(pos == lengths[:, None], -1, rows).astype(jnp.int32)


def gather_selected(kv_rows, rows):
    """The latent rows ``rows`` (B, K) (pool rows, :func:`pool_rows`'s
    numbering offset to the layer; a negative one reads row 0, masked by
    the caller) of the pool viewed ``(L · P · page, W)``: (B, K, W) in
    the pool's dtype, one XLA gather of whole rows."""
    return kv_rows[jnp.maximum(rows, 0)]


def _sparse_kernel(q_ref, kv_ref, bias_ref, o_ref, mo_ref, lo_ref, *,
                   blk: int, dv: int, scale: float):
    """One batch row: ``(Hp, W)`` queries against its ``K`` gathered
    rows, ``blk`` at a time with a running softmax; ``bias`` 0 where a
    row counts and ``-1e30`` where it does not. The value is a row's
    first ``dv`` columns."""
    hp = q_ref.shape[1]
    k = kv_ref.shape[1]
    q = q_ref[0].astype(jnp.float32)
    acc = jnp.zeros((hp, dv), jnp.float32)
    m = jnp.full((hp, 1), -1e30, jnp.float32)
    l = jnp.zeros((hp, 1), jnp.float32)
    for j in range(k // blk):                    # static unroll
        kv = kv_ref[0, j * blk:(j + 1) * blk, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias_ref[0, :, j * blk:(j + 1) * blk]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p_, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p_, kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m = m_new
    o_ref[0] = acc
    mo_ref[0] = jnp.broadcast_to(m, (hp, LANE))
    lo_ref[0] = jnp.broadcast_to(l, (hp, LANE))


@functools.partial(jax.jit, static_argnames=("dv", "scale", "interpret"))
def sparse_latent_attention(q, rows, counts, *, dv: int, scale: float,
                            interpret: bool = False):
    """Absorbed-form attention of ``q`` (B, H, W) float32 over gathered
    latent rows ``rows`` (B, K, W) of which ``counts`` (B, K) bool
    count. Returns ``(acc (B, H, dv) float32 unnormalised, m (B, H), l
    (B, H))``, the identity ``(0, -1e30, 0)`` where nothing counts."""
    b, h, w = q.shape
    k = rows.shape[1]
    blk = min(SPARSE_BLOCK_ROWS, k)
    if w % LANE or dv % LANE or k % blk or blk % 16:
        raise ValueError(f"queries {q.shape} / rows {rows.shape}: want W "
                         f"and dv multiples of {LANE}, K of 16")
    hp = -(-h // 8) * 8
    if hp != h:
        q = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    bias = jnp.where(counts, 0.0, -1e30).astype(jnp.float32)[:, None]
    row = lambda b_: (b_, 0, 0)
    # two buffers of a row's K rows and its queries, the float32 blocks
    need = (2 * k * w * rows.dtype.itemsize + 2 * hp * w * 4
            + 4 * blk * (w + hp) * 4 + (4 << 20))
    acc, m, l = pl.pallas_call(
        functools.partial(_sparse_kernel, blk=blk, dv=dv, scale=scale),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, w), row),
                  pl.BlockSpec((1, k, w), row),
                  pl.BlockSpec((1, 1, k), row)],
        out_specs=[pl.BlockSpec((1, hp, dv), row),
                   pl.BlockSpec((1, hp, LANE), row),
                   pl.BlockSpec((1, hp, LANE), row)],
        out_shape=[jax.ShapeDtypeStruct((b, hp, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=need),
        interpret=interpret,
    )(q, rows, bias)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]


def sparse_latent_reference(q, rows, counts, *, dv: int, scale: float):
    """XLA twin of :func:`sparse_latent_attention` (same contract)."""
    kv = rows.astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), kv) * scale
    s = jnp.where(counts[:, None], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.where(counts[:, None], jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bhk,bkv->bhv", p, kv[..., :dv])
    m = jnp.where(jnp.any(counts, axis=-1)[:, None], m, -1e30)
    return acc, m, jnp.sum(p, axis=-1)


def sparse_latent_stats(q, rows, counts, *, dv: int, scale: float,
                        interpret: Optional[bool] = None):
    """Backend dispatch: Mosaic kernel on TPU, XLA elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return sparse_latent_reference(q, rows, counts, dv=dv,
                                           scale=scale)
        interpret = False
    return sparse_latent_attention(q, rows, counts, dv=dv, scale=scale,
                                   interpret=interpret)


def fold_current(acc, m, l, q, row, take, *, dv: int, scale: float):
    """Normalise the partial ``(acc, m, l)`` over the selected cached
    rows, with the current token's ``row`` (B, W) folded in where
    ``take`` (B,) says it was selected: (B, H, dv) float32."""
    from bigdl_tpu.llm.kernels.paged_attention import \
        merge_attention_partial
    with_row = merge_attention_partial(acc, m, l, q, row[:, None],
                                       row[:, None, :dv], scale=scale)
    without = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.where(take[:, None, None], with_row, without)
