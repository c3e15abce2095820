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
- :func:`dsa_select` (:func:`dsa_topk_decode`, Pallas: a bisection for
  each live row's ``k``-th score, then a compaction of the kept entries,
  no sort): the current token's score at position ``len`` beside the
  cached ones, nothing past it read, the ``k`` largest kept; a row of
  ``len + 1 <= k`` keeps everything. Ties go to the lower position, as
  ``lax.top_k`` breaks them. Exact: an approximate top-k is another
  result.
- :func:`sparse_latent_attention` (Pallas): absorbed-form multi-query
  attention over the selected rows, which it fetches from the latent
  pool in HBM itself by the pool rows the selection carried
  (:func:`pool_rows`, :func:`gather_selected`): one copy a counted
  entry of a live row, of the 8-row group that holds its row (Mosaic
  refuses a copy of one 16-bit row: the pool's HBM tile is 8 rows),
  the next block fetched while this one is scored, 512 rows a block
  with a running softmax, each entry's 7 other rows masked. Returns the
  flash-style partial ``(acc, m, l)`` over the cached rows it was
  given; the current token is folded in by the caller only where it
  was selected.

Each has an XLA twin for the CPU (``*_reference``), which the engine
runs off the TPU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.llm.kernels.paged_attention import LANE

# cached index keys a block of the scoring walk fetches (32 pages of 16
# at 128 bfloat16 numbers: 128 KB)
INDEX_BLOCK_TOKENS = 512
# pool rows the sparse attention scores at a time
SPARSE_BLOCK_ROWS = 512
# latent rows one of its copies brings: the group of 8 that holds the
# selected row. The pool's HBM tile is 8 bfloat16 rows (two to a 32-bit
# word); Mosaic refuses a copy of fewer
FETCH_ROWS = 8


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
def dsa_select_reference(scores, current, lengths, payload=None, *, k: int):
    """XLA twin of :func:`dsa_topk_decode` (:func:`dsa_select`'s
    contract), ``lax.top_k``'s form: a stable sort of the negated scores
    with the payload beside them, the kept entries in score order."""
    pos = jnp.arange(scores.shape[1], dtype=jnp.int32)[None]
    lens = lengths.astype(jnp.int32)[:, None]
    s = jnp.where(pos < lens, scores, -jnp.inf)
    s = jnp.where(pos == lens, current[:, None].astype(jnp.float32), s)
    what = jnp.broadcast_to(pos, s.shape) if payload is None \
        else payload.astype(jnp.int32)
    neg, what = jax.lax.sort((-s, what), dimension=1, is_stable=True,
                             num_keys=1)
    return what[:, :k], neg[:, :k] < jnp.inf


# positions the selection kernel takes at a time: one (8, 128) tile; its
# loops take TOPK_GROUP tiles a step, independent work the scheduler can
# interleave (a lone tile's chain of cross-lane moves waits on itself)
TOPK_TILE = 8 * LANE
TOPK_GROUP = 4
_SIGN = -(1 << 31)


def _order_key(bits):
    """int32 bit patterns of float32 scores -> int32 keys in the scores'
    order: :func:`select_mask`'s uint32 key with its sign bit flipped
    (negatives have every bit but the sign flipped, the rest stay), and
    -0.0 given 0.0's key, as the sort compares them."""
    flip = (bits >> 31) & 0x7FFFFFFF
    return jax.lax.select(bits == _SIGN, jnp.zeros_like(bits), bits ^ flip)


def _tile_scans(masks, tri, sub):
    """``masks``, each (8, 128) bool over a tile's positions row by row
    -> for each: (the exclusive count within each row, the count in the
    rows before each row, each row's count, all (8, 128) int32; the
    tile's count, a scalar). The counts within the rows of all the tiles
    are one product with the triangle ``tri`` (128, 128) on the MXU (0
    and 1 are exact in bfloat16, their sums in float32)."""
    shape = masks[0].shape
    zero = jnp.zeros(shape, jnp.int32)
    ones = [jax.lax.select(m, jnp.ones(shape, jnp.int32), zero)
            for m in masks]
    inc = jax.lax.dot_general(
        jnp.concatenate(ones, axis=0).astype(jnp.float32).astype(tri.dtype),
        tri, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    out = []
    for j, x in enumerate(ones):
        row_inc = inc[8 * j:8 * (j + 1)]
        rows = jnp.broadcast_to(row_inc[:, LANE - 1:], shape)
        upto = rows
        for sh in (1, 2, 4):
            upto = upto + jax.lax.select(sub >= sh, pltpu.roll(upto, sh, 0),
                                         zero)
        out.append((row_inc - x, upto - rows, rows, upto[7, 0]))
    return out


def _topk_kernel(len_ref, cur_ref, s_ref, *rest, k: int, has_payload: bool,
                 widen: bool, place: bool = True):
    """One batch row ``b``: the ``keep = min(k, len + 1)`` highest keys of
    positions ``<= len`` (``cur_ref[b]``, the current token's, at
    ``len``), their payloads written to the row's output in position
    order. A row with ``len + 1 <= k`` copies its first ``len + 1``.
    Else over the row's live tiles, ``TOPK_GROUP`` at a time: (a) the
    keys, positions past ``len`` set below every score (never read by
    value: what lies there may be NaN); (b) the ``keep``-th largest key
    ``thr``, two bits a pass from the top, counting keys at or above
    three candidates; (c) the kept entries, ``key >= thr`` where no tie
    at ``thr`` must be left out, else ``key > thr`` and the first ``keep
    - #{key > thr}`` ties in position order, moved to the left of their
    row by the bits of the count of entries dropped before each (a move
    by 1, 2, 4, ... lanes: kept entries keep their order and never
    meet), rotated to the lane the row's first kept entry goes to, and
    merged into the output at their window. s_ref (1, R, 128) float32
    and p_ref (1, R, 128) int32 a whole row; o_ref (1, KR, 128) int32;
    key_ref (R, 128) int32. ``widen``: interpret mode on the CPU, whose
    products take no bfloat16 operands into float32 (the triangle is
    float32 there). ``place=False`` stops after (b), ``thr`` in the
    output's first row (``tools/exp_dsa_decode.py`` times the parts
    apart)."""
    if has_payload:
        p_ref, o_ref, key_ref = rest
    else:
        (o_ref, key_ref), p_ref = rest, None
    b = pl.program_id(0)
    ln = len_ref[b]
    keep = jnp.minimum(ln + 1, k)
    kr = o_ref.shape[1]
    shape = (8, LANE)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    flat = (sub << 7) | lane
    zero = jnp.zeros(shape, jnp.int32)
    one = jnp.ones(shape, jnp.int32)

    def tile_rows(t):
        return pl.ds(pl.multiple_of(t << 3, 8), 8)

    def payload(t):
        if p_ref is None:
            return (t << 10) + flat
        return p_ref[0, tile_rows(t), :]

    @pl.when(ln < k)
    def _everything():
        for t in range(kr // 8):
            v = zero
            if (t + 1) * 8 <= s_ref.shape[1]:
                v = jax.lax.select((t << 10) + flat <= ln, payload(t), zero)
            o_ref[0, t * 8:(t + 1) * 8, :] = v

    @pl.when(ln >= k)
    def _select():
        group = TOPK_GROUP
        lg = group.bit_length() - 1                 # a power of two
        groups = ((ln >> 10) + group) >> lg
        cur = jnp.full(shape, cur_ref[b], jnp.int32)
        below = jnp.full(shape, _SIGN, jnp.int32)

        def keys(g, c):
            for j in range(group):
                t = (g << lg) + j
                pos = (t << 10) + flat
                bits = jax.lax.bitcast_convert_type(
                    s_ref[0, tile_rows(t), :], jnp.int32)
                key = jax.lax.select(pos < ln, _order_key(bits), below)
                key_ref[tile_rows(t), :] = jax.lax.select(pos == ln, cur,
                                                          key)
            return c

        jax.lax.fori_loop(0, groups, keys, 0)

        def counts(*tests):
            """The number of keys each of ``tests`` holds for."""
            def one_group(g, accs):
                accs = list(accs)
                for j in range(group):
                    key = key_ref[tile_rows((g << lg) + j), :]
                    for i, test in enumerate(tests):
                        accs[i * group + j] = accs[i * group + j] + \
                            jax.lax.select(test(key), one, zero)
                return tuple(accs)
            accs = jax.lax.fori_loop(0, groups, one_group,
                                     (zero,) * (len(tests) * group))
            return [jnp.sum(sum(accs[i * group:(i + 1) * group]))
                    for i in range(len(tests))]

        def digits(i, thr):
            at = 30 - 2 * i
            got = counts(*(
                lambda key, c=(thr | (d << at)) ^ _SIGN: key >= c
                for d in (1, 2, 3)))
            d = sum(jnp.where(c >= keep, 1, 0) for c in got)
            return thr | (d << at)

        thr = jax.lax.fori_loop(0, 16, digits, jnp.int32(0)) ^ _SIGN
        if not place:
            o_ref[0, 0:8, :] = jnp.full(shape, thr, jnp.int32)
            return
        above, at_or_above = counts(lambda key: key > thr,
                                    lambda key: key >= thr)
        room = keep - above
        gone = jnp.full(shape, -LANE, jnp.int32)    # no bit under 128 set
        rowid = jax.lax.broadcasted_iota(jnp.int32, (kr, LANE), 0)
        tri = (jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 1)
               ).astype(jnp.float32 if widen else jnp.bfloat16)
        o_ref[0] = jnp.zeros((kr, LANE), jnp.int32)

        def merge(in_order: bool):
            """Every tile's kept entries into the output; ``in_order``:
            the ties at ``thr`` taken in position order up to
            ``room``."""
            def one_group(g, carry):
                done, ties = carry
                out = o_ref[0]
                ts = [(g << lg) + j for j in range(group)]
                key = [key_ref[tile_rows(t), :] for t in ts]
                live = [(t << 10) + flat <= ln for t in ts]
                if in_order:
                    tie = [lv & (ky == thr) for lv, ky in zip(live, key)]
                    kept = []
                    for lv, ky, ti, (in_row, before, _, n_tie) in zip(
                            live, key, tie, _tile_scans(tie, tri, sub)):
                        kept.append((lv & (ky > thr)) | (
                            ti & (ties + before + in_row < room)))
                        ties = ties + n_tie
                else:
                    kept = [lv & (ky >= thr) for lv, ky in zip(live, key)]
                for t, kp, (in_row, before, in_rows, n_kept) in zip(
                        ts, kept, _tile_scans(kept, tri, sub)):
                    # each row's kept entries to its left, in order
                    v = payload(t)
                    s = jax.lax.select(kp, lane - in_row, gone)
                    sh = 1
                    while sh < LANE:
                        v2 = pltpu.roll(v, LANE - sh, 1)
                        s2 = pltpu.roll(s, LANE - sh, 1)
                        come = (s2 & sh) != 0
                        v = jax.lax.select(come, v2, v)
                        s = jax.lax.select(come, s2, jax.lax.select(
                            (s & sh) == 0, s, gone))
                        sh <<= 1
                    # rotated to the lane the row's first goes to
                    dest = done + before
                    at = dest & (LANE - 1)
                    sh = 1
                    while sh < LANE:
                        v = jax.lax.select((at & sh) != 0,
                                           pltpu.roll(v, sh, 1), v)
                        sh <<= 1
                    win = jax.lax.select(
                        ((lane - at) & (LANE - 1)) < in_rows,
                        (dest >> 7) + jax.lax.select(lane < at, one, zero),
                        -one)
                    for r in range(8):
                        wr = jnp.broadcast_to(win[r:r + 1], (kr, LANE))
                        out = jax.lax.select(rowid == wr, jnp.broadcast_to(
                            v[r:r + 1], (kr, LANE)), out)
                    done = done + n_kept
                o_ref[0] = out
                return done, ties

            jax.lax.fori_loop(0, groups, one_group,
                              (jnp.int32(0), jnp.int32(0)))

        @pl.when(at_or_above == keep)
        def _no_tie_left_out():
            merge(False)

        @pl.when(at_or_above != keep)
        def _ties_in_order():
            merge(True)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def dsa_topk_decode(scores, current, lengths, payload=None, *, k: int,
                    interpret: bool = False):
    """:func:`dsa_select`'s contract without a sort (Pallas): one grid
    step a batch row, the exact ``k``-th largest score by bisection over
    the 32 bits of an order-preserving integer key of the scores, then
    the kept entries compacted, all in the row's live tiles (see
    :func:`_topk_kernel`). The kept entries come in POSITION order, not
    score order (no consumer needs an order: the sparse kernel takes a
    softmax over them, ``fold_current`` asks only whether the current
    token is among them); entries past ``min(k, len + 1)`` are 0."""
    b, s = scores.shape
    span = TOPK_GROUP * TOPK_TILE
    sp = -(-s // span) * span
    kout = -(-k // TOPK_TILE) * TOPK_TILE
    lens = lengths.astype(jnp.int32)
    cur = _order_key(jax.lax.bitcast_convert_type(
        current.astype(jnp.float32), jnp.int32))
    args = [scores.astype(jnp.float32)]
    if payload is not None:
        args.append(payload.astype(jnp.int32))
    args = [jnp.pad(a, ((0, 0), (0, sp - s))).reshape(b, sp // LANE, LANE)
            for a in args]
    row = lambda b_, *_: (b_, 0, 0)
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, has_payload=payload is not None,
                          widen=bool(interpret)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, sp // LANE, LANE), row)
                      for _ in args],
            out_specs=pl.BlockSpec((1, kout // LANE, LANE), row),
            scratch_shapes=[pltpu.VMEM((sp // LANE, LANE), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, kout // LANE, LANE), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dsa_topk_decode",
        interpret=interpret,
    )(lens, cur, *args)
    keep = jnp.minimum(lens + 1, k)[:, None]
    return (out.reshape(b, kout)[:, :k],
            jnp.arange(k, dtype=jnp.int32)[None] < keep)


def dsa_select(scores, current, lengths, payload=None, *, k: int,
               interpret=None):
    """``scores`` (B, S) of the cached positions (only ``< lengths``
    are read), ``current`` (B,) the score of the token at position
    ``lengths`` -> ``(what (B, k) int32, selected (B, k) bool)``: the
    ``min(k, len + 1)`` highest-scored positions ``<= len``, the current
    token competing like a cached one; ties go to the lower position.
    ``what`` is each selected position, or its entry of ``payload`` (B,
    S) int32 where one is given (the decode step's: the pool row a
    position is cached in), so that no gather follows. ``selected`` is
    True on exactly the first ``min(k, len + 1)`` entries. Which order
    the kept entries come in is the form's own. Backend dispatch:
    :func:`dsa_topk_decode` on TPU (position order), the sort
    :func:`dsa_select_reference` elsewhere (score order)."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return dsa_select_reference(scores, current, lengths, payload,
                                        k=k)
        interpret = False
    return dsa_topk_decode(scores, current, lengths, payload, k=k,
                           interpret=interpret)


def select_mask(scores, k: int, *, n_blocks=None, block=None):
    """(Q, S) float32 scores, ``-inf`` where a query may not look ->
    (Q, S) bool of each query's ``k`` highest (all its finite ones
    where that is fewer); ties to the lower position, as
    :func:`dsa_select`'s. Prefill's form, for many queries at once, in
    XLA: no sort. Each row's ``k``-th largest score is found exactly by
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


class SelectedRows(NamedTuple):
    """What :func:`gather_selected` hands the attention: the pool's rows
    ``pool`` (L · P · page, W) and the selected ones ``rows`` (B, K)
    int32, none negative. The kernel fetches them itself; the twin
    gathers them."""
    pool: jax.Array
    rows: jax.Array


def gather_selected(kv_rows, rows):
    """The latent rows ``rows`` (B, K) (pool rows, :func:`pool_rows`'s
    numbering offset to the layer; a negative one reads row 0, masked by
    the caller) of the pool viewed ``(L · P · page, W)``, as
    :func:`sparse_latent_stats` takes them: the one place where a step's
    selection meets the pool. Nothing is copied here: the Mosaic kernel
    fetches the rows from the pool in HBM, the XLA twin gathers them."""
    return SelectedRows(kv_rows, jnp.maximum(rows, 0).astype(jnp.int32))


def _sparse_kernel(n_ref, grp_ref, q_ref, bias_ref, pool_hbm, o_ref, mo_ref,
                   lo_ref, buf, sem, walked, *, k: int, dv: int,
                   scale: float, score: bool = True):
    """One batch row ``b``: ``(Hp, W)`` queries against the groups that
    hold its first ``n`` selected rows (``n_ref[b]``: one past the last
    that counts), ``E`` entries a block with a running softmax. An entry
    is one copy: the ``FETCH_ROWS``-row group of the pool that holds its
    row (``grp_ref``), into its place in one of two buffer slots. A
    block's ``E`` copies are issued unrolled, with no branch (an entry
    that does not count, past ``n`` in the row's last block or the
    current token's, fetches group 0 and is masked), share the slot's
    semaphore and are awaited by one wait of the slot's bytes. The next
    block, or after the row's last the next live row's first, is
    fetched while this one is scored (``walked[0]`` counts the blocks of
    the rows before this one). A block scores all ``E · FETCH_ROWS``
    rows in hand; ``bias`` (1, 1, K · FETCH_ROWS) is 0 on the lane of
    each counted entry's own row and ``-1e30`` on every other, whose
    weight is then 0. A row with ``n == 0`` fetches nothing and writes
    the identity. ``score=False`` stops after the waits
    (``tools/exp_dsa_decode.py`` times the fetch alone)."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    ent = buf.shape[1]
    lg = ent.bit_length() - 1                   # a power of two
    blk = ent * FETCH_ROWS
    hp, w = q_ref.shape[1], q_ref.shape[2]

    @pl.when(b == 0)
    def _first_row():
        walked[0] = 0

    nblk = (n_ref[b] + (ent - 1)) >> lg
    first = walked[0]

    def fetch(row, j, slot):
        base = row * k + (j << lg)
        for i in range(ent):                    # static unroll
            pltpu.make_async_copy(pool_hbm.at[grp_ref[base + i]],
                                  buf.at[slot, i], sem.at[slot]).start()

    @pl.when((nblk > 0) & (first == 0))
    def _nobody_fetched_it():
        fetch(b, 0, 0)

    nxt = jax.lax.while_loop(
        lambda r: (r < rows) & (n_ref[jnp.minimum(r, rows - 1)] == 0),
        lambda r: r + 1, b + 1)
    q = q_ref[0]

    def block(j, carry):
        acc, m, l = carry
        slot = (first + j) & 1
        more = j + 1 < nblk

        @pl.when(more | (nxt < rows))
        def _fetch_ahead():
            fetch(jnp.where(more, b, nxt), jnp.where(more, j + 1, 0),
                  1 - slot)

        # a wait takes its destination's bytes off the semaphore: the
        # block's E copies at once
        pltpu.make_async_copy(pool_hbm.at[pl.ds(0, ent)], buf.at[slot],
                              sem.at[slot]).wait()
        if not score:
            return carry
        kv = buf[slot].astype(jnp.float32).reshape(blk, w)
        bias = bias_ref[0, :, pl.ds(pl.multiple_of(j * blk, blk), blk)]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p_ = jnp.where(bias == 0.0, jnp.exp(s - m_new), 0.0)
        l = alpha * l + jnp.sum(p_, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p_, kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc, m, l = jax.lax.fori_loop(0, nblk, block, (
        jnp.zeros((hp, dv), jnp.float32),
        jnp.full((hp, 1), -1e30, jnp.float32),
        jnp.zeros((hp, 1), jnp.float32)))
    walked[0] = first + nblk
    o_ref[0] = acc
    mo_ref[0] = jnp.broadcast_to(m, (hp, LANE))
    lo_ref[0] = jnp.broadcast_to(l, (hp, LANE))


@functools.partial(jax.jit, static_argnames=("dv", "scale", "interpret",
                                             "score"))
def sparse_latent_attention(q, kv_rows, rows, counts, *, dv: int,
                            scale: float, interpret: bool = False,
                            score: bool = True):
    """Absorbed-form attention of ``q`` (B, H, W) float32 over the rows
    ``rows`` (B, K) int32 of the pool ``kv_rows`` (R, W) in HBM, of which
    ``counts`` (B, K) bool count, fetched by the kernel
    (:func:`_sparse_kernel`). Returns ``(acc (B, H, dv) float32
    unnormalised, m (B, H), l (B, H))``, the identity ``(0, -1e30, 0)``
    where nothing counts."""
    b, h, w = q.shape
    r_all = kv_rows.shape[0]
    ent = SPARSE_BLOCK_ROWS // FETCH_ROWS
    if w % LANE or dv % LANE or r_all % FETCH_ROWS:
        raise ValueError(f"queries {q.shape} / pool {kv_rows.shape}: want W "
                         f"and dv multiples of {LANE}, rows of "
                         f"{FETCH_ROWS}")
    hp = -(-h // 8) * 8
    if hp != h:
        q = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    k = -(-rows.shape[1] // ent) * ent
    pad = ((0, 0), (0, k - rows.shape[1]))
    rows = jnp.pad(rows.astype(jnp.int32), pad)
    counts = jnp.pad(counts, pad)
    n = jnp.max(jnp.where(counts, jnp.arange(1, k + 1, dtype=jnp.int32), 0),
                axis=1)
    lg = FETCH_ROWS.bit_length() - 1
    grp = jnp.where(counts, rows >> lg, 0).reshape(-1)
    own = jnp.where(counts, rows & (FETCH_ROWS - 1), -1)
    lane = jnp.arange(k * FETCH_ROWS, dtype=jnp.int32)[None]
    bias = jnp.where(jnp.repeat(own, FETCH_ROWS, axis=1)
                     == (lane & (FETCH_ROWS - 1)), 0.0, -1e30
                     ).astype(jnp.float32)[:, None]
    pool = kv_rows.reshape(r_all // FETCH_ROWS, FETCH_ROWS, w)
    row = lambda b_, *_: (b_, 0, 0)
    blk = ent * FETCH_ROWS
    # the two slots (an 8-row bfloat16 group takes a 16-row tile of
    # VMEM), the queries, a row's bias (a 1-row block takes 8), the
    # float32 block
    need = (2 * ent * 16 * w * kv_rows.dtype.itemsize + 2 * hp * w * 4
            + 2 * 8 * k * FETCH_ROWS * 4 + 4 * blk * (w + hp) * 4
            + (4 << 20))
    acc, m, l = pl.pallas_call(
        functools.partial(_sparse_kernel, k=k, dv=dv, scale=scale,
                          score=score),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((1, hp, w), row),
                      pl.BlockSpec((1, 1, k * FETCH_ROWS), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, hp, dv), row),
                       pl.BlockSpec((1, hp, LANE), row),
                       pl.BlockSpec((1, hp, LANE), row)],
            scratch_shapes=[
                pltpu.VMEM((2, ent, FETCH_ROWS, w), kv_rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hp, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((b, hp, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need),
        name="sparse_latent_attention",
        interpret=interpret,
    )(n, grp, q, bias, pool)
    return acc[:, :h], m[:, :h, 0], l[:, :h, 0]


def sparse_latent_reference(q, rows, counts, *, dv: int, scale: float):
    """XLA twin of :func:`sparse_latent_attention` (same contract) over
    the selected rows gathered, ``rows`` (B, K, W)."""
    kv = rows.astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), kv) * scale
    s = jnp.where(counts[:, None], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.where(counts[:, None], jnp.exp(s - m[..., None]), 0.0)
    acc = jnp.einsum("bhk,bkv->bhv", p, kv[..., :dv])
    m = jnp.where(jnp.any(counts, axis=-1)[:, None], m, -1e30)
    return acc, m, jnp.sum(p, axis=-1)


def sparse_latent_stats(q, sel: SelectedRows, counts, *, dv: int,
                        scale: float, interpret: Optional[bool] = None):
    """Backend dispatch over :func:`gather_selected`'s rows: the Mosaic
    kernel, which fetches them, on TPU; the XLA gather and twin
    elsewhere."""
    if interpret is None:
        if jax.default_backend() != "tpu":
            return sparse_latent_reference(q, sel.pool[sel.rows], counts,
                                           dv=dv, scale=scale)
        interpret = False
    return sparse_latent_attention(q, sel.pool, sel.rows, counts, dv=dv,
                                   scale=scale, interpret=interpret)


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
