"""Chip experiment (ISSUE 34): where does a call of MiMo's decode
kernels (``hybrid_attention.py``) spend its time, and what does a walk
inside the kernel buy?

MiMo's decode step calls ``full_attention_decode_stats`` twice at ``q``
(32, 64, 256) bf16 over the flat full pool (2 x 43,000 pages of 4 heads
x 16 rows of 384 bf16), table (32, 2176), and reads 36 % of the bytes it
must; ``window_attention_decode_stats`` five times over (5 x 513, 8, 16,
384), a ring of 16 pages a row, at 16 %. This harness times one call at
those shapes, lengths drawn like the cell's (14 live rows of 0.3-34k
tokens, 98k in all, dead rows between them), by the slope of a
``fori_loop`` of calls as ``exp_latent_body.py`` does:

- ``grid``        the kernel as PR 31 had it (kept here verbatim): grid
                  (B, nblk), a block's pages started and awaited inside
                  its grid step                          step 0's (a)
- ``grid:zero``   the same, every length zero: what the empty grid
                  steps cost                                      (b)
- ``grid:dma``    the DMAs with the arithmetic taken out          (c)
- ``grid:math``   the arithmetic on a resident buffer, no DMA     (d)
- ``new``         the module's kernel; ``new:blk=1024`` with
                  ``DECODE_BLOCK_TOKENS`` changed
- ``walk``        the walk of this file (grid (B,), two slots), whose
                  pieces come apart: ``walk:ahead=0`` fetches no next
                  row's first block, ``walk:late=1`` starts the fetch
                  ahead after this block's wait and not before it,
                  ``walk:static=1`` writes a block's code once a slot,
                  ``walk:pair=1`` scores two KV heads' query rows
                  against every key tile (twice the rows streamed for
                  the same tiles loaded: does the MXU's time follow the
                  tiles or the rows?), ``walk:dma`` / ``walk:math`` as
                  above, ``walk:blk=1024`` the block's tokens

``--cls window`` runs the window class (every live row one block, the
ring). ``--rows``/``--tokens``/``--lens-seed`` change the lengths drawn.
Results go to ``chiprun_out/exp_hybrid_body[_window].json`` and into the
header of the decode section of
``bigdl_tpu/llm/kernels/hybrid_attention.py``."""

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bigdl_tpu.llm.kernels import hybrid_attention as ha  # noqa: E402
from bigdl_tpu.llm.kernels.hybrid_attention import (  # noqa: E402
    _flash_update, _scores, ring_positions)

LANE = ha.LANE
F32 = jnp.float32
B, HQ, DK, DV, PAGE = 32, 64, 256, 128, 16
SCALE = 192 ** -0.5
WINDOW = 128
# (kv heads, table columns, pages of the flat pool) by class
CLASSES = {"full": (4, 2176, 2 * 43000), "window": (8, 16, 5 * 513)}


def _touch(page_rows, lo_ref):
    """What stands in for the arithmetic: one page's first lanes."""
    lo_ref[0, 0, 0:8] = lo_ref[0, 0, 0:8] \
        + page_rows[0:8, 0:LANE].astype(F32)


def grid_kernel(len_ref, bt_ref, q_ref, kv_hbm, o_ref, mo_ref, lo_ref,
                buf, sem, acc_ref, m_ref, l_ref, *, page, ppb, pages_max,
                hkv, scale, window, dma=True, math=True):
    """PR 31's ``_decode_kernel``: one (row, block) a grid step."""
    b = pl.program_id(0)
    blk = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    if not dma:
        @pl.when((b == 0) & (blk == 0))
        def _resident():
            buf[...] = jnp.zeros_like(buf)

    seq = len_ref[b]
    n = ppb * page
    base_tok = blk * n

    @pl.when(seq > 0 if window is not None else base_tok < seq)
    def _compute():
        if dma:
            copies = []
            for i in range(ppb):                    # static unroll
                col = jnp.minimum(blk * ppb + i, pages_max - 1)
                pid = bt_ref[b * pages_max + col]
                c = pltpu.make_async_copy(kv_hbm.at[pid], buf.at[i], sem)
                c.start()
                copies.append(c)
            for c in copies:
                c.wait()
        if not math:
            _touch(buf[0, 0], lo_ref)
            return
        gp, dk = q_ref.shape[2], q_ref.shape[3]
        idx = jax.lax.broadcasted_iota(jnp.int32, (gp, n), 1)
        if window is None:
            pos = base_tok + idx
            valid = pos < seq
        else:
            pos = ring_positions(idx // page, idx % page, seq, page,
                                 pages_max)
            valid = (pos >= 0) & (pos < seq) & (pos > seq - window)
        for h in range(hkv):                    # static unroll over heads
            kv = buf[:, h].reshape(n, buf.shape[-1])
            s = _scores(q_ref[0, h], kv[:, :dk], scale)
            _flash_update(jnp.where(valid, s, -1e30), kv[:, dk:], h * gp,
                          gp, acc_ref, m_ref, l_ref)

    @pl.when(blk == nblk - 1)
    def _finish():
        gp = q_ref.shape[2]
        if math:
            o_ref[0] = acc_ref[...].reshape(hkv, gp, acc_ref.shape[-1])
            mo_ref[0] = m_ref[...].reshape(hkv, gp, LANE)
            lo_ref[0] = l_ref[...].reshape(hkv, gp, LANE)


def walk_kernel(len_ref, bt_ref, q_ref, kv_hbm, o_ref, mo_ref, lo_ref, buf,
                sem, walked, *, page, ppb, pages_max, hkv, scale, window,
                ahead=True, late=False, static=False, pair=False, dma=True,
                math=True):
    """The walk with its pieces as switches: grid (B,), a row's live
    blocks in a loop, two slots. ``late`` starts the fetch ahead AFTER
    this block's wait, in one region with the arithmetic (and always:
    where nothing is left to fetch it fetches this row's first block
    again, awaited after the last row); ``static`` writes the block's
    code once a slot; ``pair`` streams two heads' query rows through
    every key tile."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    n = ppb * page
    gp, dk = q_ref.shape[2], q_ref.shape[3]
    width = buf.shape[-1]

    @pl.when(b == 0)
    def _first_row():
        walked[0] = 0
        if not dma:
            buf[...] = jnp.zeros_like(buf)

    seq = len_ref[b]
    nblk = (seq + (n - 1)) // n if window is None else jnp.minimum(seq, 1)
    first = walked[0]

    def fetch(row, blk, slot):
        if not dma:
            return
        for i in range(ppb):                    # static unroll
            col = jnp.minimum(blk * ppb + i, pages_max - 1)
            pid = bt_ref[row * pages_max + col]
            pltpu.make_async_copy(kv_hbm.at[pid], buf.at[slot, i],
                                  sem.at[slot]).start()

    def wait(slot):
        if not dma:
            return
        for i in range(ppb):
            pltpu.make_async_copy(kv_hbm.at[0], buf.at[slot, i],
                                  sem.at[slot]).wait()

    o_ref[0] = jnp.zeros(o_ref.shape[1:], F32)
    mo_ref[0] = jnp.full(mo_ref.shape[1:], -1e30, F32)
    lo_ref[0] = jnp.zeros(lo_ref.shape[1:], F32)

    if ahead:
        @pl.when((nblk > 0) & (first == 0))
        def _nobody_fetched_it():
            fetch(b, 0, 0)
        nxt = jax.lax.while_loop(
            lambda r: (r < rows) & (len_ref[jnp.minimum(r, rows - 1)] == 0),
            lambda r: r + 1, b + 1)
    else:
        @pl.when(nblk > 0)
        def _own_first_block():
            fetch(b, 0, first % 2)
        nxt = rows

    def one_block(j, slot):
        more = j + 1 < nblk
        to_row = jnp.where(more | (nxt >= rows), b, nxt)
        to_blk = jnp.where(more, j + 1, 0)
        if late:
            wait(slot)
            fetch(to_row, to_blk, 1 - slot)
        else:
            @pl.when(more | (nxt < rows))
            def _fetch_ahead():
                fetch(to_row, to_blk, 1 - slot)
            wait(slot)
        if not math:
            _touch(buf[slot, 0, 0], lo_ref)
            return
        idx = jax.lax.broadcasted_iota(jnp.int32, (gp, n), 1)
        if window is None:
            valid = j * n + idx < seq
        else:
            pos = ring_positions(idx // page, idx % page, seq, page,
                                 pages_max)
            valid = (pos >= 0) & (pos < seq) & (pos > seq - window)
        for h in range(hkv):                    # static unroll over heads
            kv = buf[slot, :, h].reshape(n, width)
            if pair:
                h0 = h - h % 2
                q2 = q_ref[0, h0:h0 + 2].reshape(2 * gp, dk)
                s = _scores(q2, kv[:, :dk], scale)[
                    (h - h0) * gp:(h - h0 + 1) * gp]
            else:
                s = _scores(q_ref[0, h], kv[:, :dk], scale)
            _flash_update(jnp.where(valid, s, -1e30), kv[:, dk:], 0, gp,
                          o_ref.at[0, h], mo_ref.at[0, h], lo_ref.at[0, h])

    def block(j, carry):
        slot = (first + j) % 2
        if static:
            for s in (0, 1):
                pl.when(slot == s)(functools.partial(one_block, j, s))
        else:
            one_block(j, slot)
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    if late:
        @pl.when((nblk > 0) & (nxt >= rows))
        def _nothing_was_left():
            wait((first + nblk) % 2)
    walked[0] = first + nblk


def call(kernel, blk_tokens, window, q, kv, bt, lens, **switches):
    """``hybrid_attention._decode_stats``'s ``pallas_call`` around one
    of this file's kernels."""
    b, hq, dk = q.shape
    _, hkv, page, width = kv.shape
    dv = width - dk
    pages_max = bt.shape[1]
    if window is None:
        ppb = max(1, min(blk_tokens // page, pages_max))
    else:
        ppb = pages_max
    g = hq // hkv
    gp = max(8, -(-g // 8) * 8)
    qg = q.reshape(b, hkv, g, dk)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    walk = kernel is walk_kernel
    if walk:
        grid, row = (b,), (lambda b_, *_: (b_, 0, 0, 0))
        scratch = [pltpu.VMEM((2, ppb, hkv, page, width), kv.dtype),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((1,), jnp.int32)]
        sem = ("arbitrary",)
    else:
        grid = (b, -(-pages_max // ppb))
        row = lambda b_, k_, *_: (b_, 0, 0, 0)
        scratch = [pltpu.VMEM((ppb, hkv, page, width), kv.dtype),
                   pltpu.SemaphoreType.DMA,
                   pltpu.VMEM((hkv * gp, dv), F32),
                   pltpu.VMEM((hkv * gp, LANE), F32),
                   pltpu.VMEM((hkv * gp, LANE), F32)]
        sem = ("parallel", "arbitrary")
    slots = (2 if walk else 1) * ppb * hkv * page * width * kv.dtype.itemsize
    acc, m, l = pl.pallas_call(
        functools.partial(kernel, page=page, ppb=ppb, pages_max=pages_max,
                          hkv=hkv, scale=SCALE, window=window, **switches),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[pl.BlockSpec((1, hkv, gp, dk), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, hkv, gp, dv), row),
                       pl.BlockSpec((1, hkv, gp, LANE), row),
                       pl.BlockSpec((1, hkv, gp, LANE), row)],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, gp, dv), F32),
                   jax.ShapeDtypeStruct((b, hkv, gp, LANE), F32),
                   jax.ShapeDtypeStruct((b, hkv, gp, LANE), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=sem,
            vmem_limit_bytes=max(16 << 20, slots + (12 << 20))),
        interpret=jax.default_backend() != "tpu",
    )(lens, bt.reshape(-1), qg, kv)
    return (acc[:, :, :g].reshape(b, hq, dv),
            m[:, :, :g, 0].reshape(b, hq), l[:, :, :g, 0].reshape(b, hq))


def variant(name, window):
    """``(run(q, kv, bt, lens) -> (acc, m, l), zero lengths?, whole?)``
    for a name such as ``walk:late=1,blk=1024``; ``whole`` is false
    where a piece was taken out and the answer means nothing."""
    form, _, rest = name.partition(":")
    opts = dict(o.partition("=")[::2] for o in rest.split(",") if o)
    blk = int(opts.pop("blk", ha.DECODE_BLOCK_TOKENS))
    zero = opts.pop("zero", None) is not None
    if form == "new":
        assert not opts, opts

        def run(q, kv, bt, lens):
            kept, ha.DECODE_BLOCK_TOKENS = ha.DECODE_BLOCK_TOKENS, blk
            try:
                return ha._decode_stats(
                    q, kv, bt, lens, page_size=PAGE, scale=SCALE,
                    window=window, name="exp_" + name.replace(":", "_")
                    .replace("=", "_"),
                    interpret=jax.default_backend() != "tpu")
            finally:
                ha.DECODE_BLOCK_TOKENS = kept
        return run, zero, True
    kernel = {"grid": grid_kernel, "walk": walk_kernel}[form]
    switches = {}
    if "dma" in opts:                 # the DMAs alone
        opts.pop("dma")
        switches["math"] = False
    if "math" in opts:                # the arithmetic alone
        opts.pop("math")
        switches["dma"] = False
    for k in ("ahead", "late", "static", "pair"):
        if k in opts:
            switches[k] = bool(int(opts.pop(k)))
    assert not opts, opts
    return (functools.partial(call, kernel, blk, window, **switches), zero,
            not ({"math", "dma"} & set(switches)))


def draw_lengths(rows, live, tokens, low, top, seed):
    """``live`` of ``rows`` lengths, log-normal (sigma 1, the cell's
    prompts'), ``tokens`` in all, none under ``low`` or over ``top``;
    the others zero, scattered."""
    rs = np.random.RandomState(seed)
    raw = np.exp(rs.normal(0.0, 1.0, live))
    lens = np.clip(raw / raw.sum() * tokens, low, top).astype(np.int64)
    out = np.zeros(rows, np.int64)
    out[rs.permutation(rows)[:live]] = lens
    return out


def tables(lens_np, cols, pages, ring, rs):
    """Every live row owns pages of its own (a ring: all 16), as the
    engine's ledgers deal them; the rest of a table names the trash
    page 0."""
    bt = np.zeros((len(lens_np), cols), np.int64)
    free = 1 + rs.permutation(pages - 1)
    at = 0
    for r, n in enumerate(lens_np):
        held = cols if ring and n else min(cols, -(-int(n) // PAGE))
        bt[r, :held] = free[at:at + held]
        at += held
    return jnp.asarray(bt, jnp.int32)


def reference(q, kv, bt, lens_np, window):
    """The XLA twin a live row at a time (a gather of all 32 tables of
    2,176 pages in float32 would not fit beside the pool)."""
    twin = jax.jit(functools.partial(ha.attention_decode_reference_stats,
                                     scale=SCALE, window=window))
    acc = np.zeros((B, HQ, DV), np.float32)
    m = np.full((B, HQ), -1e30, np.float32)
    l = np.zeros((B, HQ), np.float32)
    with jax.default_matmul_precision("highest"):
        for r in np.flatnonzero(lens_np):
            a, m_, l_ = twin(q[r:r + 1], kv, bt[r:r + 1],
                             jnp.asarray(lens_np[r:r + 1], jnp.int32))
            acc[r], m[r], l[r] = a[0], m_[0], l_[0]
    return acc, m, l


def slope(run, q, kv, bt, lens, iters):
    """Per-call device time: slope of a fori_loop of calls between
    iters/4 and iters, best of 3."""
    def loop_for(n_it):
        @jax.jit
        def loop(q, kv, bt, lens):
            def body(i, carry):
                acc, m, l = run(q + (carry * 1e-30).astype(q.dtype), kv, bt,
                                lens)
                return acc[0, 0, 0] + m[0, 0] * 1e-30 + l[0, 0]
            return jax.lax.fori_loop(0, n_it, body, F32(0))
        return loop
    pts = []
    for n_it in (iters // 4, iters):
        loop = loop_for(n_it)
        float(loop(q, kv, bt, lens))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(loop(q, kv, bt, lens))
            best = min(best, time.perf_counter() - t0)
        pts.append((n_it, best))
    (a1, b1), (a2, b2) = pts
    return (b2 - b1) / (a2 - a1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cls", choices=sorted(CLASSES), default="full")
    ap.add_argument("--variants", default="grid;grid:zero;grid:dma;"
                    "grid:math;new;walk;walk:dma;walk:math;walk:blk=768;"
                    "walk:blk=1024;walk:ahead=0;walk:static=1;walk:late=1;"
                    "walk:pair=1")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--rows", type=int, default=14, help="live rows of 32")
    ap.add_argument("--tokens", type=int, default=98000)
    ap.add_argument("--lens-seed", type=int, default=0)
    ap.add_argument("--pages", type=int, default=0,
                    help="pages of the pool (0: the cell's)")
    ap.add_argument("--maxp", type=int, default=0,
                    help="table columns of the full class (0: the cell's)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    chip = jax.default_backend() == "tpu"
    hkv, cols, pages = CLASSES[args.cls]
    window = WINDOW if args.cls == "window" else None
    pages = args.pages or pages
    if window is None:
        cols = args.maxp or cols
    rs = np.random.RandomState(1)
    lens_np = draw_lengths(B, args.rows, args.tokens, 300, cols * PAGE - 1
                           if window is None else 34815, args.lens_seed)
    lens = jnp.asarray(lens_np, jnp.int32)
    bt = tables(lens_np, cols, pages, window is not None, rs)
    qn = np.zeros((B, HQ, DK), np.float32)
    qn[..., :192] = rs.randn(B, HQ, 192)
    q = jnp.asarray(qn, jnp.bfloat16)
    # one page's worth of noise a 1,000 pages, tiled: the pool's bytes
    # are what is timed, and a draw of 2 G numbers at once is not needed
    tile = jax.random.normal(jax.random.PRNGKey(1),
                             (min(pages, 1000), hkv, PAGE, DK + DV),
                             jnp.bfloat16)
    kv = jnp.tile(tile, (-(-pages // tile.shape[0]), 1, 1, 1))[:pages]
    want = reference(q, kv, bt, lens_np, window)
    n = ha.DECODE_BLOCK_TOKENS
    seen = np.minimum(lens_np, window) if window else lens_np
    least_us = float(seen.sum()) * hkv * 320 * 2 / 819e9 * 1e6
    out = {"cls": args.cls, "lengths": lens_np.tolist(),
           "tokens": int(lens_np.sum()),
           "live_blocks": int((-(-lens_np // n)).sum()) if window is None
           else int((lens_np > 0).sum()),
           "least_us": round(least_us, 2)}
    print(json.dumps(out), flush=True)
    for name in args.variants.split(";"):
        try:
            run, zero, whole = variant(name, window)
            ln = jnp.zeros_like(lens) if zero else lens
            got = jax.jit(run)(q, kv, bt, ln)
            res = {}
            if whole and not zero:
                res["err"] = [round(float(
                    np.abs(np.asarray(g) - w_).max() / np.abs(w_).max()), 5)
                    for g, w_ in zip(got, want)]
            if chip:
                us = slope(run, q, kv, bt, ln, args.iters) * 1e6
                res["us"] = round(us, 2)
                if not zero:
                    res["roofline_pct"] = round(100 * least_us / us, 1)
        except Exception as e:           # a body Mosaic refuses
            res = {"error": str(e)[-400:]}
        out[name] = res
        print(name, json.dumps(res), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    dest = args.out or ("exp_hybrid_body.json" if window is None
                        else "exp_hybrid_body_window.json")
    with open(os.path.join("chiprun_out", dest), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
