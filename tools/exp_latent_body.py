"""Chip experiment (ISSUE 32): where does a call of the latent decode
kernel spend its time, and what does a walk inside the kernel buy?

Kanana's decode step calls ``latent_attention_decode_stats`` eight
times at ``q`` (32, 32, 640) float32 over a pool of ``1 + 32 x 512``
pages of 16 rows of 640 bf16, table (32, 512), and reads 30 % of the
bytes it must. This harness times one call at those shapes, lengths
drawn like the cell's (24 live rows of 0.3-6k tokens, 61k in all,
8 rows empty), by the slope of a ``fori_loop`` of calls as
``exp_int4_body.py`` does (each call's queries hang on the one before,
so nothing is hoisted: the same few hundred ns on every variant):

- ``grid``        the kernel as PR 31 had it (kept here verbatim):
                  grid (B, nblk), a block's 32 page DMAs started and
                  awaited inside its grid step            step 0's (a)
- ``grid:zero``   the same, every length zero: what 512 empty grid
                  steps cost                                       (b)
- ``grid:dma``    the DMAs with the arithmetic taken out           (c)
- ``grid:math``   the arithmetic on a resident buffer, no DMA      (d)
- ``new``         the module's kernel; ``new:blk=256`` with
                  ``LATENT_BLOCK_TOKENS`` changed
- ``walk``        the walk of this file (grid (B,), two slots), whose
                  pieces come apart: ``walk:ahead=0`` fetches no next
                  row's first block, ``walk:live=1`` fetches only the
                  live pages of a row's last block (a loop of their
                  count) and zeroes the rest of the slot,
                  ``walk:chunk=8`` fetches them in static groups of 8,
                  ``walk:late=1`` starts the fetch ahead after this
                  block's wait and not before it, ``walk:static=1``
                  writes a block's code once a slot, ``walk:dma`` /
                  ``walk:math`` as above, ``walk:blk=1024`` the block's
                  tokens

``--rows``/``--tokens``/``--lens-seed`` change the lengths drawn.
Results go to ``chiprun_out/exp_latent_body.json`` and into the header
of the latent section of ``bigdl_tpu/llm/kernels/paged_attention.py``."""

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
from bigdl_tpu.llm.kernels import paged_attention as pa  # noqa: E402

LANE = pa.LANE
F32 = jnp.float32
B, H, W, DV, PAGE, MAXP = 32, 32, 640, 512, 16, 512
PAGES = 1 + B * MAXP
SCALE = 192 ** -0.5


def _score(q_ref, block, base_tok, seq, acc_ref, m_ref, l_ref, dv, scale):
    """A block's arithmetic, as the kernel has had it since PR 27."""
    hp = q_ref.shape[1]
    n, _ = block.shape
    q = q_ref[0].astype(F32)
    kv = block.astype(F32)
    s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    pos = base_tok + jax.lax.broadcasted_iota(jnp.int32, (hp, n), 1)
    s = jnp.where(pos < seq, s, -1e30)
    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p_ = jnp.exp(s - m_new[:, :1])
    l_new = alpha * l_prev[:, :1] + jnp.sum(p_, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p_, kv[:, :dv], (((1,), (0,)), ((), ())), preferred_element_type=F32)
    m_ref[...] = m_new
    l_ref[...] = jnp.broadcast_to(l_new, l_prev.shape)


def _touch(block, l_ref):
    """What stands in for the arithmetic: one page's first lanes."""
    l_ref[0:PAGE] = l_ref[0:PAGE] + block[0:PAGE, 0:LANE].astype(F32)


def grid_kernel(len_ref, bt_ref, q_ref, kv_hbm, o_ref, mo_ref, lo_ref, buf,
                sem, acc_ref, m_ref, l_ref, *, page, ppb, pages_max, dv,
                scale, dma=True, math=True):
    """PR 31's ``_latent_decode_kernel``: one (row, block) a grid step."""
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
    base_tok = blk * (ppb * page)

    @pl.when(base_tok < seq)
    def _compute():
        if dma:
            copies = []
            for i in range(ppb):
                col = jnp.minimum(blk * ppb + i, pages_max - 1)
                pid = bt_ref[b * pages_max + col]
                c = pltpu.make_async_copy(kv_hbm.at[pid, 0], buf.at[i], sem)
                c.start()
                copies.append(c)
            for c in copies:
                c.wait()
        block = buf[...].reshape(ppb * page, q_ref.shape[2])
        if math:
            _score(q_ref, block, base_tok, seq, acc_ref, m_ref, l_ref, dv,
                   scale)
        else:
            _touch(block, l_ref)

    @pl.when(blk == nblk - 1)
    def _finish():
        o_ref[0] = acc_ref[...]
        mo_ref[0] = m_ref[...]
        lo_ref[0] = l_ref[...]


def walk_kernel(len_ref, bt_ref, q_ref, kv_hbm, o_ref, mo_ref, lo_ref, buf,
                sem, walked, *, page, ppb, pages_max, dv, scale, ahead=True,
                live=False, chunk=0, late=False, static=False, dma=True,
                math=True):
    """The walk with its pieces as switches: grid (B,), a row's live
    blocks in a loop, two slots. ``live`` fetches a block's live pages
    in a loop of their count, ``chunk`` in static groups of that many
    pages; ``late`` starts the fetch ahead AFTER this block's wait, in
    one region with the arithmetic (and always: where nothing is left
    to fetch it fetches this row's first block again, awaited after the
    last row); ``static`` writes the block's code once a slot."""
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    n = ppb * page
    hp, w = q_ref.shape[1], q_ref.shape[2]

    @pl.when(b == 0)
    def _first_row():
        walked[0] = 0
        if not dma or live or chunk:
            buf[...] = jnp.zeros_like(buf)

    seq = len_ref[b]
    nblk = (seq + (n - 1)) // n
    first = walked[0]

    def held(row, blk):
        """Pages of block ``blk`` that hold a token of ``row``."""
        return jnp.clip((len_ref[row] + (page - 1)) // page - blk * ppb,
                        0, ppb)

    def start(row, blk, slot, i):
        col = jnp.minimum(blk * ppb + i, pages_max - 1)
        pid = bt_ref[row * pages_max + col]
        pltpu.make_async_copy(kv_hbm.at[pid, 0], buf.at[slot, i],
                              sem.at[slot]).start()

    def wait_page(slot, i):
        pltpu.make_async_copy(kv_hbm.at[0, 0], buf.at[slot, i],
                              sem.at[slot]).wait()

    def pages(op, got):
        """``op(i)`` for the pages of a block that are fetched."""
        if live:
            jax.lax.fori_loop(0, got, lambda i, c: (op(i), c)[1], 0)
        elif chunk:
            for c0 in range(0, ppb, chunk):
                @pl.when(c0 < got)
                def _group():
                    for i in range(c0, min(c0 + chunk, ppb)):
                        op(i)
        else:
            for i in range(ppb):
                op(i)

    def fetch(row, blk, slot):
        if not dma:
            return
        got = held(row, blk)
        pages(lambda i: start(row, blk, slot, i), got)
        if live:        # a slot's other pages: zero, whatever they held
            def blank(i, c):
                buf[slot, i] = jnp.zeros((page, w), buf.dtype)
                return c
            jax.lax.fori_loop(got, ppb, blank, 0)

    def wait(row, blk, slot):
        if dma:
            pages(lambda i: wait_page(slot, i), held(row, blk))

    o_ref[0] = jnp.zeros((hp, dv), F32)
    mo_ref[0] = jnp.full((hp, LANE), -1e30, F32)
    lo_ref[0] = jnp.zeros((hp, LANE), F32)

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
            wait(b, j, slot)
            fetch(to_row, to_blk, 1 - slot)
        else:
            @pl.when(more | (nxt < rows))
            def _fetch_ahead():
                fetch(to_row, to_blk, 1 - slot)
            wait(b, j, slot)
        blk_rows = buf[slot].reshape(n, w)
        if math:
            _score(q_ref, blk_rows, j * n, seq, o_ref.at[0], mo_ref.at[0],
                   lo_ref.at[0], dv, scale)
        else:
            _touch(blk_rows, lo_ref.at[0])

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
            wait(b, 0, (first + nblk) % 2)
    walked[0] = first + nblk


def call(kernel, blk_tokens, q, kv, bt, lens, **switches):
    """``latent_attention_decode_stats``'s ``pallas_call`` around one of
    this file's kernels."""
    b, h, w = q.shape
    page = kv.shape[2]
    pages_max = bt.shape[1]
    ppb = max(1, min(blk_tokens // page, pages_max))
    walk = kernel is walk_kernel
    if walk:
        grid, row = (b,), (lambda b_, *_: (b_, 0, 0))
        scratch = [pltpu.VMEM((2, ppb, page, w), kv.dtype),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((1,), jnp.int32)]
        sem = ("arbitrary",)
    else:
        grid, row = (b, -(-pages_max // ppb)), (lambda b_, k_, *_: (b_, 0, 0))
        scratch = [pltpu.VMEM((ppb, page, w), kv.dtype),
                   pltpu.SemaphoreType.DMA,
                   pltpu.VMEM((h, DV), F32), pltpu.VMEM((h, LANE), F32),
                   pltpu.VMEM((h, LANE), F32)]
        sem = ("parallel", "arbitrary")
    acc, m, l = pl.pallas_call(
        functools.partial(kernel, page=page, ppb=ppb, pages_max=pages_max,
                          dv=DV, scale=SCALE, **switches),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=[pl.BlockSpec((1, h, w), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, h, DV), row),
                       pl.BlockSpec((1, h, LANE), row),
                       pl.BlockSpec((1, h, LANE), row)],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((b, h, DV), F32),
                   jax.ShapeDtypeStruct((b, h, LANE), F32),
                   jax.ShapeDtypeStruct((b, h, LANE), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=sem),
        interpret=jax.default_backend() != "tpu",
    )(lens, bt.reshape(-1), q, kv)
    return acc, m[:, :, 0], l[:, :, 0]


def variant(name):
    """``(run(q, kv, bt, lens) -> (acc, m, l), zero lengths?, whole?)``
    for a name such as ``walk:live=1,blk=256``; ``whole`` is false
    where a piece was taken out and the answer means nothing."""
    form, _, rest = name.partition(":")
    opts = dict(o.partition("=")[::2] for o in rest.split(",") if o)
    blk = int(opts.pop("blk", pa.LATENT_BLOCK_TOKENS))
    zero = opts.pop("zero", None) is not None
    if form == "new":
        assert not opts, opts

        def run(q, kv, bt, lens):
            kept, pa.LATENT_BLOCK_TOKENS = pa.LATENT_BLOCK_TOKENS, blk
            try:
                return pa.latent_attention_decode_stats.__wrapped__(
                    q, kv, bt, lens, page_size=PAGE, dv=DV, scale=SCALE,
                    interpret=jax.default_backend() != "tpu")
            finally:
                pa.LATENT_BLOCK_TOKENS = kept
        return run, zero, True
    kernel = {"grid": grid_kernel, "walk": walk_kernel}[form]
    switches = {}
    if "dma" in opts:                 # the DMAs alone
        opts.pop("dma")
        switches["math"] = False
    if "math" in opts:                # the arithmetic alone
        opts.pop("math")
        switches["dma"] = False
    for k in ("ahead", "live", "late", "static"):
        if k in opts:
            switches[k] = bool(int(opts.pop(k)))
    if "chunk" in opts:
        switches["chunk"] = int(opts.pop("chunk"))
    assert not opts, opts
    return (functools.partial(call, kernel, blk, **switches), zero,
            not ({"math", "dma"} & set(switches)))


def draw_lengths(rows, live, tokens, top, seed):
    """``live`` of ``rows`` lengths, log-normal, ``tokens`` in all, none
    over ``top``; the others zero, scattered."""
    rs = np.random.RandomState(seed)
    raw = np.exp(rs.normal(0.0, 0.75, live))
    lens = np.clip(raw / raw.sum() * tokens, 300, top).astype(np.int64)
    out = np.zeros(rows, np.int64)
    out[rs.permutation(rows)[:live]] = lens
    return out


def slope(run, q, kv, bt, lens, iters):
    """Per-call device time: slope of a fori_loop of calls between
    iters/4 and iters, best of 3."""
    def loop_for(n_it):
        @jax.jit
        def loop(q, kv, bt, lens):
            def body(i, carry):
                acc, m, l = run(q + carry * 1e-30, kv, bt, lens)
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
    ap.add_argument("--variants", default="grid;grid:zero;grid:dma;"
                    "grid:math;new;new:blk=256;new:blk=1024;walk;walk:dma;"
                    "walk:math;walk:ahead=0;walk:blk=768;walk:static=1;"
                    "walk:late=1;walk:live=1;walk:chunk=8")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--rows", type=int, default=24, help="live rows of 32")
    ap.add_argument("--tokens", type=int, default=61000)
    ap.add_argument("--lens-seed", type=int, default=0)
    ap.add_argument("--pages", type=int, default=PAGES)
    ap.add_argument("--maxp", type=int, default=MAXP)
    ap.add_argument("--out", default="exp_latent_body.json")
    args = ap.parse_args()
    chip = jax.default_backend() == "tpu"
    rs = np.random.RandomState(1)
    lens_np = draw_lengths(B, args.rows, args.tokens, args.maxp * PAGE - 1,
                           args.lens_seed)
    lens = jnp.asarray(lens_np, jnp.int32)
    # every row owns pages of its own, as the engine's ledger deals them
    bt = jnp.asarray(1 + rs.permutation(args.pages - 1)[:B * args.maxp]
                     .reshape(B, args.maxp), jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, W), F32)
    kv = jax.random.normal(jax.random.PRNGKey(1),
                           (args.pages, 1, PAGE, W), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = pa.latent_attention_reference_stats(q, kv, bt, lens, dv=DV,
                                                   scale=SCALE)
    n = pa.LATENT_BLOCK_TOKENS
    least_us = float(lens_np.sum()) * 576 * 2 / 819e9 * 1e6
    out = {"lengths": lens_np.tolist(), "tokens": int(lens_np.sum()),
           "live_blocks_of_512": int((-(-lens_np // n)).sum()),
           "least_us": round(least_us, 2)}
    print(json.dumps(out), flush=True)
    for name in args.variants.split(";"):
        try:
            run, zero, whole = variant(name)
            ln = jnp.zeros_like(lens) if zero else lens
            got = jax.jit(run)(q, kv, bt, ln)
            res = {}
            if whole and not zero:
                res["err"] = [round(float(
                    jnp.abs(g - w_).max() / jnp.abs(w_).max()), 5)
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
    with open(os.path.join("chiprun_out", args.out), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
