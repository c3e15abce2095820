"""Mamba-2's selective state-space update over a fixed state a row
(ISSUE 37).

A Mamba-2 layer keeps no token. For every head ``h`` (``P`` channels,
its group's ``B`` and ``C`` of ``N`` numbers: head ``h`` is in group
``h // (H / G)``) it holds a matrix ``S`` (P, N):

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

with ``dt_t > 0`` the head's time step, ``a < 0`` its decay rate. (The
skip ``D x`` and everything around the recurrence, the convolution
too, is the family's and stays XLA: ``models/nemotron_h.py``.)

The state is held **in float32**, ``(rows, H, P, N)``, ``N`` on the
lanes: the rank-one update broadcasts ``B`` along the sublanes and the
read-out is the ``A B^T`` product the MXU does natively. Row 0 of a
layer is its trash row (as page 0 of every pool): a dead batch row
reads and writes there.

Two entry points, each a Mosaic kernel on the chip and a plain-XLA twin
elsewhere (the twin is what the CPU tests and the CPU engine run):

- :func:`ssm_decode`: one token a row, the **recurrent form**. ONE pass
  over each live row's state: the kernel walks it in blocks of
  :data:`HEADS_BLOCK` heads, writes ``exp(dt a) S + (dt x) B^T`` back
  **in place** (``input_output_aliases``) and reads the new state out
  against ``C`` on the MXU (bfloat16 operands, float32 sums). The grid
  walks the live rows first; the steps of dead rows repeat the last
  live block's indices, which costs no DMA, and skip the arithmetic
  (``retention_decode``'s walk).
- :func:`ssd_prefill_chunk`: the **chunked form** (SSD) over one row's
  chunk of a prompt, in sub-chunks of the model's ``chunk_size``: inside
  a sub-chunk the dual form ``(C_t . B_s) exp(sum_{r=s+1..t} dt_r a)
  dt_s x_s``, from before it ``exp(sum_{r<=t} dt_r a) S C_t``, the state
  carried from sub-chunk to sub-chunk in VMEM. The band ``C B^T`` and
  the products with the state on the MXU in bfloat16 with float32
  accumulation; the running sums of ``dt a`` and every decay in
  float32 (made in XLA: two small arrays, laid out once by rows and once
  by columns, so that the kernel transposes nothing).

Both kernel calls are jitted on their own, so that a program of five
such layers traces and lowers a kernel once.

**The name a planted fault replaces.** ``benchmark/faults_nemotron_h.py``
swaps :func:`_held` on this module while the served program is traced
(a state kept in bfloat16). Both kernel bodies and their twins reach it
through the module's global at trace time: do not inline it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.llm.kernels.retention import _live_first

# heads of a row's state one decode grid step holds: whole groups, and a
# divisor of the heads. 64 heads of (64, 128) float32 are 2.1 MB a block
HEADS_BLOCK = 64


def _held(s):
    """The state as it is kept between two tokens (what a step writes
    back): float32, a running sum under decays near 1."""
    return s


def _per_head(a, heads: int):
    """``(..., G, N)`` by group -> ``(..., H, N)`` by head."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_xla(state, dtx, bm, cm, da, slots):
    heads = state.shape[1]
    s_new = _held(
        da[..., None, None] * state[slots].astype(jnp.float32)
        + dtx[..., None] * _per_head(bm, heads)[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", s_new.astype(jnp.float32),
                   _per_head(cm, heads))
    return y, state.at[slots].set(s_new.astype(state.dtype))


def _decode_kernel(nl_ref, slot_ref, row_ref, da_ref, dtx_ref, b_ref, c_ref,
                   s_ref, y_ref, so_ref, *, heads: int, hpg: int):
    del slot_ref
    r, j = pl.program_id(0), pl.program_id(1)
    hb, p, n = s_ref.shape[1:]
    f32, bf16 = jnp.float32, jnp.bfloat16

    @pl.when(r < nl_ref[0])
    def _():
        row = row_ref[r]
        eye = jax.lax.broadcasted_iota(jnp.int32, (p, p), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1)
        for gi in range(hb // hpg):
            g = j * (hb // hpg) + gi
            bg = b_ref[0, pl.ds(g, 1), :]                   # (1, N)
            c8 = jnp.broadcast_to(c_ref[0, pl.ds(g, 1), :], (8, n))
            for hh in range(hpg):
                h = gi * hpg + hh
                da = da_ref[row * heads + j * hb + h]
                # the head's dt x on the sublanes: a row to a column
                xrow = jnp.broadcast_to(
                    dtx_ref[0, pl.ds(j * hb + h, 1), :], (p, p))
                xcol = jnp.sum(jnp.where(eye, xrow, 0.0), axis=1,
                               keepdims=True)
                so_ref[0, h] = _held(
                    da * s_ref[0, h].astype(f32) + xcol * bg
                ).astype(so_ref.dtype)
            # the group's new state against its C: (8, N) x (hpg P, N)^T
            s_new = so_ref[0, pl.ds(gi * hpg, hpg)].reshape(hpg * p, n)
            out = jax.lax.dot_general(
                c8.astype(bf16), s_new.astype(bf16),
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
            y_ref[0, pl.ds(g, 1), :] = out[0:1]


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _decode_pallas_call(state, dtx, bm, cm, da, eslot, erow, n_live, *,
                        hb: int, interpret: bool):
    """The pass over the live rows' state: ``state`` updated in place and
    of every live row the new state read out against ``C``, (B, G, H/G
    P) float32. ``dtx`` (B, H, P), ``bm``/``cm`` (B, G, N), ``da`` (B,
    H), all float32."""
    b, heads, p = dtx.shape
    groups, n = bm.shape[1:]
    hpg = heads // groups
    nj = heads // hb

    def state_map(r, j, nl, slot, row):
        return slot[r], jnp.where(r < nl[0], j, nj - 1), 0, 0

    def by_row(*blk):
        return pl.BlockSpec((1,) + blk, lambda r, j, nl, slot, row:
                            (row[r],) + (0,) * len(blk))

    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=heads, hpg=hpg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nj),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                by_row(heads, p), by_row(groups, n), by_row(groups, n),
                pl.BlockSpec((1, hb, p, n), state_map)],
            out_specs=[
                by_row(groups, hpg * p),
                pl.BlockSpec((1, hb, p, n), state_map)]),
        out_shape=[jax.ShapeDtypeStruct((b, groups, hpg * p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret, name="ssm_decode",
    )(n_live, eslot, erow, da.reshape(-1), dtx, bm, cm, state)
    return y, state


def ssm_decode(state, x, bm, cm, dt, a, d_skip, slots, live, *,
               heads_block: int = HEADS_BLOCK,
               interpret: Optional[bool] = None):
    """One token a row, the recurrent form: ``S <- exp(dt a) S + dt x
    B^T``, ``y = S C + D x``.

    ``state`` (R, H, P, N) float32; ``x`` (B, H, P), ``bm`` and ``cm``
    (B, G, N); ``dt`` (B, H) float32 the time steps (softplus taken),
    ``a`` (H,) float32 the decay rates (negative), ``d_skip`` (H,) the
    skip; ``slots`` (B,) int32 the state row of each batch row and
    ``live`` (B,) bool which of them count (a dead row names a trash
    row: what is written there means nothing, and its ``y`` is zero).
    Returns ``(y (B, H, P) float32, state)``; give ``state`` donated and
    it is updated in place, by the kernel itself: one Mosaic call reads
    and writes every live row's state once, and no slot a live row does
    not name is touched. Elsewhere than on the chip, or at a shape the
    kernel does not fit (``N % 128``, ``P % 8``, the heads not whole
    blocks of whole groups), the plain-XLA twin runs."""
    b, heads, p = x.shape
    groups, n = bm.shape[1:]
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    da = jnp.exp(dt * a.astype(f32))
    dtx = dt[..., None] * xf
    bm, cm = bm.astype(f32), cm.astype(f32)
    hb = min(heads_block, heads)
    fits = n % 128 == 0 and p % 8 == 0 and heads % hb == 0 \
        and hb % (heads // groups) == 0
    if interpret is None and (jax.default_backend() != "tpu" or not fits):
        y, state = _decode_xla(state, dtx, bm, cm, da, slots)
    else:
        erow, n_live = _live_first(live)
        y, state = _decode_pallas_call(
            state, dtx, bm, cm, da, slots[erow], erow, n_live, hb=hb,
            interpret=bool(interpret))
        y = y.reshape(b, heads, p)
    y = y + d_skip.astype(f32)[None, :, None] * xf
    return jnp.where(live[:, None, None], y, 0.0), state


def decode_bytes(rows: int, heads: int, p: int, n: int) -> int:
    """What :func:`ssm_decode` must move for ``rows`` live rows of one
    layer: each row's float32 state, read and written."""
    return rows * heads * p * n * 4 * 2


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _running_sums(dt, a, n_live, sub: int):
    """``dt`` (C, H) float32 -> the time steps with the dead positions'
    at zero (they then neither decay nor add), and ``cum`` (C, H), the
    running sum of ``dt a`` from each sub-chunk's start, inclusive."""
    c, heads = dt.shape
    dt = jnp.where((jnp.arange(c) < n_live)[:, None], dt, 0.0)
    cum = (dt * a).reshape(c // sub, sub, heads).cumsum(axis=1)
    return dt, cum.reshape(c, heads)


def _chunk_xla(s0, dtx, bm, cm, cum, sub: int):
    """``s0`` (H, P, N); ``dtx`` (C, H, P), ``bm``/``cm`` (C, G, N),
    ``cum`` (C, H). Returns ``(y (C, H, P), the state after C)``."""
    c, heads, p = dtx.shape
    ns = c // sub
    causal = jnp.tril(jnp.ones((sub, sub), bool))
    cut = lambda v: v.reshape((ns, sub) + v.shape[1:])

    def step(s, xs):
        x, b, cc, cu = xs       # (sub, H, P) (sub, G, N) (sub, G, N) (sub, H)
        bh, ch = _per_head(b, heads), _per_head(cc, heads)
        seg = cu[:, None, :] - cu[None, :, :]               # (t, s, H)
        band = jnp.einsum("thn,shn->tsh", ch, bh) \
            * jnp.where(causal[..., None], jnp.exp(jnp.minimum(seg, 0.0)),
                        0.0)
        y = jnp.einsum("tsh,shp->thp", band, x) \
            + jnp.exp(cu)[..., None] * jnp.einsum(
                "thn,hpn->thp", ch, s.astype(jnp.float32))
        w = jnp.exp(cu[-1][None] - cu)                      # (s, H)
        s = _held(jnp.exp(cu[-1])[:, None, None] * s.astype(jnp.float32)
                  + jnp.einsum("shp,shn->hpn", w[..., None] * x, bh))
        return s.astype(s0.dtype), y

    s, y = jax.lax.scan(step, s0, (cut(dtx), cut(bm), cut(cm), cut(cum)))
    return y.reshape(c, heads, p), s


def _chunk_kernel(fresh_ref, slot_ref, last_ref, elast_ref, cumr_ref,
                  cumc_ref, x_ref, xt_ref, b_ref, c_ref, s_ref, y_ref,
                  so_ref, *, sub: int):
    del slot_ref
    g = pl.program_id(0)
    hpg, c, p = x_ref.shape
    f32, bf16 = jnp.float32, jnp.bfloat16
    nt = (((1,), (1,)), ((), ()))
    so_ref[...] = jnp.where(fresh_ref[0] > 0, 0, s_ref[...])
    causal = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)

    def one(ci, _):
        at = pl.multiple_of(ci * sub, sub)
        bq = b_ref[0, pl.ds(at, sub), :].astype(bf16)       # (sub, N)
        cq = c_ref[0, pl.ds(at, sub), :].astype(bf16)
        band = jax.lax.dot_general(cq, bq, nt, preferred_element_type=f32)
        cols = cumc_ref[0, pl.ds(at, sub), :]               # (sub, hpg)
        for j in range(hpg):
            cr = cumr_ref[pl.ds(j, 1), pl.ds(at, sub)]      # (1, sub) by s
            cc = cols[:, j:j + 1]                           # (sub, 1) by t
            decay = jnp.where(causal, jnp.exp(jnp.minimum(cc - cr, 0.0)),
                              0.0)
            s_h = so_ref[0, j].astype(f32)                  # (P, N)
            y = jnp.dot((band * decay).astype(bf16),
                        x_ref[j, pl.ds(at, sub), :].astype(bf16),
                        preferred_element_type=f32)
            y += jnp.exp(cc) * jax.lax.dot_general(
                cq, s_h.astype(bf16), nt, preferred_element_type=f32)
            y_ref[j, pl.ds(at, sub), :] = y
            k = (ci * pl.num_programs(0) + g) * hpg + j
            w = jnp.exp(last_ref[k] - cr)                   # (1, sub)
            xt = (xt_ref[j, :, pl.ds(at, sub)] * w).astype(bf16)
            so_ref[0, j] = _held(
                elast_ref[k] * s_h
                + jnp.dot(xt, bq, preferred_element_type=f32)
            ).astype(so_ref.dtype)
        return 0

    jax.lax.fori_loop(0, c // sub, one, 0)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _chunk_pallas(state, dtx, bm, cm, cum, slot, fresh, *, sub: int,
                  interpret: bool):
    c, heads, p = dtx.shape
    groups, n = bm.shape[1:]
    hpg = heads // groups
    # the running sums by rows (H, C) and by columns (G, C, H / G), and
    # each sub-chunk's last, as it is and exponentiated, for SMEM
    last = cum.reshape(c // sub, sub, heads)[:, -1]         # (ns, H)
    by_group = lambda *blk: pl.BlockSpec(
        blk, lambda g, fr, sl: (g,) + (0,) * (len(blk) - 1))
    state_spec = pl.BlockSpec((1, hpg, p, n),
                              lambda g, fr, sl: (sl[0], g, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    xh = dtx.transpose(1, 0, 2)                             # (H, C, P)
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(groups,),
            in_specs=[
                smem, smem,
                by_group(hpg, c), by_group(1, c, hpg),
                by_group(hpg, c, p), by_group(hpg, p, c),
                by_group(1, c, n), by_group(1, c, n), state_spec],
            out_specs=[by_group(hpg, c, p), state_spec]),
        out_shape=[jax.ShapeDtypeStruct((heads, c, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name="ssd_prefill_chunk",
    )(fresh, slot, last.reshape(-1), jnp.exp(last).reshape(-1), cum.T,
      cum.reshape(c, groups, hpg).transpose(1, 0, 2), xh,
      xh.transpose(0, 2, 1), bm.transpose(1, 0, 2), cm.transpose(1, 0, 2),
      state)
    return y.transpose(1, 0, 2), state


def ssd_dense(s0, x, bm, cm, dt, a, d_skip, *, sub: int = 64):
    """One row's ``T`` positions from the state ``s0`` (H, P, N), in
    plain XLA (the chunked form, sub-chunks of ``sub``): ``x`` (T, H,
    P), ``bm``/``cm`` (T, G, N), ``dt`` (T, H). Returns ``(y (T, H, P)
    float32, the state after position T - 1)``."""
    t = x.shape[0]
    sub = min(sub, t)
    pad = lambda v: jnp.pad(v, [(0, -t % sub)] + [(0, 0)] * (v.ndim - 1))
    f32 = jnp.float32
    xf = pad(x.astype(f32))
    dt, cum = _running_sums(pad(dt.astype(f32)), a.astype(f32), t, sub)
    y, s = _chunk_xla(s0, dt[..., None] * xf, pad(bm.astype(f32)),
                      pad(cm.astype(f32)), cum, sub)
    return (y + d_skip.astype(f32)[None, :, None] * xf)[:t], s


def ssd_prefill_chunk(state, x, bm, cm, dt, a, d_skip, slot, fresh, n_live,
                      *, sub: int = 128, interpret: Optional[bool] = None):
    """The chunked form over one row's chunk of ``C`` positions, of
    which the first ``n_live`` count (the rest add nothing to the state
    and their outputs mean nothing).

    ``state`` (R, H, P, N) float32; ``x`` (C, H, P), ``bm``/``cm`` (C,
    G, N), ``dt`` (C, H) the time steps (softplus taken), ``a`` and
    ``d_skip`` (H,); ``slot`` () int32 the row's state row; ``fresh`` ()
    bool: the row is newly seated, so what the state row holds is its
    last occupant's and is taken as zero. ``sub`` the sub-chunk (the
    model's ``chunk_size``). Returns ``(y (C, H, P) float32, state)``,
    the state row now holding the state after the chunk's last live
    position."""
    c, heads, p = x.shape
    n = bm.shape[-1]
    sub = min(sub, c)
    if c % sub:
        raise ValueError(f"a chunk of {c} is not whole sub-chunks of {sub}")
    f32 = jnp.float32
    xf, bm, cm = x.astype(f32), bm.astype(f32), cm.astype(f32)
    dt, cum = _running_sums(dt.astype(f32), a.astype(f32), n_live, sub)
    dtx = dt[..., None] * xf
    if interpret is None and jax.default_backend() == "tpu" \
            and n % 128 == 0 and sub % 128 == 0 and p % 8 == 0:
        interpret = False
    if interpret is None:
        y, s1 = _chunk_xla(jnp.where(fresh, 0, state[slot]), dtx, bm, cm,
                           cum, sub)
        state = state.at[slot].set(s1)
    else:
        y, state = _chunk_pallas(
            state, dtx, bm, cm, cum,
            jnp.asarray(slot, jnp.int32).reshape(1),
            jnp.asarray(fresh, jnp.int32).reshape(1),
            sub=sub, interpret=interpret)
    return y + d_skip.astype(f32)[None, :, None] * xf, state


def prefill_chunk_flops(c: int, heads: int, groups: int, p: int, n: int,
                        sub: int = 128) -> int:
    """Multiply-adds x 2 of :func:`ssd_prefill_chunk` over ``c``
    positions: a group's band ``C B^T`` once, and a head's three
    products (the band with ``x``, ``C`` with the state, ``x^T`` with
    ``B``)."""
    sub = min(sub, c)
    return 2 * c * (groups * sub * n + heads * (sub * p + 2 * p * n))
