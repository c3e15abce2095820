"""Power retention (power 2) over a fixed state a row (ISSUE 33).

A retention layer keeps no token. For every KV head it holds a state
``S = sum_s decay * phi(k_s) v_s^T`` and a normaliser ``z = sum_s decay
* phi(k_s)``, with ``phi(x) . phi(y) = (x . y)^2``, so that
``S^T phi(q) / (z . phi(q))`` is attention with the weights ``decay *
(q . k_s)^2`` over everything the row has seen, at a cost that does not
grow with the context.

**The symmetric form and its layout.** ``phi(x)`` holds the
``n (n + 1) / 2`` products ``x_a x_b``, ``a <= b`` (8,256 at ``n`` =
128), the cross terms times ``sqrt 2``. They are laid out by their
**cyclic distance**: tile ``d`` (``n`` wide, ``d`` = 0 .. n/2) holds
``c_d x_a x_((a + d) mod n)`` at lane ``a``; every unordered pair
appears once in tiles ``1 .. n/2 - 1``, the squares fill tile 0, and
tile ``n/2`` would hold each of its pairs twice, so its upper half is
zero. That is ``n/2 + 1`` whole tiles, :func:`state_width` = 8,320
numbers a row at ``n`` = 128: the 8,256 and 64 zeros the layout pads,
and a tile of ``phi`` is one lane rotation and one multiplication.

The state is held **transposed and in float32**: ``(rows, kv heads,
v width, state_width)``, the ``phi`` axis on the lanes, so that the
rank-one update broadcasts ``phi(k)`` along the sublanes and reading it
out is the ``A B^T`` product the MXU does natively. ``z`` is ``(rows,
kv heads, state_width)``. Row 0 of a layer is its trash row (as page 0
of every pool): a dead batch row reads and writes there.

Two entry points, each a Mosaic kernel on the chip and a plain-XLA twin
elsewhere (the twin is what the CPU tests and the CPU engine run):

- :func:`retention_decode`: one token a row. ONE pass over each live
  row's cache (ISSUE 36): the kernel takes ``q``, ``k``, ``v``, the
  gates and both cache arrays, walks the row's state in slabs and, for
  the slab it holds, builds its tiles of ``phi(q)`` and ``phi(k)`` in
  VMEM, reads the old state and the decayed normaliser out against
  ``phi(q)`` for the group's query heads, and writes ``gamma S + phi(k)
  v^T`` and ``gamma z + phi(k)`` back **in place**
  (``input_output_aliases``, both arrays). It hands back the read-out's
  numerator and denominator; the token's own term and the division are
  left to XLA at ``(B, Hkv, group, dv)``. No ``phi`` is ever in HBM
  and XLA touches neither cache array. The grid walks the live rows
  first; the steps of dead rows repeat the last live block's indices,
  which costs no DMA, and skip the arithmetic.
- :func:`retention_prefill_chunk`: the chunked form over one row's
  chunk of a prompt, the state carried from sub-chunk to sub-chunk in
  VMEM; the band ``(q . k)^2`` and the ``phi`` products on the MXU in
  bfloat16 with float32 accumulation. The gates' cumulative sums are
  folded into ``q`` and ``k`` beforehand (``phi(e^(b/2) q) = e^b
  phi(q)``), in float32.

Both kernel calls are jitted on their own, so that a program of eight
layers traces and lowers a kernel once and not eight times (the prefill
kernel's body is 65 tiles unrolled: 0.6 s a trace, a minute and a half
of set-up over the engine's seven prefill programs).

**What the decode kernel's time is made of** (ISSUE 36; my chip runs,
PR 36, one "TPU v5 lite": ``tools/exp_retention.py`` and the builder's
piece-at-a-time copies of the body; 20 batch rows, 8 KV heads, group 5,
``n`` = ``dv`` = 128; ms a call of ONE layer, the Mosaic call alone, two
rounds; the share is :func:`decode_bytes` over 819 GB/s)::

    body                              slab   11 live       15 live
    as it is                          1,664  1.305-1.307   1.661-1.663
                                             70.6-70.7 %   75.6-75.7 %
    as it is                          8,320  1.206-1.211   1.614-1.618
                                             76.2-76.5 %   77.8-78.0 %
    tiles concatenated, no scratch    8,320  1.210-1.212   1.617-1.623
    no tiles (the scratch as it lies) 1,664  1.304-1.310   1.661-1.662
                                      8,320  1.207-1.210   1.614-1.616
    no normaliser                     1,664  1.305         1.660-1.664
                                      8,320  1.207-1.214   1.613
    neither, v's column a constant    1,664  1.300         1.657-1.659
                                      8,320  1.205-1.214   1.609-1.622
    ISSUE 33's kernel (phi(q), phi(k) 1,664  1.296         1.686
    read from HBM, no normaliser)     8,320  1.233         1.649

No piece of the body shows: the 65 tiles, the normaliser's row and the
transposition of ``v`` all hide under the state's copies, and the call
is what its block DMAs and its grid steps take (0.1 ms of 1.3 between
440 steps of 852 KB and 88 of 4.26 MB at 11 live rows: ≈ 0.27 µs a
step). Hence a slab of the whole row. LLO counts a step (the chip's
compiler, no chip): 940 vector operations, 226 loads and 238 stores at
1,664 lanes against a block the DMA needs ≈ 2,600 cycles for.

**Four names a planted fault replaces.** ``benchmark/faults_brumby.py``
swaps :func:`_phi_tile`, :data:`SQRT2`, :func:`_decay` and
:func:`_finish` on this module while the served program is traced, and
the benchmark's check must then fail. Both kernel bodies and the XLA
around them reach each through the module's global at trace time: do
not inline them, pass them as arguments, or bind them at import.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SQRT2 = math.sqrt(2.0)
# tokens of a prefill chunk the state is carried over at a time: the
# band is (group * SUB, SUB) float32 in VMEM, and the gates' running sum
# inside it stays far from float32's range (256 * log 0.9 = -27)
SUB = 256
# lanes of the state a decode grid step holds: a head's whole row (all 65
# tiles, 4.26 MB a block). A slab is whole tiles and divides the row:
# 128, 640, 1,664 or 8,320 lanes (the table in the module's docstring)
SLAB = 8320


def state_width(n: int) -> int:
    """Numbers of ``phi`` of an ``n``-vector as laid out here."""
    return n * (n // 2 + 1)


def _phi_tile(x, x2, d: int, roll):
    """Tile ``d`` of ``phi(x)``: ``c_d x_a x_((a + d) mod n)`` at lane
    ``a``. ``x2`` is ``sqrt 2 * x`` (made once by the caller),
    ``roll(a, d)`` brings lane ``(a + d) mod n`` to lane ``a``. The one
    definition: :func:`phi` and the prefill kernel both build it."""
    if d == 0:
        return x * x
    tile = x * roll(x2, d)
    n = x.shape[-1]
    if 2 * d == n:
        # each pair of this distance would appear twice: keep one half
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        tile = jnp.where(lane < n // 2, tile, 0.0)
    return tile


def phi(x):
    """``(..., n)`` -> ``(..., state_width(n))`` float32 with ``phi(x) .
    phi(y) = (x . y)^2`` (``n`` even)."""
    x = x.astype(jnp.float32)
    x2 = x * SQRT2
    roll = lambda a, d: jnp.roll(a, -d, axis=-1)
    return jnp.concatenate([_phi_tile(x, x2, d, roll)
                            for d in range(x.shape[-1] // 2 + 1)], axis=-1)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _finish(num, den, n: int, eps: float):
    """``(num / n) / (den / n + eps)``; ``den`` with a last axis of 1."""
    return num / (den + n * eps)


def _decay(gam, z):
    """The normaliser a step on: ``gamma z``. ``gam`` is one gate a row
    of ``z``, or (inside the decode kernel) a scalar read from SMEM."""
    gam = jnp.asarray(gam)
    return (gam[..., None] if gam.ndim else gam) * z


def _decode_xla(state, z, q, k, v, g, slots, live, eps):
    del live
    gam = jnp.exp(g)
    pq, pk = phi(q), phi(k)
    s_new = (gam[..., None, None] * state[slots].astype(jnp.float32)
             + v.astype(jnp.float32)[..., :, None] * pk[..., None, :]
             ).astype(state.dtype)
    z_new = (_decay(gam, z[slots].astype(jnp.float32)) + pk).astype(z.dtype)
    num = jnp.einsum("bhgp,bhvp->bhgv", pq, s_new.astype(jnp.float32))
    den = jnp.einsum("bhgp,bhp->bhg", pq, z_new.astype(jnp.float32))
    return (_finish(num, den[..., None], q.shape[-1], eps),
            state.at[slots].set(s_new), z.at[slots].set(z_new))


def _decode_kernel(nl_ref, slot_ref, row_ref, gam_ref, q_ref, k_ref, v_ref,
                   s_ref, z_ref, num_ref, den_ref, so_ref, zo_ref,
                   pq_ref, pk_ref, *, hkv: int, ns: int):
    del slot_ref
    r, s, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n, slab = q_ref.shape[-1], s_ref.shape[-1]
    per = slab // n                       # tiles of phi a slab
    f32 = jnp.float32
    roll = lambda a, d: pltpu.roll(a, n - d, 1)

    @pl.when(r < nl_ref[0])
    def _():
        q = q_ref[0, pl.ds(h, 1)][0]                        # (rows, n)
        # the key's row on all 8 sublanes: a whole tile to rotate
        k = jnp.broadcast_to(k_ref[0, pl.ds(h, 1), :], (8, n))
        q2, k2 = q * SQRT2, k * SQRT2
        # this slab's tiles of phi(q) and phi(k), made here and never in
        # HBM. ``_phi_tile`` wants its distance static: a branch a slab
        for s0 in range(ns):
            @pl.when(s == s0)
            def _(s0=s0):
                for j in range(per):
                    lanes = pl.ds(j * n, n)
                    pq_ref[:, lanes] = _phi_tile(q, q2, s0 * per + j, roll)
                    pk_ref[:, lanes] = _phi_tile(k, k2, s0 * per + j, roll)

        pq, pk = pq_ref[...], pk_ref[0:1, :]
        gam = gam_ref[row_ref[r] * hkv + h]
        state = s_ref[0, 0].astype(f32)                     # (dv, slab)
        part = jax.lax.dot_general(
            pq.astype(jnp.bfloat16), state.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                     # (rows, dv)
        z_dec = _decay(gam, z_ref[0, pl.ds(h, 1), :].astype(f32))
        dpart = jnp.broadcast_to(
            jnp.sum(pq * z_dec, axis=1, keepdims=True), den_ref.shape[2:])

        @pl.when(s == 0)
        def _():
            num_ref[0, pl.ds(h, 1)] = part[None]
            den_ref[0, pl.ds(h, 1)] = dpart[None]

        @pl.when(s > 0)
        def _():
            num_ref[0, pl.ds(h, 1)] += part[None]
            den_ref[0, pl.ds(h, 1)] += dpart[None]

        dv = state.shape[0]
        vrow = jnp.broadcast_to(v_ref[0, pl.ds(h, 1), :], (dv, dv))
        eye = jax.lax.broadcasted_iota(jnp.int32, (dv, dv), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (dv, dv), 1)
        vcol = jnp.sum(jnp.where(eye, vrow, 0.0), axis=1, keepdims=True)
        so_ref[0, 0] = (gam * state + vcol * pk).astype(so_ref.dtype)
        zo_ref[0, pl.ds(h, 1), :] = (z_dec + pk).astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("slab", "interpret"))
def _decode_pallas_call(state, z, q, k, v, gam, eslot, erow, n_live, *,
                        slab: int, interpret: bool):
    """The pass over the live rows' cache: ``state`` and ``z`` updated in
    place, and of every live row ``S_old^T phi(q)`` (B, Hkv, rows, dv)
    and ``(gamma z_old) . phi(q)`` (B, Hkv, rows, 128; the sum on every
    lane). ``q`` (B, Hkv, rows, n), ``k`` (B, Hkv, n) float32."""
    b, hkv, rows, n = q.shape
    dv, width = state.shape[2], state.shape[3]
    ns = width // slab

    def at(r, s, h, nl):
        lv = r < nl[0]
        return jnp.where(lv, s, ns - 1), jnp.where(lv, h, hkv - 1)

    def state_map(r, s, h, nl, slot, row):
        s, h = at(r, s, h, nl)
        return slot[r], h, 0, s

    def z_map(r, s, h, nl, slot, row):
        return slot[r], 0, at(r, s, h, nl)[0]

    def by_row(*blk):
        return pl.BlockSpec((1,) + blk, lambda r, s, h, nl, slot, row:
                            (row[r],) + (0,) * len(blk))

    num, den, state, z = pl.pallas_call(
        functools.partial(_decode_kernel, hkv=hkv, ns=ns),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, ns, hkv),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                by_row(hkv, rows, n), by_row(hkv, n), by_row(hkv, dv),
                pl.BlockSpec((1, 1, dv, slab), state_map),
                pl.BlockSpec((1, hkv, slab), z_map)],
            out_specs=[
                by_row(hkv, rows, dv), by_row(hkv, rows, 128),
                pl.BlockSpec((1, 1, dv, slab), state_map),
                pl.BlockSpec((1, hkv, slab), z_map)],
            scratch_shapes=[pltpu.VMEM((rows, slab), jnp.float32),
                            pltpu.VMEM((8, slab), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, rows, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, hkv, rows, 128), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        input_output_aliases={7: 2, 8: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret, name="retention_decode",
    )(n_live, eslot, erow, gam.reshape(-1), q, k, v, state, z)
    return num, den, state, z


def _live_first(live):
    """The batch row of each grid step, the live rows first and the
    steps after them repeating the last live row, and how many are
    live, (1,). They depend on ``live`` alone: XLA makes them once a
    program, not once a layer."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_live = live.sum().astype(jnp.int32)
    steps = jnp.minimum(jnp.arange(live.shape[0]), jnp.maximum(n_live - 1, 0))
    return order[steps], n_live[None]


def _decode_pallas(state, z, q, k, v, g, slots, live, eps, slab, interpret):
    b, hkv, grp, n = q.shape
    rows = -(-grp // 8) * 8
    gam = jnp.exp(g)
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    erow, n_live = _live_first(live)
    num_old, den_old, state, z = _decode_pallas_call(
        state, z, jnp.pad(qf, ((0, 0), (0, 0), (0, rows - grp), (0, 0))),
        kf, vf, gam, slots[erow], erow, n_live,
        slab=slab, interpret=interpret)
    # the token itself, folded in: phi(q) . phi(k) = (q . k)^2
    qk2 = jnp.einsum("bhgn,bhn->bhg", qf, kf) ** 2
    num = gam[..., None, None] * num_old[:, :, :grp] \
        + qk2[..., None] * vf[:, :, None, :]
    den = den_old[:, :, :grp, :1] + qk2[..., None]
    return _finish(num, den, n, eps), state, z


def retention_decode(state, z, q, k, v, g, slots, live, *,
                     eps: float = 1e-6, slab: int = SLAB,
                     interpret: Optional[bool] = None):
    """One token a row: ``S <- gamma S + phi(k) v^T``, ``z <- gamma z +
    phi(k)``, ``y = S^T phi(q) / (z . phi(q) + n eps)``.

    ``state`` (R, Hkv, dv, P) float32 and ``z`` (R, Hkv, P), ``P`` =
    ``state_width(n)``; ``q`` (B, Hkv, group, n), ``k`` (B, Hkv, n),
    ``v`` (B, Hkv, dv); ``g`` (B, Hkv) float32 the log of the gate;
    ``slots`` (B,) int32 the state row of each batch row and ``live``
    (B,) bool which of them count (a dead row names a trash row: what
    is written there and returned for it means nothing). Returns ``(y
    (B, Hkv, group, dv) float32, state, z)``; give ``state`` and ``z``
    donated and both are updated in place, by the kernel itself: one
    Mosaic call reads and writes every live row's state and normaliser
    once, and no slot a live row does not name is touched. Elsewhere
    than on the chip, or at a shape the kernel does not fit (``n % 128``,
    ``P % slab``), the plain-XLA twin runs."""
    if interpret is None:
        if jax.default_backend() != "tpu" or q.shape[-1] % 128 \
                or state.shape[-1] % slab:
            return _decode_xla(state, z, q, k, v, g, slots, live, eps)
        interpret = False
    return _decode_pallas(state, z, q, k, v, g, slots, live, eps, slab,
                          interpret)


def decode_bytes(rows: int, hkv: int, dv: int, n: int) -> int:
    """What :func:`retention_decode` must move for ``rows`` live rows of
    one layer: each row's state and normaliser, read and written."""
    return rows * hkv * (dv + 1) * state_width(n) * 4 * 2


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _fold_gates(q, k, v, g, n_live, sub: int):
    """The chunk cut into sub-chunks, the gates folded in. ``q`` (C,
    Hkv, G, n), ``k`` (C, Hkv, n), ``v`` (C, Hkv, dv), ``g`` (C, Hkv).
    Returns ``q e^(b/2)``, ``k e^(-b/2)``, ``v`` (dead positions zero),
    each float32 with the heads in front, and ``e^(b_end)`` (subs,
    Hkv), ``b`` the gates' running sum from its sub-chunk's start."""
    c, hkv = g.shape
    alive = (jnp.arange(c) < n_live)[:, None]
    g = jnp.where(alive, g.astype(jnp.float32), 0.0)
    b = g.reshape(c // sub, sub, hkv).cumsum(axis=1)
    end = jnp.exp(b[:, -1])
    b = b.reshape(c, hkv)
    qt = q.astype(jnp.float32) * jnp.exp(0.5 * b)[:, :, None, None]
    kt = jnp.where(alive[..., None],
                   k.astype(jnp.float32) * jnp.exp(-0.5 * b)[..., None], 0.0)
    vt = jnp.where(alive[..., None], v.astype(jnp.float32), 0.0)
    return (qt.transpose(1, 2, 0, 3), kt.transpose(1, 0, 2),
            vt.transpose(1, 0, 2), end)


def _chunk_xla(s0, z0, qt, kt, vt, end, n: int, eps: float, sub: int):
    """``s0`` (Hkv, dv, P), ``z0`` (Hkv, P); the folded chunk."""
    hkv, grp, c, _ = qt.shape
    ns = c // sub
    causal = jnp.tril(jnp.ones((sub, sub), bool))

    def step(carry, xs):
        s, z = carry
        q, k, v, e = xs             # (Hkv, G, sub, n) (Hkv, sub, ·) (Hkv,)
        band = jnp.einsum("hgtn,hsn->hgts", q, k) ** 2
        band = jnp.where(causal, band, 0.0)
        pq, pk = phi(q), phi(k)
        s32, z32 = s.astype(jnp.float32), z.astype(jnp.float32)
        num = jnp.einsum("hgts,hsv->hgtv", band, v) \
            + jnp.einsum("hgtp,hvp->hgtv", pq, s32)
        den = band.sum(-1) + jnp.einsum("hgtp,hp->hgt", pq, z32)
        s = e[:, None, None] * (s32 + jnp.einsum("hsv,hsp->hvp", v, pk))
        z = e[:, None] * (z32 + pk.sum(1))
        return (s.astype(s0.dtype), z.astype(z0.dtype)), \
            _finish(num, den[..., None], n, eps)

    cut = lambda a, ax: jnp.moveaxis(
        a.reshape(a.shape[:ax] + (ns, sub) + a.shape[ax + 1:]), ax, 0)
    (s, z), y = jax.lax.scan(
        step, (s0, z0), (cut(qt, 2), cut(kt, 1), cut(vt, 1), end))
    # (ns, Hkv, G, sub, dv) -> (Hkv, G, C, dv)
    return jnp.moveaxis(y, 0, 2).reshape(hkv, grp, c, -1), s, z


def _chunk_kernel(fresh_ref, slot_ref, end_ref, q_ref, k_ref, v_ref,
                  vt_ref, s_ref, z_ref, y_ref, so_ref, zo_ref, *,
                  sub: int, n: int, eps: float):
    del slot_ref
    h = pl.program_id(0)
    fresh = fresh_ref[0] > 0
    grp, c = q_ref.shape[1], q_ref.shape[2]
    tiles = n // 2 + 1
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(h == 0)
    def _():
        zo_ref[...] = jnp.where(fresh, 0, z_ref[...])

    so_ref[...] = jnp.where(fresh, 0, s_ref[...])
    causal = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    causal = jnp.concatenate([causal] * grp, axis=0)
    roll = lambda a, d: pltpu.roll(a, n - d, 1)
    mine = jax.lax.broadcasted_iota(
        jnp.int32, (zo_ref.shape[1], n), 0) == h

    def one(ci, _):
        at = pl.multiple_of(ci * sub, sub)
        q = q_ref[0, :, pl.ds(at, sub), :].reshape(grp * sub, n)
        k = k_ref[0, pl.ds(at, sub), :]
        v = v_ref[0, pl.ds(at, sub), :].astype(bf16)
        vt = vt_ref[0, :, pl.ds(at, sub)].astype(bf16)
        e = end_ref[ci * pl.num_programs(0) + h]
        band = jax.lax.dot_general(
            q.astype(bf16), k.astype(bf16), (((1,), (1,)), ((), ())),
            preferred_element_type=f32)
        band = jnp.where(causal, band * band, 0.0)
        num = jnp.dot(band.astype(bf16), v, preferred_element_type=f32)
        den = jnp.sum(band, axis=1, keepdims=True)
        q2, k2 = q * SQRT2, k * SQRT2
        dacc = jnp.zeros((grp * sub, n), f32)
        for d in range(tiles):
            lanes = pl.ds(d * n, n)
            pq = _phi_tile(q, q2, d, roll)
            pk = _phi_tile(k, k2, d, roll)
            s_d = so_ref[0, 0, :, lanes].astype(f32)         # (dv, n)
            # this head's row of the tile, by mask: a sublane picked
            # by a traced index is not a load Mosaic has
            z_all = zo_ref[0, :, lanes].astype(f32)          # (Hkv, n)
            z_d = jnp.sum(jnp.where(mine, z_all, 0.0), axis=0,
                          keepdims=True)                     # (1, n)
            num += jax.lax.dot_general(
                pq.astype(bf16), s_d.astype(bf16),
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
            dacc += pq * z_d
            so_ref[0, 0, :, lanes] = (e * (s_d + jnp.dot(
                vt, pk.astype(bf16), preferred_element_type=f32))
            ).astype(so_ref.dtype)
            zo_ref[0, :, lanes] = jnp.where(
                mine, e * (z_d + jnp.sum(pk, axis=0, keepdims=True)), z_all
            ).astype(zo_ref.dtype)
        den = den + jnp.sum(dacc, axis=1, keepdims=True)
        y_ref[0, :, pl.ds(at, sub), :] = _finish(num, den, n, eps).reshape(
            grp, sub, -1)
        return 0

    jax.lax.fori_loop(0, c // sub, one, 0)


@functools.partial(jax.jit, static_argnames=("sub", "eps", "interpret"))
def _chunk_pallas(state, z, qt, kt, vt, end, slot, fresh, *, sub: int,
                  eps: float, interpret: bool):
    hkv, grp, c, n = qt.shape
    dv, width = state.shape[2], state.shape[3]
    per_head = lambda *blk: pl.BlockSpec(
        (1,) + blk, lambda h, fr, sl: (h,) + (0,) * len(blk))
    y, state, z = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub, n=n, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hkv,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                per_head(grp, c, n), per_head(c, n), per_head(c, dv),
                per_head(dv, c),
                pl.BlockSpec((1, 1, dv, width),
                             lambda h, fr, sl: (sl[0], h, 0, 0)),
                pl.BlockSpec((1, hkv, width),
                             lambda h, fr, sl: (sl[0], 0, 0))],
            out_specs=[
                per_head(grp, c, dv),
                pl.BlockSpec((1, 1, dv, width),
                             lambda h, fr, sl: (sl[0], h, 0, 0)),
                pl.BlockSpec((1, hkv, width),
                             lambda h, fr, sl: (sl[0], 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((hkv, grp, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name="retention_prefill_chunk",
    )(fresh, slot, end.reshape(-1), qt, kt, vt, vt.transpose(0, 2, 1),
      state, z)
    return y, state, z


def retention_dense(s0, z0, q, k, v, g, *, eps: float = 1e-6,
                    sub: int = 64):
    """One row's ``T`` positions from the state ``s0`` (Hkv, dv, P) and
    ``z0`` (Hkv, P), in plain XLA (the chunked form, sub-chunks of
    ``sub``): ``q`` (T, Hkv, group, n), ``k`` (T, Hkv, n), ``v`` (T,
    Hkv, dv), ``g`` (T, Hkv). Returns ``(y (T, Hkv, group, dv) float32,
    the state and the normaliser after position T - 1)``."""
    t = q.shape[0]
    sub = min(sub, t)
    pad = lambda a: jnp.pad(a, [(0, -t % sub)] + [(0, 0)] * (a.ndim - 1))
    qt, kt, vt, end = _fold_gates(pad(q), pad(k), pad(v), pad(g), t, sub)
    y, s, z = _chunk_xla(s0, z0, qt, kt, vt, end, q.shape[-1], eps, sub)
    return y.transpose(2, 0, 1, 3)[:t], s, z


def retention_prefill_chunk(state, z, q, k, v, g, slot, fresh, n_live, *,
                            eps: float = 1e-6, sub: int = SUB,
                            interpret: Optional[bool] = None):
    """The chunked form over one row's chunk of ``C`` positions, of
    which the first ``n_live`` count (the rest add nothing to the state
    and their outputs mean nothing).

    ``state`` (R, Hkv, dv, P) float32, ``z`` (R, Hkv, P); ``q`` (C,
    Hkv, group, n), ``k`` (C, Hkv, n), ``v`` (C, Hkv, dv), ``g`` (C,
    Hkv) the log-gates; ``slot`` () int32 the row's state row;
    ``fresh`` () bool: the row is newly seated, so what the state row
    holds is its last occupant's and is taken as zero. Returns ``(y (C,
    Hkv, group, dv) float32, state, z)``, the state row now holding the
    state after the chunk's last live position."""
    c, n = q.shape[0], q.shape[-1]
    sub = min(sub, c)
    if c % sub:
        raise ValueError(f"a chunk of {c} is not whole sub-chunks of {sub}")
    if interpret is None and jax.default_backend() == "tpu" \
            and n % 128 == 0 and sub % 128 == 0:
        interpret = False
    qt, kt, vt, end = _fold_gates(q, k, v, g, n_live, sub)
    if interpret is None:
        y, s1, z1 = _chunk_xla(
            jnp.where(fresh, 0, state[slot]), jnp.where(fresh, 0, z[slot]),
            qt, kt, vt, end, n, eps, sub)
        state, z = state.at[slot].set(s1), z.at[slot].set(z1)
    else:
        y, state, z = _chunk_pallas(
            state, z, qt, kt, vt, end,
            jnp.asarray(slot, jnp.int32).reshape(1),
            jnp.asarray(fresh, jnp.int32).reshape(1),
            sub=sub, eps=eps, interpret=interpret)
    return y.transpose(2, 0, 1, 3), state, z
