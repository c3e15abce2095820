"""Routed experts without a dropped token: sort by expert, then a
grouped SwiGLU over the experts that received tokens (ISSUE 27).

Every (token, expert) assignment is computed. There is no capacity and
no one-hot over the experts: the assignments are sorted by expert and
laid out in tiles of ``tm`` rows, each expert's rows padded up to whole
tiles, so that a tile belongs to exactly one expert. The kernel then
walks the tiles; tile ``i`` multiplies its ``(tm, H)`` rows by the
weights of expert ``tile_group[i]``, which scalar prefetch lets the
block index name, so the weights of an expert with no token are never
read. Shapes are static (``ceil(A / tm) + G`` tiles for ``A``
assignments over ``G`` groups, the worst case); the tiles past the
last used one repeat its block indices, which costs no DMA, and skip
the compute.

The stacked expert weights ``(L, G, H, 2I)`` / ``(L, G, I, H)`` are
given whole, viewed as ``(L·G, …)``, and the layer picks its groups by
offsetting ``tile_group`` with ``l·G``: a ``weights[l]`` slice inside
the layer scan would copy a layer's experts (1.2 GB at 128 × 768 ×
2048) in front of the custom call every step.

A family's shared expert rides along as extra groups that every live
token is assigned to with weight 1 (a SwiGLU of width ``S·I`` is the
sum of ``S`` SwiGLUs of width ``I`` over the column blocks), so the
whole expert layer but its router is this one named op.

**An expert-parallel share** (ISSUE 31). A chip of a deployment that
divides a layer's experts over several chips holds ``count`` of them
from ``first`` on: :func:`grouped_ffn` is told that range (``held``),
the router still chooses among all the experts, and an assignment to
an expert held elsewhere takes no row, no tile and no weight read and
adds nothing: the result is the partial sum of the held experts. With
``held=None`` every group is held and the traced program is what it
was.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


class Dispatch(NamedTuple):
    """Where each assignment sits in the tiled layout.

    ``row_src`` (M,) the token each padded row copies (``T`` = none, a
    zero row); ``pos`` (T, K) the padded row of each assignment (0 for
    a dead token's); ``tile_group`` (NT,) the group of each tile (the
    last used tile's for the unused ones); ``n_tiles`` () tiles in use;
    ``group_sizes`` (G,) assignments per group."""
    row_src: jnp.ndarray
    pos: jnp.ndarray
    tile_group: jnp.ndarray
    n_tiles: jnp.ndarray
    group_sizes: jnp.ndarray


def num_tiles(assignments: int, groups: int, tm: int) -> int:
    """Tiles that always suffice: every group may end in a partial
    tile."""
    return -(-assignments // tm) + groups


def _per_assignment(live):
    """``live`` (T,) by token or (T, K) by assignment -> broadcastable
    over (T, K)."""
    return live if live.ndim == 2 else live[:, None]


def dispatch(groups_of, live, n_groups: int, tm: int) -> Dispatch:
    """Sort the assignments ``groups_of`` (T, K) int32 by group and lay
    them out in tiles of ``tm`` rows, one group a tile. Tokens with
    ``live`` (T,) False get no row anywhere; ``live`` (T, K) says it
    of single assignments (an expert held on another chip)."""
    t, k = groups_of.shape
    a = t * k
    nt = num_tiles(a, n_groups, tm)
    gid = jnp.where(_per_assignment(live), groups_of, n_groups).reshape(a)
    order = jnp.argsort(gid, stable=True).astype(jnp.int32)
    # rank of each group's first assignment, by counting (no scatter)
    edges = (gid[None, :] < jnp.arange(n_groups + 1, dtype=jnp.int32)
             [:, None]).sum(-1, dtype=jnp.int32)
    first, sizes = edges[:-1], edges[1:] - edges[:-1]
    tiles = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles)
    tile0 = tile_end - tiles
    n_tiles = tile_end[-1]
    # padded rows -> source token
    tile_of_row = jnp.arange(nt * tm, dtype=jnp.int32) // tm
    group_of_tile = jnp.minimum(
        (tile_end[None, :] <= jnp.arange(nt, dtype=jnp.int32)[:, None])
        .sum(-1, dtype=jnp.int32), n_groups - 1)
    g_row = group_of_tile[tile_of_row]
    within = jnp.arange(nt * tm, dtype=jnp.int32) - tile0[g_row] * tm
    used = (tile_of_row < n_tiles) & (within < sizes[g_row])
    rank = jnp.clip(first[g_row] + within, 0, a - 1)
    row_src = jnp.where(used, order[rank] // k, t).astype(jnp.int32)
    # assignments -> padded row
    rank_of = jnp.argsort(order).astype(jnp.int32)
    g_safe = jnp.minimum(gid, n_groups - 1)
    pos = tile0[g_safe] * tm + rank_of - first[g_safe]
    pos = jnp.where(gid < n_groups, pos, 0).reshape(t, k)
    last = group_of_tile[jnp.maximum(n_tiles - 1, 0)]
    tile_group = jnp.where(jnp.arange(nt) < n_tiles, group_of_tile, last)
    return Dispatch(row_src, pos.astype(jnp.int32),
                    tile_group.astype(jnp.int32),
                    n_tiles.astype(jnp.int32), sizes)


ACTIVATIONS = ("swiglu", "relu2")


def _activate(up, width: int, activation: str):
    """The expert's nonlinearity over its first product ``up`` (...,
    2I | I) float32: ``"swiglu"`` ``silu(gate) * up`` over ``(H, 2I)``
    weights (gate columns, then up); ``"relu2"`` ``relu(up)^2`` over
    ``(H, I)`` (an expert that is not gated). The one definition: the
    kernel body and the XLA twin both call it."""
    if activation == "swiglu":
        return jax.nn.silu(up[..., :width]) * up[..., width:]
    if activation == "relu2":
        return jnp.square(jax.nn.relu(up))
    raise ValueError(f"activation {activation!r} is not one of "
                     f"{ACTIVATIONS}")


def _ffn_kernel(tg_ref, nt_ref, x_ref, wgu_ref, wd_ref, o_ref, *,
                activation: str):
    i = pl.program_id(0)

    @pl.when(i < nt_ref[0])
    def _():
        width = wd_ref.shape[0]
        gu = jnp.dot(x_ref[...], wgu_ref[...],
                     preferred_element_type=jnp.float32)
        act = _activate(gu, width, activation)
        o_ref[...] = jnp.dot(act.astype(wd_ref.dtype), wd_ref[...],
                             preferred_element_type=jnp.float32)


def _ffn_kernel_split(tg_ref, nt_ref, x_ref, *refs, activation: str):
    """:func:`_ffn_kernel` over a slice of the expert's width: grid step
    ``(i, j)`` adds row tile ``i``'s part through columns ``j`` of the
    intermediate to its output (float32, the same row block for every
    ``j``)."""
    i, j = pl.program_id(0), pl.program_id(1)
    *w_refs, wd_ref, o_ref = refs

    @pl.when(i < nt_ref[0])
    def _():
        x = x_ref[...]
        up = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
              for w in w_refs]
        act = _activate(jnp.concatenate(up, -1) if len(up) > 1 else up[0],
                        wd_ref.shape[0], activation)
        part = jnp.dot(act.astype(wd_ref.dtype), wd_ref[...],
                       preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            o_ref[...] = part

        @pl.when(j > 0)
        def _more():
            o_ref[...] += part


# VMEM a kernel's double-buffered expert weights may take before the
# intermediate is split over a second grid axis (v5e has 128 MiB)
WHOLE_EXPERT_VMEM = 120 << 20
SPLIT_EXPERT_VMEM = 48 << 20


@functools.partial(jax.jit,
                   static_argnames=("tm", "interpret", "activation"))
def moe_expert_ffn(x_pad, w_gate_up, w_down, tile_group, n_tiles, *,
                   tm: int, interpret: bool = False,
                   activation: str = "swiglu"):
    """``(M, H)`` rows in tiles of ``tm`` -> ``(M, H')`` float32: tile
    ``i`` goes through the expert ``tile_group[i]``: ``"swiglu"`` over
    ``w_gate_up`` (G, H, 2I: gate columns, then up), ``"relu2"`` over
    (G, H, I), then ``w_down`` (G, I, H'). Rows of tiles ``>=
    n_tiles[0]`` are left unwritten."""
    m, h = x_pad.shape
    nt = m // tm
    width = w_down.shape[1]
    first = w_gate_up.shape[2]
    last = lambda i, tg, n: (jnp.minimum(i, jnp.maximum(n[0] - 1, 0)), 0)
    # two buffers of one group's weights and of the row tiles, the
    # float32 intermediates, and slack
    need = (2 * (h * first + width * h) * w_down.dtype.itemsize
            + 2 * tm * h * (x_pad.dtype.itemsize + 4)
            + 3 * tm * 2 * width * 4 + (4 << 20))
    if need > WHOLE_EXPERT_VMEM:
        return _expert_ffn_split(x_pad, w_gate_up, w_down, tile_group,
                                 n_tiles, tm=tm, interpret=interpret,
                                 activation=activation)
    return pl.pallas_call(
        functools.partial(_ffn_kernel, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nt,),
            in_specs=[
                pl.BlockSpec((tm, h), last),
                pl.BlockSpec((None, h, first),
                             lambda i, tg, n: (tg[i], 0, 0)),
                pl.BlockSpec((None, width, h),
                             lambda i, tg, n: (tg[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, h), last)),
        out_shape=jax.ShapeDtypeStruct((m, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need),
        interpret=interpret,
    )(tile_group, n_tiles.reshape(1), x_pad, w_gate_up, w_down)


def _expert_ffn_split(x_pad, w_gate_up, w_down, tile_group, n_tiles, *,
                      tm: int, interpret: bool, activation: str):
    """:func:`moe_expert_ffn` for an expert too wide for two buffers of
    its weights (GLM-5's 6,144 x 2 x 2,048 + 2,048 x 6,144: 75 MB): the
    intermediate in slices of ``ti`` columns on a second grid axis, each
    slice's gate and up columns (two views of ``w_gate_up``) and its rows
    of ``w_down``; the output row tile is the running sum over the
    slices. A row tile streams its expert's weights once; the tiles past
    the last used one copy nothing, as in the whole form."""
    m, h = x_pad.shape
    nt = m // tm
    width = w_down.shape[1]
    per = (2 if activation == "swiglu" else 1) * h + h      # a column
    ti = width
    while 2 * per * ti * w_down.dtype.itemsize > SPLIT_EXPERT_VMEM \
            and ti % 256 == 0:
        ti //= 2
    nj = width // ti
    last = lambda i, n: jnp.minimum(i, jnp.maximum(n[0] - 1, 0))
    # a tile past the last used one keeps the last used block of every
    # operand (its group's last slice): no copy, as the whole form's
    part = lambda i, j, n: jnp.where(i < n[0], j, nj - 1)
    w_specs = [pl.BlockSpec((None, h, ti),
                            lambda i, j, tg, n, o=o: (tg[i], 0,
                                                      o + part(i, j, n)))
               for o in ((0, nj) if activation == "swiglu" else (0,))]
    need = (2 * per * ti * w_down.dtype.itemsize
            + 2 * tm * h * (x_pad.dtype.itemsize + 4)
            + 4 * tm * 2 * ti * 4 + (4 << 20))
    return pl.pallas_call(
        functools.partial(_ffn_kernel_split, activation=activation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nt, nj),
            in_specs=[pl.BlockSpec((tm, h),
                                   lambda i, j, tg, n: (last(i, n), 0))]
            + w_specs
            + [pl.BlockSpec((None, ti, h),
                            lambda i, j, tg, n: (tg[i], part(i, j, n), 0))],
            out_specs=pl.BlockSpec((tm, h),
                                   lambda i, j, tg, n: (last(i, n), 0))),
        out_shape=jax.ShapeDtypeStruct((m, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=need),
        interpret=interpret,
    )(tile_group, n_tiles.reshape(1), x_pad,
      *([w_gate_up] * len(w_specs)), w_down)


def moe_expert_ffn_reference(x_pad, w_gate_up, w_down, tile_group,
                             n_tiles, *, tm: int,
                             activation: str = "swiglu"):
    """XLA twin of :func:`moe_expert_ffn` (the path off the TPU): the
    tiles' weights are gathered, so it is for small widths. Unused
    tiles read zero."""
    m, h = x_pad.shape
    nt = m // tm
    width = w_down.shape[1]
    # float32 operands: the CPU backend has no bf16 x bf16 -> f32 dot
    x = x_pad.reshape(nt, tm, h).astype(jnp.float32)
    gu = jnp.einsum("nth,nhf->ntf", x,
                    w_gate_up[tile_group].astype(jnp.float32))
    act = _activate(gu, width, activation)
    out = jnp.einsum("ntf,nfh->nth",
                     act.astype(w_down.dtype).astype(jnp.float32),
                     w_down[tile_group].astype(jnp.float32))
    used = jnp.arange(nt)[:, None, None] < n_tiles
    return jnp.where(used, out, 0.0).reshape(m, -1)


def tile_rows(tokens: int) -> int:
    """Rows a tile holds: 16 (one packed bf16 sublane tile) while an
    expert sees a handful of rows, 128 once a prefill fills them."""
    return 128 if tokens >= 512 else 16


def grouped_ffn(x, groups_of, weights, live, w_gate_up, w_down, layer,
                n_groups: int, interpret=None, held=None,
                activation: str = "swiglu", shared: int = 0):
    """The expert layer's sum for ``x`` (T, H): ``sum_j weights[t, j] *
    FFN_{groups_of[t, j]}(x[t])`` over the ``n_groups`` groups of
    layer ``layer`` in the whole-stack weights ``(L·G, H, 2I)`` /
    ``(L·G, I, H)`` (``activation`` ``"swiglu"``; ``"relu2"``: ``(L·G,
    H, I)``). Returns ``(y (T, H) float32, group_sizes (G,))``.
    Dead tokens (``live`` False) get zero and touch no group.

    ``held = (first, count)``: the stack holds only the groups ``first
    .. first + count - 1`` of the ``groups_of`` numbering (``n_groups``
    is then ``count``); an assignment outside them is computed on
    another chip and is left out here, whatever its token. ``shared``
    (with ``held``): the last ``shared`` columns of ``groups_of`` are
    experts every chip holds (a family's shared experts, numbered ``0
    .. shared - 1`` in a block of their own), the stack's groups after
    the held ones."""
    t, h = x.shape
    if held is not None:
        first, count = held
        if shared:
            k = groups_of.shape[1] - shared
            routed = groups_of[:, :k]
            mine = (routed >= first) & (routed < first + count)
            kept = jnp.concatenate([jnp.where(mine, routed - first, 0),
                                    count + groups_of[:, k:]], 1)
            mine = jnp.concatenate([mine, jnp.ones((t, shared), bool)], 1)
            live = _per_assignment(live) & mine
            groups_of = kept
        else:
            mine = (groups_of >= first) & (groups_of < first + count)
            live = _per_assignment(live) & mine
            groups_of = jnp.where(mine, groups_of - first, 0)
        n_groups = count + shared
    tm = tile_rows(t)
    d = dispatch(groups_of, live, n_groups, tm)
    x_ext = jnp.concatenate([x, jnp.zeros((1, h), x.dtype)])
    x_pad = x_ext[d.row_src]
    tg = d.tile_group + layer * n_groups
    if interpret is None and jax.default_backend() != "tpu":
        y_pad = moe_expert_ffn_reference(x_pad, w_gate_up, w_down, tg,
                                         d.n_tiles, tm=tm,
                                         activation=activation)
    else:
        y_pad = moe_expert_ffn(x_pad, w_gate_up, w_down, tg, d.n_tiles,
                               tm=tm, interpret=bool(interpret),
                               activation=activation)
    w = jnp.where(_per_assignment(live), weights.astype(jnp.float32), 0.0)
    y = jnp.where((w != 0)[..., None], w[..., None] * y_pad[d.pos], 0.0)
    return y.sum(axis=1), d.group_sizes


def share_stats(group_sizes, live, top_k: int):
    """What a chip that holds a share of a layer's experts counts of one
    pass over it, (4,) int32: assignments computed here, assignments
    left to the chips that hold the other experts, held experts with a
    token, the fullest held expert's tokens. ``group_sizes`` (G,) is
    :func:`grouped_ffn`'s, ``live`` the tokens that count, ``top_k``
    the experts a token chose."""
    here = group_sizes.sum()
    return jnp.stack([here, live.sum() * top_k - here,
                      (group_sizes > 0).sum(),
                      group_sizes.max()]).astype(jnp.int32)


def route_sigmoid(router, h, top_k: int, norm_topk: bool = True,
                  scaling: float = 1.0):
    """The ``noaux_tc`` router every sigmoid-routed family shares: (T,
    H) -> chosen experts (T, k) int32 and their weights (T, k) float32.
    ``s = sigmoid(h W^T)``; the ``k`` largest of ``s + bias`` are
    chosen (the correction bias chooses and does not weigh); weights
    ``s`` over their sum, times ``scaling``. Float32 at the highest
    matmul precision, as published: a bf16 score flips choices whose
    ``s + b`` lie close."""
    s = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), router["w"].astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + router["bias"], top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling
