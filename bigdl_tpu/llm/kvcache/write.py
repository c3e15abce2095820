"""Writing new K/V into the page pool where it sits (ISSUE 26).

The pools are ``(L, P, H_kv, page, D)`` in the default layout, which is
what the Mosaic attention kernels read. A vectorised scatter on the
``P`` and ``page`` dimensions (``pool.at[:, phys, :, slots].set``) is
compiled in another layout, so XLA copies each whole pool into that
layout and back around it: 25 ms of a 56 ms decode step and 38 ms of
every prefill at 7B (PERF.md §5-6). The two writers here are
``dynamic_update_slice`` s, which the compiler performs in whatever
layout the operand has: the donated pool is updated in place and no
pool-sized array is made.

Both read every size from their operands' shapes and serve every paged
family, page size and cache dtype.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Sequence

import jax.numpy as jnp
from jax import lax


def write_kv(pool, phys, slots, new):
    """Token ``j``'s ``new[:, j]`` lands in ``pool[:, phys[j], :,
    slots[j], :]``, any targets: one ``(L, 1, H_kv, 1, D)`` slab a
    token, unrolled and in order (a later token wins a collision, which
    only the trash page sees). ``pool`` ``(L, P, H_kv, page, D)``,
    ``new`` ``(L, T, H_kv, D)``, ``phys``/``slots`` ``(T,)`` int32. For
    the decode step's ``T = B`` rows; a long run of one sequence's
    positions goes page by page through :func:`write_kv_run`."""
    new = new.astype(pool.dtype)
    zero = jnp.int32(0)
    for j in range(new.shape[1]):
        pool = lax.dynamic_update_slice(
            pool, new[:, j][:, None, :, None, :],
            (zero, phys[j], zero, slots[j], zero))
    return pool


def write_kv_run(pool, phys, slots, new):
    """:func:`write_kv` for ``T`` CONSECUTIVE positions of one sequence
    (a prefill's suffix, a chunk, a verify window), one visit a page.

    The contract is the engine's scatter targets: ``slots[j] ==
    (slots[0] + j) % page``, tokens that share a page share ``phys``,
    and the tokens the request must not write (padding, a later
    chunk's positions) are the run's tail, routed to trash page 0.
    The run is laid on a page grid at cell ``slots[0]``; visit ``v``
    reads the page of its first token, replaces the slots whose token
    targets that page and writes it back, so a first page entered
    mid-way, a last page left mid-way and the masked tail keep what
    they held. ``T // page + 1`` visits (two for a ``T`` below a page)
    of one ``(L, 1, H_kv, page, D)`` block each."""
    L, _, H, page, D = pool.shape
    T = new.shape[1]
    nvis = (T + page - 2) // page + 1
    phys = phys.astype(jnp.int32)
    s0 = slots[0]
    zero = jnp.int32(0)
    grid = lax.dynamic_update_slice(
        jnp.zeros((L, nvis * page, H, D), pool.dtype),
        new.astype(pool.dtype), (zero, s0, zero, zero))
    grid = grid.reshape(L, nvis, page, H, D).transpose(0, 1, 3, 2, 4)
    # the page each grid cell's token targets (-1: no token there), and
    # the page each visit rewrites: its first token's
    target = lax.dynamic_update_slice(
        jnp.full((nvis * page,), -1, jnp.int32), phys,
        (s0,)).reshape(nvis, page)
    first = jnp.clip(jnp.arange(nvis, dtype=jnp.int32) * page - s0,
                     0, T - 1)
    visit_phys = phys[first]
    mine = target == visit_phys[:, None]                    # (nvis, page)

    def visit(v, pool):
        at = (zero, visit_phys[v], zero, zero, zero)
        held = lax.dynamic_slice(pool, at, (L, 1, H, page, D))
        block = lax.dynamic_slice_in_dim(grid, v, 1, axis=1)
        keep = lax.dynamic_index_in_dim(mine, v, 0, keepdims=False)
        return lax.dynamic_update_slice(
            pool, jnp.where(keep[None, None, None, :, None], block, held),
            at)

    return lax.fori_loop(0, nvis, visit, pool)


def scatter_new_kv(k_pages, v_pages, bt, lens, k_new, v_new, *,
                   page: int):
    """Every layer's new-token K/V into the (donated) pools, in place —
    shared by every family's decode step. ``k_new``/``v_new`` are the
    layer-scan ys ``(L, B, Hkv, D)``; row ``b``'s token lands in page
    ``bt[b, lens[b] // page]`` at slot ``lens[b] % page`` through
    :func:`write_kv`, which leaves the pool in its own
    layout (no pool-sized copy in the compiled step)."""
    phys = bt[jnp.arange(lens.shape[0]), lens // page]        # (B,)
    slot = lens % page
    return (write_kv(k_pages, phys, slot, k_new),
            write_kv(v_pages, phys, slot, v_new))


def pool_shaped_copies(hlo_text: str, pool_shape: Sequence[int]) -> List[str]:
    """The instructions of an optimized HLO module that make a new
    array of the pool's shape by ``copy`` or ``transpose`` (fused ones
    too: a fusion's body is in the text). A step that writes the pool
    in place has none; the tests on the chip and ``chip_smoke.py``
    hold the engine's programs to that."""
    dims = ",".join(str(d) for d in pool_shape)
    made = re.compile(r"=\s*\w+\[" + dims + r"\]\S*\s+(copy|transpose)\(")
    return [line.strip() for line in hlo_text.splitlines()
            if made.search(line)]


def weight_slices(hlo_text: str, layers: Dict[str, Any]) -> List[str]:
    """The instructions of an optimized HLO module that take ONE layer
    out of a quantised stack in ``layers`` (a family's
    ``params["layers"]``: every ``{"q", "scale"}`` stack's dtype and
    shape less its ``L`` axis, read here and not written down): a
    ``dynamic-slice`` or ``slice`` that makes ``[1, *one_layer]``,
    alone or inside a fusion, whose body is in the text, and a ``copy``
    (an asynchronous one at its ``copy-done``) of one layer's packed
    ``q``. Copies of a ``scale``'s shape are not listed: float
    activations take it too (``f32[1,128,4096]`` is a 128-token prefill
    bucket at 7B), and a scale cannot be copied before it is sliced.
    A program whose INT4 kernel reads its layer out of the stack in
    place has none (ISSUE 28); one that hands the kernel a ``stack[l]``
    has a slice a leaf, each a copy of that layer's weights every
    step."""
    def one_layer(leaf):
        dt = jnp.dtype(leaf.dtype)
        return ({"u": "u", "i": "s"}.get(dt.kind, "f")
                + str(8 * dt.itemsize),
                ",".join(map(str, leaf.shape[1:])))

    stacks = [wd for wd in layers.values()
              if isinstance(wd, dict) and "q" in wd]
    if not stacks:
        return []
    sliced = "|".join(sorted({"%s\\[1,%s\\]" % one_layer(wd[k])
                              for wd in stacks for k in ("q", "scale")}))
    copied = "|".join(sorted({"%s\\[(1,)?%s\\]" % one_layer(wd["q"])
                              for wd in stacks}))
    made = re.compile(r"=\s*((" + sliced + r")\S*\s+(dynamic-slice|slice)|("
                      + copied + r")\S*\s+(copy|copy-done))\(")
    return [line.strip() for line in hlo_text.splitlines()
            if made.search(line)]
