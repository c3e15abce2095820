"""The in-place K/V writers (ISSUE 26) against the vectorised scatter
they replace, ``pool.at[:, phys, :, slots].set(...)``: every page but
the trash page must hold the same bits."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.llm.kvcache.prefill import scatter_suffix_kv
from bigdl_tpu.llm.kvcache.write import scatter_new_kv

L = 2


def _pools(rs, P, H, page, D, dtype):
    shape = (L, P, H, page, D)
    return (jnp.asarray(rs.randn(*shape), dtype),
            jnp.asarray(rs.randn(*shape), dtype))


def _old(pool, phys, slots, new):
    return pool.at[:, phys, :, slots].set(
        new.transpose(1, 0, 2, 3).astype(pool.dtype))


def _same_but_trash(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(
        got[:, 1:].view(np.uint8), want[:, 1:].view(np.uint8))


def _decode_case(rs, page):
    """16 rows: three inactive (block table and length masked to the
    trash page, as ``make_sampled_step`` does), one on a page's last
    slot, one on a page's first, the rest anywhere; a page a row."""
    B, maxp = 16, 4
    bt = (1 + np.arange(B * maxp, dtype=np.int32)).reshape(B, maxp)
    lens = rs.randint(0, maxp * page, B).astype(np.int32)
    lens[3] = 2 * page - 1
    lens[5] = page
    for b in (0, 7, 15):
        bt[b], lens[b] = 0, 0
    return bt, lens, 1 + B * maxp


def _run_case(page, kind):
    """(offset, real tokens, bucket) of a run of consecutive positions."""
    if kind == "prefill":            # enters and leaves a page mid-way
        off, t = page + 3, 2 * page + 5
    elif kind == "aligned":          # whole pages, nothing padded
        off, t = page, 2 * page
    else:                            # verify window across a page edge
        off, t = 2 * page - 1, 3
    bucket = max(page if kind != "verify" else 2,
                 1 << (t - 1).bit_length())
    return off, t, bucket


CASES = list(itertools.product((8, 1), (128, 64), (8, 16, 128),
                               ("bfloat16", "float32")))


@pytest.mark.parametrize("H,D,page,dtype", CASES)
def test_decode_rows_match_scatter(H, D, page, dtype):
    rs = np.random.RandomState(H * 1000 + D + page)
    bt, lens, P = _decode_case(rs, page)
    k, v = _pools(rs, P, H, page, D, dtype)
    k_new = jnp.asarray(rs.randn(L, 16, H, D), jnp.float32)
    v_new = jnp.asarray(rs.randn(L, 16, H, D), jnp.float32)
    phys = bt[np.arange(16), lens // page]
    want = _old(k, phys, lens % page, k_new), \
        _old(v, phys, lens % page, v_new)
    got = jax.jit(scatter_new_kv, static_argnames="page")(
        k, v, jnp.asarray(bt), jnp.asarray(lens), k_new, v_new, page=page)
    _same_but_trash(got[0], want[0])
    _same_but_trash(got[1], want[1])


@pytest.mark.parametrize("kind", ("prefill", "aligned", "verify"))
@pytest.mark.parametrize("H,D,page,dtype", CASES)
def test_run_matches_scatter(H, D, page, dtype, kind):
    rs = np.random.RandomState(H * 1000 + D + page)
    off, t, bucket = _run_case(page, kind)
    pages_cap = -(-(off + bucket) // page) + 1
    P = pages_cap + 3
    bt_row = np.zeros(pages_cap, np.int32)
    n_own = -(-(off + t) // page)
    bt_row[:n_own] = rs.permutation(np.arange(1, P))[:n_own]
    # the engine's scatter targets (serving._prefill_ragged)
    pos = off + np.arange(bucket)
    phys = np.where(pos < off + t, bt_row[np.minimum(pos // page,
                                                     pages_cap - 1)],
                    0).astype(np.int32)
    slots = (pos % page).astype(np.int32)
    k, v = _pools(rs, P, H, page, D, dtype)
    k_new = jnp.asarray(rs.randn(L, bucket, H, D), jnp.float32)
    v_new = jnp.asarray(rs.randn(L, bucket, H, D), jnp.float32)
    want = _old(k, phys, slots, k_new), _old(v, phys, slots, v_new)
    got = jax.jit(scatter_suffix_kv)(
        k, v, jnp.asarray(phys), jnp.asarray(slots), k_new, v_new)
    _same_but_trash(got[0], want[0])
    _same_but_trash(got[1], want[1])
