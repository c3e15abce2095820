"""ISSUE 30: the INT4 kernel's body walks K in slabs, takes a group's
float32 scale row as a sublane broadcast, rounds ``q * scale`` once,
and folds the zero-point through group sums of ``x`` taken outside the
kernel. The body against the numpy reference over both zero-point
strategies, M tiles padded and not, K in one partial slab (256), in
whole slabs (4,096), in two chunks whose last slab is partial (11,008:
2 x (2 x 1,024 + 704) packed rows, g = 172; 14,336: 2 x (3 x 1,024 +
512)), N a multiple of the tile and not, the 2-D form and two
layers of a stack (which must equal the 2-D form bit for bit).
ISSUE 38: the kernel takes each K chunk of x whole and builds its
even and odd k-planes in VMEM; those planes against ``x[:, 0::2]``
and ``x[:, 1::2]``, bit for bit."""

import functools
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from bigdl_tpu.llm.ggml.quantize import quantize
from bigdl_tpu.llm.kernels import (int4_matmul, int4_matmul_reference,
                                   to_tpu_layout)

im = importlib.import_module("bigdl_tpu.llm.kernels.int4_matmul")

L = 3
BN = 256            # the N tile these cases run at: N = 256 is a whole
                    # tile, N = 384 is padded to 512 (2-D) or tiled by 128 (stack)


_cases = itertools.count(1)


@pytest.fixture(autouse=True)
def _drop_executables():
    """Every case compiles programs of its own and the CPU client keeps
    hundreds of memory mappings an executable: a worker that ran all
    of them on top of the other files' could pass ``vm.max_map_count``
    (65,530 here), and the next ``mmap`` takes the worker down (seen
    with an earlier form of the body). Drop them every 32 cases."""
    yield
    if next(_cases) % 32 == 0:
        jax.clear_caches()


@pytest.fixture(autouse=True, scope="module")
def _leave_no_executables():
    yield
    jax.clear_caches()


@functools.lru_cache(maxsize=None)
def _weights(k, n):
    rs = np.random.RandomState(k + n)
    qds = [quantize(rs.randn(n, k).astype(np.float32) * 0.1, "sym_int4")
           for _ in range(L)]
    tds = [to_tpu_layout(qd) for qd in qds]
    return (qds, jnp.asarray(np.stack([t["q"] for t in tds])),
            jnp.asarray(np.stack([t["scale"] for t in tds])))


@functools.lru_cache(maxsize=None)
def _flat(mode, m, k, n, layer, bn):
    """The 2-D form on layer ``layer`` and the input it ran on."""
    _, q, scale = _weights(k, n)
    x = np.random.RandomState(m + k).randn(m, k).astype(np.float32)
    return x, np.asarray(int4_matmul(
        jnp.asarray(x), q[layer], scale[layer], bn=bn, interpret=True,
        out_dtype=jnp.float32, mode=mode))


def every_case(ks):
    """mode x m x K x N x form, the K's a file's own (the cases of one
    file run in one worker: the chunked K's, which cost most, are
    ``test_int4_body_chunked.py``'s)."""
    def wrap(fn):
        for name, values in (("form", ["2d", "stack0", "stack2"]),
                             ("n", [256, 384]), ("k", ks),
                             ("m", [1, 16, 100, 300]),
                             ("mode", ["corr", "sub8", "auto"])):
            fn = pytest.mark.parametrize(name, values)(fn)
        return fn
    return wrap


def check_body(mode, m, k, n, form):
    layer = 0 if form == "2d" else int(form[-1])
    qds, q, scale = _weights(k, n)
    if form == "2d":
        x, got = _flat(mode, m, k, n, layer, BN)
    else:
        # bit for bit against the 2-D form at the tile the stack is
        # blocked by (the CPU's dot sums in another order at another
        # width): 128 where BN does not divide N
        plan = im._stack_blocks(k, n, BN)
        x, flat = _flat(mode, m, k, n, layer, plan[0] if plan else BN)
        got = np.asarray(int4_matmul(
            jnp.asarray(x), q, scale, layer=layer, bn=BN, interpret=True,
            out_dtype=jnp.float32, mode=mode))
        np.testing.assert_array_equal(got, flat)
    ref = int4_matmul_reference(x, qds[layer]["q"], qds[layer]["scale"])
    assert np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6) < 0.02


@every_case([256, 4096])
def test_body_matches_reference(mode, m, k, n, form):
    check_body(mode, m, k, n, form)


def test_slabs_cover_every_chunk_shape():
    """The slab walk is a whole number of groups and covers K/2 exactly,
    whatever the chunk: what the kernel's loop assumes."""
    for k in (256, 4096, 11008, 14336, 224, 32):
        for _, kc in im._chunk_k(k):
            half = kc // 2
            starts = range(0, half, im._SLAB)
            rows = [min(im._SLAB, half - r0) for r0 in starts]
            assert sum(rows) == half
            assert all(r % im.HALF == 0 for r in rows)


@pytest.mark.parametrize("kc", [96, 4096, 5504])
def test_group_sums_are_float32_sums_over_each_group(kc):
    """The sums the kernel builds beside the planes (``corr``): float32
    sums of the bf16 activations over each group of 32, in another
    order than a plain reduce, so within float32 rounding of it. A
    chunk of whole blocks (4,096), one with a partial last block
    (5,504) and one of a partial block alone (96)."""
    x = np.random.RandomState(kc).randn(16, kc).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    _, _, got = _planes(xb, 16)
    want = np.asarray(xb.astype(jnp.float32)).reshape(16, -1, 32).sum(-1)
    assert np.asarray(got).dtype == np.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6)


def _planes(x, bm):
    """The kernel's ``_deinterleave`` run alone under interpret, one
    row tile a grid step: its two planes and its group sums written
    out."""
    mp, kc = x.shape

    def kern(x_ref, xe_ref, xo_ref, xs_ref, *stack):
        im._deinterleave(x_ref, xe_ref, xo_ref, xs_ref, *stack,
                         cdt=jnp.float32)

    plane = pl.BlockSpec((bm, kc // 2), lambda i: (i, 0))
    return pl.pallas_call(
        kern, grid=(mp // bm,),
        in_specs=[pl.BlockSpec((bm, kc), lambda i: (i, 0))],
        out_specs=[plane, plane,
                   pl.BlockSpec((bm, kc // 32), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, kc // 2), jnp.bfloat16)] * 2
        + [jax.ShapeDtypeStruct((mp, kc // 32), jnp.float32)],
        scratch_shapes=im._split_scratch(bm, kc, sub8=False)[3:],
        interpret=True)(x)


@pytest.mark.parametrize("m", [1, 16, 100, 300])
@pytest.mark.parametrize("kc", [224, 256, 4096, 5504, 7168])
def test_planes_are_the_even_and_odd_lanes(kc, m):
    """Every K chunk the kernel meets: one partial block (224), one
    whole block, 16 and 28 of them (Mistral's K and ``down_proj``'s
    chunk), and 21 and a half (Llama-2's 5,504). The selection product
    must hand every number on unchanged: both signs, exponents over the
    whole normal range, the M padding's zeros. (A -0.0 comes out +0.0:
    one 1.0 and 255 zeros a column add up to +0.0, which the weight
    product then takes as the same zero.)"""
    bm = im._align_bm(128, m)
    mp = -(-m // bm) * bm
    rs = np.random.RandomState(kc + m)
    x = (np.where(rs.rand(mp, kc) < 0.5, -1.0, 1.0)
         * (1.0 + rs.rand(mp, kc))
         * np.exp2(rs.randint(-126, 64, (mp, kc)))).astype(np.float32)
    x[m:] = 0.0
    x = jnp.asarray(x, jnp.bfloat16)
    xe, xo, _ = _planes(x, bm)
    want_e, want_o = np.asarray(x[:, 0::2]), np.asarray(x[:, 1::2])
    np.testing.assert_array_equal(np.asarray(xe).view(np.uint16),
                                  want_e.view(np.uint16))
    np.testing.assert_array_equal(np.asarray(xo).view(np.uint16),
                                  want_o.view(np.uint16))
