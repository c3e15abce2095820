"""ISSUE 30: the INT4 kernel's body walks K in slabs, takes a group's
float32 scale row as a sublane broadcast, rounds ``q * scale`` once,
and folds the zero-point through group sums of ``x`` taken outside the
kernel. The body against the numpy reference over both zero-point
strategies, M tiles padded and not, K in one partial slab (256), in
whole slabs (4,096), in two chunks whose last slab is partial (11,008:
2 x (2 x 1,024 + 704) packed rows, g = 172; 14,336: 2 x (3 x 1,024 +
512)), N a multiple of the tile and not, the 2-D form and two
layers of a stack (which must equal the 2-D form bit for bit)."""

import functools
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


def test_group_sums_are_float32_sums_over_each_group():
    x = np.random.RandomState(0).randn(5, 96).astype(np.float32)
    got = np.asarray(im._group_sums(jnp.asarray(x, jnp.bfloat16)))
    want = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)) \
        .reshape(5, 3, 32).sum(-1)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
