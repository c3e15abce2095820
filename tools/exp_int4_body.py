"""Chip experiment (ISSUE 30): where does one grid step of the INT4
kernel spend its time, per packed byte?

The decode step of Mistral-7B is 82 % this kernel at m = 16, at a third
of its HBM roofline, and the share is the same for a call of 112 grid
steps and one of 16: the cost is the body's own work on one block.
This harness times bodies that differ in ONE thing each, stacked form,
at ``(16, 4096) x (4096, 28672)`` (gate_up) and ``(16, 14336) x
(14336, 4096)`` (down: two K chunks of half 3584), by the slope of a
``fori_loop`` as ``exp_stream_sharing.py`` does:

- ``dma``      the block DMA alone under a trivial body: the floor
- ``pr29``     the body as PR 29 had it (kept here verbatim)
- ``noscale``  widen / mask / shift / convert + the two dots, no scale
- ``nocorr``   pr29 without the correction dot
- ``e_in``     pr29 with the expansion matrix handed in
- ``s1``       scale in float32 out of the expansion, round once
- ``s12``      s1 + E handed in + the correction from group sums of x
- ``s123``     s12 walked in slabs of 512 packed rows
- ``new``      the body as the kernel now has it (``int4_matmul``);
               ``new:slab=256,bn=512`` with the slab rows or the N tile
               changed; ``parent`` the module of the checkout that
               ``$EXP_PARENT`` names (both take ``--m`` above 16)
- ``probe``    the new arithmetic in this file, and ``probe_nocorr``,
               ``probe_1plane``, ``probe_nomul`` with one piece taken
               out; ``<body>@512`` runs a body of this file at bn = 512

ISSUE 38 (step 0 of the split inside the kernel): ``parent`` with
``$EXP_PARENT`` the checkout whose kernel takes the even/odd planes
from XLA is the split outside (p); ``new`` the kernel's own form (the
blocks of x stacked by a reshape, one selection product);
``new:split=<form>`` swaps the kernel's ``_deinterleave`` for a form
of this file
(``SPLITS``: ``block``, one selection product a 256-lane block in a
``fori_loop``; ``transpose``, x transposed into float32 VMEM and its
rows read at stride 2; ``strided``, a lane-strided load, which Mosaic
refuses; ``loops``, the blocks stacked and dealt out by two
``fori_loop``s in place of the kernel's reshape; ``none``, no split,
the floor); ``parent:sums=zero`` hands the
parent's kernel zeros for the group sums, so that ``parent`` less it
is what their XLA reduce costs. ``--vary`` rolls the operands a row
each iteration: without it XLA hoists the parent's split out of the
timed loop.

``--count`` needs no chip: it compiles each body for a described v5e
with Mosaic's dump on and counts the vector operations of one grid
step in the final LLO (run with ``JAX_PLATFORMS=cpu``). Results go to
``chiprun_out/exp_int4_body.json`` and into the header ledger of
``bigdl_tpu/llm/kernels/int4_matmul.py``."""

import argparse
import collections
import functools
import glob
import importlib
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
im = importlib.import_module("bigdl_tpu.llm.kernels.int4_matmul")

QK, HALF = 32, 16
L = 3
SHAPES = {"gate_up": (4096, 28672), "down": (14336, 4096),
          "qkv": (4096, 6144), "o": (4096, 4096)}
F32, BF16 = jnp.float32, jnp.bfloat16


def _expand_matrix(rows, per):
    """E[i, g] = [i // per == g], bf16."""
    g = rows // per
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, g), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, g), 1)
    return jnp.where(row // per == col, 1.0, 0.0).astype(BF16)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


# -- bodies: (xe, xo, q, scale[, e][, xs], o) -------------------------------

def body_dma(xe_ref, xo_ref, q_ref, scale_ref, o_ref):
    bn = o_ref.shape[1]
    o_ref[:] = (scale_ref[0:16, :]
                + q_ref[0:32, :].astype(jnp.int32).astype(F32)[0:16]
                + xe_ref[:, 0:bn].astype(F32) + xo_ref[:, 0:bn].astype(F32))


def body_pr29(xe_ref, xo_ref, q_ref, scale_ref, o_ref, e_ref=None,
              corr=True, scale=True):
    q = q_ref[:].astype(jnp.int32)
    half = q.shape[0]
    xe, xo = xe_ref[:], xo_ref[:]
    if not scale:
        acc = _dot(xe, (q & 0xF).astype(BF16))
        acc += _dot(xo, (q >> 4).astype(BF16))
        o_ref[:] = acc
        return
    e = _expand_matrix(half, HALF) if e_ref is None else e_ref[:]
    s_exp = _dot(e, scale_ref[:].astype(BF16)).astype(BF16)
    acc = _dot(xe, (q & 0xF).astype(BF16) * s_exp)
    acc += _dot(xo, (q >> 4).astype(BF16) * s_exp)
    if corr:
        acc -= 8.0 * _dot(xe + xo, s_exp)
    o_ref[:] = acc


def body_e_in(xe_ref, xo_ref, q_ref, scale_ref, e_ref, o_ref):
    body_pr29(xe_ref, xo_ref, q_ref, scale_ref, o_ref, e_ref=e_ref)


def body_s1(xe_ref, xo_ref, q_ref, scale_ref, o_ref):
    q = q_ref[:].astype(jnp.int32)
    s_exp = _dot(_expand_matrix(q.shape[0], HALF),
                 scale_ref[:].astype(BF16))
    xe, xo = xe_ref[:], xo_ref[:]
    acc = _dot(xe, ((q & 0xF).astype(F32) * s_exp).astype(BF16))
    acc += _dot(xo, ((q >> 4).astype(F32) * s_exp).astype(BF16))
    acc -= 8.0 * _dot(xe + xo, s_exp.astype(BF16))
    o_ref[:] = acc


def _corr(xs_ref, sb):
    """8 * (group sums of x) @ scales, the sums as a bf16 hi + lo pair."""
    xs = xs_ref[:]
    hi = xs.astype(BF16)
    lo = (xs - hi.astype(F32)).astype(BF16)
    return 8.0 * (_dot(hi, sb) + _dot(lo, sb))


def body_s12(xe_ref, xo_ref, q_ref, scale_ref, e_ref, xs_ref, o_ref):
    q = q_ref[:].astype(jnp.int32)
    sb = scale_ref[:].astype(BF16)
    s_exp = _dot(e_ref[:], sb)
    acc = _dot(xe_ref[:], ((q & 0xF).astype(F32) * s_exp).astype(BF16))
    acc += _dot(xo_ref[:], ((q >> 4).astype(F32) * s_exp).astype(BF16))
    o_ref[:] = acc - _corr(xs_ref, sb)


def body_s123(xe_ref, xo_ref, q_ref, scale_ref, e_ref, xs_ref, o_ref,
              rows=512):
    half = q_ref.shape[0]
    sb = scale_ref[:].astype(BF16)
    acc = -_corr(xs_ref, sb)
    for r0 in range(0, half, rows):
        q = q_ref[r0:r0 + rows, :].astype(jnp.int32)
        s_exp = _dot(e_ref[:], sb[r0 // HALF:(r0 + rows) // HALF])
        acc += _dot(xe_ref[:, r0:r0 + rows],
                    ((q & 0xF).astype(F32) * s_exp).astype(BF16))
        acc += _dot(xo_ref[:, r0:r0 + rows],
                    ((q >> 4).astype(F32) * s_exp).astype(BF16))
    o_ref[:] = acc


def body_probe(xe_ref, xo_ref, q_ref, scale_ref, xs_ref, o_ref, corr=True,
               planes=2, mul=True, rows=512):
    """The new body (sublane-broadcast float32 scales, slabs) with one
    piece taken out at a time: what the rest costs."""
    half, bn = q_ref.shape
    acc = jnp.zeros(o_ref.shape, F32)
    if corr:
        acc = -8.0 * jnp.dot(xs_ref[:], scale_ref[:],
                             preferred_element_type=F32,
                             precision=jax.lax.Precision.HIGHEST)
    for r0 in range(0, half, rows):
        q = q_ref[r0:r0 + rows, :].astype(jnp.int32)
        lo, hi = (q & 0xF).astype(F32), (q >> 4).astype(F32)
        if mul:
            s_exp = jnp.concatenate(
                [jnp.broadcast_to(scale_ref[g:g + 1, :], (HALF, bn))
                 for g in range(r0 // HALF, (r0 + rows) // HALF)])
            lo, hi = lo * s_exp, hi * s_exp
        acc += _dot(xe_ref[:, r0:r0 + rows], lo.astype(BF16))
        if planes == 2:
            acc += _dot(xo_ref[:, r0:r0 + rows], hi.astype(BF16))
    o_ref[:] = acc


BODIES = {
    "dma": (body_dma, ()),
    "pr29": (body_pr29, ()),
    "noscale": (functools.partial(body_pr29, scale=False), ()),
    "nocorr": (functools.partial(body_pr29, corr=False), ()),
    "e_in": (body_e_in, ("e",)),
    "s1": (body_s1, ()),
    "s12": (body_s12, ("e", "xs")),
    "s123": (body_s123, ("e_slab", "xs")),
    "probe": (body_probe, ("xs",)),
    "probe_nocorr": (functools.partial(body_probe, corr=False), ("xs",)),
    "probe_1plane": (functools.partial(body_probe, planes=1), ("xs",)),
    "probe_nomul": (functools.partial(body_probe, mul=False), ("xs",)),
}


# -- the even/odd split, forms the kernel does not take (ISSUE 38) -------

def _planes(bm, kc, sub8):
    return _KERNEL_SCRATCH(bm, kc, sub8)[:2 if sub8 else 3]


def _no_sums(xs_ref):
    """The forms below build the planes alone: zeros for the group
    sums (``corr`` reads wrong then; the time is what they are for)."""
    if xs_ref is not None:
        xs_ref[...] = jnp.zeros(xs_ref.shape, F32)


def split_block(x_ref, xe_ref, xo_ref, xs_ref, *, cdt):
    """One selection product a 256-lane block: the MXU loads the
    selection kc/256 times."""
    _no_sums(xs_ref)
    bm, kc = x_ref.shape
    sel = im._selection(256, cdt)

    def body(b, carry):
        r = _dot(x_ref[:, pl.ds(pl.multiple_of(b * 256, 256), 256)]
                 .astype(cdt), sel).astype(BF16)
        at = pl.ds(pl.multiple_of(b * 128, 128), 128)
        xe_ref[:, at] = r[:, :128]
        xo_ref[:, at] = r[:, 128:]
        return carry
    jax.lax.fori_loop(0, kc // 256, body, 0)


def split_transpose(x_ref, xe_ref, xo_ref, xs_ref, t_ref, *, cdt):
    """x transposed into float32 VMEM, its rows read at stride 2 and
    the planes transposed back (the XLU and strided sublane loads)."""
    del cdt
    _no_sums(xs_ref)
    h = xe_ref.shape[1]
    t_ref[...] = x_ref[...].astype(F32).T
    xe_ref[...] = t_ref[pl.ds(0, h, stride=2), :].T.astype(BF16)
    xo_ref[...] = t_ref[pl.ds(1, h, stride=2), :].T.astype(BF16)


def split_strided(x_ref, xe_ref, xo_ref, xs_ref, *, cdt):
    """A lane-strided load: "not implemented: Strided load with non
    32-bit data" (and in float32, "The last dim size is not 128")."""
    del cdt
    _no_sums(xs_ref)
    h = xe_ref.shape[1]
    xe_ref[...] = x_ref[:, pl.ds(0, h, stride=2)]
    xo_ref[...] = x_ref[:, pl.ds(1, h, stride=2)]


def split_loops(x_ref, xe_ref, xo_ref, xs_ref, s_ref, *, cdt):
    """The first form the kernel took: the blocks gathered into a VMEM
    stack and dealt out to the planes by two ``fori_loop``s of dynamic
    256-lane slices, the same two products between; whole blocks only.
    As fast at decode and faster at prefill than the reshape, but its
    two loops, nested under the ``pl.when``, made each lowering slower
    (+3.5-4.5 s of warm ``setup_s`` in the cell)."""
    bm, kc = x_ref.shape
    nb, g = kc // 256, kc // QK

    def gather(b, carry):
        s_ref[pl.ds(pl.multiple_of(b * bm, bm), bm), :] = \
            x_ref[:, pl.ds(pl.multiple_of(b * 256, 256), 256)]
        return carry

    def deal(b, carry):
        r = s_ref[pl.ds(pl.multiple_of(b * bm, bm), bm), :]
        at = pl.ds(pl.multiple_of(b * 128, 128), 128)
        xe_ref[:, at] = r[:, :128]
        xo_ref[:, at] = r[:, 128:]
        return carry

    jax.lax.fori_loop(0, nb, gather, 0)
    s = s_ref[...].astype(cdt)
    if xs_ref is not None:
        y = _dot(s, im._grouping(256, g, None, cdt)).reshape(nb, bm, g)
        own = (jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
               == jax.lax.broadcasted_iota(jnp.int32, y.shape, 2) >> 3)
        xs_ref[...] = jax.lax.reduce_sum(
            jax.lax.select(own, y, jnp.zeros_like(y)), (0,))
    s_ref[...] = _dot(s, im._selection(256, cdt)).astype(BF16)
    jax.lax.fori_loop(0, nb, deal, 0)


def split_none(x_ref, xe_ref, xo_ref, xs_ref, *, cdt):
    """No split at all: the planes hold whatever VMEM held (the floor
    the other forms are measured against; the products are wrong)."""
    _no_sums(xs_ref)


_KERNEL_SCRATCH = im._split_scratch

SPLITS = {
    "block": (split_block, _planes),
    "loops": (split_loops, lambda bm, kc, sub8: _planes(bm, kc, sub8)
              + [pltpu.VMEM((kc // 256 * bm, 256), BF16)]),
    "none": (split_none, _planes),
    "transpose": (split_transpose, lambda bm, kc, sub8: _planes(
        bm, kc, sub8) + [pltpu.VMEM((kc, bm), F32)]),
    "strided": (split_strided, _planes),
}


def _parent_module():
    """``int4_matmul.py`` of another checkout, under another name."""
    import importlib.util
    path = os.path.join(os.environ["EXP_PARENT"],
                        "bigdl_tpu/llm/kernels/int4_matmul.py")
    spec = importlib.util.spec_from_file_location("int4_matmul_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stacked_kernel(layer_ref, *refs, body):
    del layer_ref
    body(*refs)


def variant_call(name, m, k, n, bn=256):
    """One linear through body ``name``: ``prep(x)`` makes the operands
    that do not depend on the layer, ``run(ops, q, scale, layer)`` is
    the Mosaic calls alone (what the slope times)."""
    if name.startswith(("new", "parent")):
        # "new" is the kernel as the module has it, "parent" the module
        # of the checkout $EXP_PARENT names (any m, both zero-point
        # modes); "new:slab=256,bn=512" with the slab rows or the N
        # tile changed
        mod = im if name.startswith("new") else _parent_module()
        opts = dict(kv.split("=") for kv in name.partition(":")[2].split(",")
                    if kv)

        patch = {"_SLAB": int(opts.get("slab", getattr(mod, "_SLAB", 0)))}
        if "split" in opts:
            patch["_deinterleave"], patch["_split_scratch"] = \
                SPLITS[opts["split"]]
        if opts.get("sums") == "zero":
            patch["_group_sums"] = lambda x: jnp.zeros(
                (x.shape[0], x.shape[1] // QK), F32)

        def run_mod(x, q, scale, layer):
            keep = {k: getattr(mod, k, None) for k in patch}
            for k, v in patch.items():
                setattr(mod, k, v)
            try:
                return mod._int4_matmul_stacked_jit.__wrapped__(
                    x, q, scale, layer, bm=128,
                    bn=int(opts.get("bn", bn)), interpret=False,
                    out_dtype=F32, mode="auto")
            finally:
                for k, v in keep.items():
                    setattr(mod, k, v)
        return (lambda x: x), run_mod
    if "@" in name:                   # "dma@512": the N tile
        name, bn = name.split("@")[0], int(name.split("@")[1])
    body, extras = BODIES[name]
    chunks = im._chunk_k(k)

    def prep(x):
        out = []
        for k0, kc in chunks:
            ex = {"xe": x[:, k0:k0 + kc:2], "xo": x[:, k0 + 1:k0 + kc:2]}
            if "e" in extras:
                ex["e"] = _expand_matrix(kc // 2, HALF)
            if "e_slab" in extras:
                ex["e"] = _expand_matrix(512, HALF)
            if "xs" in extras:
                ex["xs"] = x[:, k0:k0 + kc].astype(F32).reshape(
                    m, kc // QK, QK).sum(-1)
            out.append(ex)
        return out

    def run(ops, q, scale, layer):
        out = None
        for c, ((_, kc), ex) in enumerate(zip(chunks, ops)):
            half, g = kc // 2, kc // QK
            in_specs = [
                pl.BlockSpec((m, half), lambda i, j, l: (i, 0)),
                pl.BlockSpec((m, half), lambda i, j, l: (i, 0)),
                pl.BlockSpec((None, half, bn),
                             lambda i, j, l, c=c: (l[0], c, j)),
                pl.BlockSpec((None, g, bn),
                             lambda i, j, l, c=c: (l[0], c, j))]
            args = [ex["xe"], ex["xo"], q, scale]
            if "e" in ex:
                in_specs.append(pl.BlockSpec(ex["e"].shape,
                                             lambda i, j, l: (0, 0)))
                args.append(ex["e"])
            if "xs" in ex:
                in_specs.append(pl.BlockSpec((m, g), lambda i, j, l: (i, 0)))
                args.append(ex["xs"])
            part = pl.pallas_call(
                functools.partial(_stacked_kernel, body=body),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1, grid=(1, n // bn),
                    in_specs=in_specs,
                    out_specs=pl.BlockSpec((m, bn), lambda i, j, l: (i, j))),
                out_shape=jax.ShapeDtypeStruct((m, n), F32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel")),
            )(layer, *args)
            out = part if out is None else out + part
        return out

    return prep, run


# -- counting, no chip ----------------------------------------------------

_SLOTS = {
    "load": ("vector_load",), "store": ("vector_store",),
    "mxu": ("vlatch", "vmatmul", "vmatres", "vdwg"),
    "free": ("vbitcast", "constant"),
}


def count_ops(name, shape):
    """Vector ops of ONE grid step of body ``name`` in Mosaic's final
    LLO, by kind; compiles for a described v5e, runs nothing."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    k, n = SHAPES[shape]

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt, sharding=one)
    x, q = sds((16, k), BF16), sds((L, k // 2, n), jnp.uint8)
    s, lyr = sds((L, k // QK, n), F32), sds((1,), jnp.int32)
    dump = os.environ["EXP_MOSAIC_DUMP"]
    for f in glob.glob(os.path.join(dump, "*")):
        os.remove(f)
    t0 = time.time()
    prep, run = variant_call(name, 16, k, n)
    jax.jit(lambda x, q, s, lyr: run(prep(x), q, s, lyr)).lower(
        x, q, s, lyr).compile()
    secs = time.time() - t0
    files = sorted(glob.glob(os.path.join(dump, "*post-finalize-llo*")))
    if not files:
        return {"compile_s": round(secs, 1), "error": "no LLO dump"}
    ops = collections.Counter()
    with open(files[0]) as f:       # the first K chunk's kernel
        for line in f:
            hit = re.search(r'"?llo\.([a-z0-9_.]+)', line)
            if hit:
                ops[hit.group(1)] += 1
    by = {"valu": 0, "load": 0, "store": 0, "mxu": 0}
    for op, c in ops.items():
        if not op.startswith("v"):
            continue
        for slot, names in _SLOTS.items():
            if op in names:
                if slot != "free":
                    by[slot] += c
                break
        else:
            by["valu"] += c
    return {"compile_s": round(secs, 1), "kernels": len(files), **by,
            "top": dict(ops.most_common(14))}


# -- timing, on the chip --------------------------------------------------

def mk_stack(key, k, n):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.randint(k1, (L, k // 2, n), 0, 256, jnp.uint8)
    s = jax.random.uniform(k2, (L, k // QK, n), F32, 0.001, 0.02)
    sign = jnp.where(jax.random.bernoulli(k3, 0.5, s.shape), 1.0, -1.0)
    return q, s * sign


def slope(run, ops, q, scale, iters, vary=False):
    """Per-call device time of ``run(ops, q, scale, layer)``: slope of a
    fori_loop between iters/4 and iters, best of 3, the layer walking
    the stack. ``vary``: the operands rolled by a row every iteration
    (XLA hoists work on loop-invariant activations out of the loop:
    the split ahead of the parent's kernel among it), so that what XLA
    does to x runs once a call, behind a producer, as in the model."""
    def loop_for(n_it):
        @jax.jit
        def loop(ops, q, scale):
            def body(i, carry):
                acc, ops = carry
                lyr = (i % L).astype(jnp.int32).reshape(1)
                acc = acc + run(ops, q, scale, lyr)[:, :128].sum()
                if vary:
                    ops = jax.tree.map(lambda a: jnp.roll(a, 1, 0), ops)
                return acc, ops
            return jax.lax.fori_loop(0, n_it, body, (F32(0), ops))[0]
        return loop
    pts = []
    for n_it in (iters // 4, iters):
        loop = loop_for(n_it)
        float(loop(ops, q, scale))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            float(loop(ops, q, scale))
            best = min(best, time.perf_counter() - t0)
        pts.append((n_it, best))
    (a1, b1), (a2, b2) = pts
    return (b2 - b1) / (a2 - a1)


def reference(x, q, scale, layer):
    """float32 dequant of layer ``layer`` and the product, in XLA."""
    ql = q[layer].astype(jnp.int32)
    s = jnp.repeat(scale[layer], HALF, axis=0)
    k = x.shape[1]
    w = jnp.zeros((k, q.shape[2]), F32)
    w = w.at[0::2].set(((ql & 0xF) - 8).astype(F32) * s)
    w = w.at[1::2].set(((ql >> 4) - 8).astype(F32) * s)
    return jnp.dot(x.astype(F32), w, precision="highest")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--count", action="store_true")
    ap.add_argument("--variants", default="dma;pr29;noscale;nocorr;e_in;"
                    "s1;s12;s123;new;new:slab=256;new:slab=1024;"
                    "new:slab=4096;new:bn=512;new:bn=512,slab=256")
    ap.add_argument("--shapes", default="gate_up,down")
    ap.add_argument("--iters", type=int, default=400)
    ap.add_argument("--m", type=int, default=16,
                    help="rows of x; only new / parent take m > 16")
    ap.add_argument("--vary", action="store_true",
                    help="roll the operands a row every iteration")
    ap.add_argument("--out", default="exp_int4_body.json")
    args = ap.parse_args()
    names = args.variants.split(";")
    shapes = args.shapes.split(",")
    out = {}
    if args.count:
        for shape in shapes:
            for name in names:
                try:
                    out[f"{shape}.{name}"] = count_ops(name, shape)
                except Exception as e:       # a body Mosaic refuses
                    out[f"{shape}.{name}"] = {"error": str(e)[-400:]}
                print(shape, name, json.dumps(out[f"{shape}.{name}"]),
                      flush=True)
        return
    assert jax.default_backend() == "tpu", jax.default_backend()
    key = jax.random.PRNGKey(0)
    for shape in shapes:
        k, n = SHAPES[shape]
        q, s = mk_stack(key, k, n)
        x = jax.random.normal(jax.random.PRNGKey(1), (args.m, k), F32) \
            .astype(BF16)
        ref = reference(x, q, s, 1)
        nbytes = k // 2 * n + k // QK * n * 4
        floor_us = nbytes / 819e9 * 1e6
        for name in names:
            lyr = jnp.ones((1,), jnp.int32)
            try:
                prep, run = variant_call(name, args.m, k, n)
                ops = jax.jit(prep)(x)
                got = jax.jit(run)(ops, q, s, lyr)
                err = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
                us = slope(run, ops, q, s, args.iters, args.vary) * 1e6
                res = {"us": round(us, 2), "roofline_pct":
                       round(100 * floor_us / us, 1),
                       "err_vs_f32": round(err, 5)}
            except Exception as e:
                res = {"error": str(e)[-400:]}
            out[f"{shape}.{name}"] = res
            print(shape, name, json.dumps(res), flush=True)
        out[f"{shape}.floor_us"] = round(floor_us, 2)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", args.out), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
