"""INT4/INT8 block-dequant matmul Pallas kernels.

Reference counterpart: bigdl-llm's native q4_0 matvec (ctypes →
llama.cpp-family C kernels, SURVEY.md §3.4 hot loop). TPU design:

- weights stream packed from HBM (uint8, 2 nibbles/byte) — 4.5 bits/
  weight including scales, ~3.5x less HBM traffic than bf16. Decode is
  HBM-bandwidth-bound, so this is where the speed comes from (same
  reason the reference's CPU kernels win on DDR bandwidth).
- **k-major "TPU layout"**: packed weights are stored (K/2, N) and
  scales (K/QK, N) — transposed once at load by :func:`to_tpu_layout` —
  so the kernel's dequantized tile feeds ``jnp.dot`` directly with no
  in-register transpose, and every BlockSpec dim is either 128-aligned
  or the full array dim (the r2 kernel's (bn, bk//QK) scale block
  violated Pallas's last-dim rule and never lowered on real TPU).
- the per-32-group scale is broadcast on the sublanes: a group's
  float32 scale row is replicated over the 16 sublanes its packed rows
  occupy (a sublane replicate and a rotate a vreg), so the expansion
  costs no matmul and the scale keeps its float32 bits. (Until PR 30 an expansion matrix E (K/2, G) was
  rebuilt from two iotas every grid step and ``E @ bf16(scales)`` ran
  on the MXU: as long as the block's own HBM time, see the 2 Oct 2026
  entry. The naive whole-block reshape-broadcast measured 3x slower
  than that on the earlier runtime.)
- every weight enters the MXU as ``bf16(q * scale)``, the product taken
  in float32 and rounded once; K is walked in slabs of ``_SLAB`` packed
  rows with the float32 accumulator carried.
- the q4_0 zero-point (-8) is algebraic, not elementwise:
  sum_k x_k*(q-8)*s = sum_k x_k*q*s - 8*sum_g (sum_{k in g} x_k)*s[g]
  so decode (m small) folds it into one skinny float32 product of the
  group sums of x, taken once a row tile in VMEM beside the even/odd
  k-planes (ISSUE 38: both were XLA fusions ahead of every call), with
  the scale block as it arrives; prefill (m large, MXU-bound) subtracts 8
  on the VPU before the product instead.
- float16 never enters the kernel: this Mosaic build cannot load fp16
  (verified on chip before PR 1: "Unsupported cast"-class compile
  failures; not re-checked under jax 0.9),
  so ggml's fp16 scales are converted to f32 on the host.

Measured on TPU v5 lite (1 chip, 819 GB/s HBM), (1, 4096)x(4096, 11008)
Llama-2-7B decode matvec: ~130 us — parity with XLA's dense bf16 matvec
(~122 us, which runs at the full 740 GB/s HBM rate) while streaming
3.2x fewer bytes. (That paragraph's account, "bounded by the VPU's
dequant, ~7 ops/packed byte", did not survive PR 30's measurement: the
PR 29 body issued 12,585 vector-ALU ops a (2048, 256) grid step, 2.4
times what this one needs, and was bound by its MXU work, not by them;
see the 2 Oct 2026 entry.) Alternatives measured and rejected on chip: VPU-only
matvec (no MXU) 174 us; scale expansion via in-kernel expansion-matrix
matmul vs pltpu.repeat — identical; int8 MXU dots offer no rate gain on
this toolchain (1.09x), closing the W4A8 route. The win int4 keeps:
4x less HBM *footprint* (7B fits comfortably beside its KV cache) and
4x less HBM traffic, which turns into throughput wherever the batch
dimension (m >= 16) lifts the compute floor — batched decode and
prefill — and on bandwidth-richer TPUs.

Round-4 additions to the measured-alternatives ledger (all on the same
v5e, 7B decode shapes, m=1): (a) fusing q/k/v and gate/up into single
kernel calls (7 → 4 launches/layer) is perf-neutral within the ~20%
tenancy noise — per-launch overhead is NOT a bottleneck on this
runtime; (b) unrolling the 32-layer scan is strictly worse (unroll=8:
-27%; full python-loop: -18%) — the rolled scan pipelines the weight
stream best; (c) bf16 scale storage is SLOWER than f32 (140 vs 115 us
micro) despite 12% fewer bytes — the f32 DMA pipelines better and the
kernel casts scales to bf16 in-register either way; (d) bn=512 blocks
exceed the 16M scoped-vmem limit at full-K chunks (no longer: with the
slab walk a (4096, 512) block compiles at bm = 128, and bn = 512 is the
default where it divides N, 2 Oct 2026 entry). The in-context
matmul-only decode floor is ~0.88 ms/layer (34.9 tok/s for 7B) — the
per-layer cost in a live scan runs ~40% above the lone-kernel micro
because consecutive distinct kernels cannot share the double-buffered
stream an identical-kernel micro loop enjoys.

Round-5 ledger entry (closes VERDICT r4 weak #3 / next-round item 5):
the proposed per-layer **megakernel** (qkv+o+gate/up+down sharing one
double-buffered weight stream) is REFUTED by direct measurement
(tools/exp_stream_sharing.py, on-chip fori-loop slope harness, 500-iter
pairs): a loop alternating the two largest distinct-shape matvecs costs
**1.012×** the sum of their individual slope times, and the full
4-matvec dependency chain (qkv→o→gate_up→down, the live layer minus
norm/rope/attention) costs **1.019×** the 4-kernel sum (669 → 682
µs/layer). Kernel-to-kernel transitions therefore lose ~2%, not the
~40% the r4 ledger hypothesized — a fused megakernel's maximum recovery
is ~13 µs/layer ≈ 0.4 tok/s at 7B. The remaining b1 gap
(~0.35 ms/layer between the 0.68 ms matmul chain and the ~1.0 ms live
layer) sits in the non-matmul work (rms_norm, rope, cache attention,
scan plumbing) — small latency-bound VPU ops, not weight streaming.
Measured slopes for the record: qkv 124.7 µs, gate_up 220.5, o 223.5,
down 100.6, alt 349.4, chain 682.0. Per-shape micros show large
run-to-run swings beyond the 20% tenancy band on the small shapes
(o measured 71/155/223 µs across three sessions; a qkv bn=512 micro
read 977 GB/s packed — above HBM spec, i.e. an artifact), so the
tile-size question was settled END-TO-END instead: interleaved A/B of
the full b1 7B decode bench with DEFAULT_BN 256 vs 512 (2 reps each)
measured 29.83/29.83 vs 29.87/29.77 tok/s — dead even. bn stays 256;
b1 decode is not kernel-tile-bound.

1 Oct 2026 ledger entry (ISSUE 28, PERF.md §5-6; v5e, Mistral-7B
shapes, m = 16, the served cell traced): the **stacked form**. A model
holds each linear as one ``(L, K/2, N)`` / ``(L, K/QK, N)`` stack and
walks it in a rolled scan; a ``stack[l]`` slice that feeds this kernel
is an operand of a Mosaic call, which XLA cannot fuse into, so it was a
real copy of every layer's packed weights: 7.3 ms of a 28.0 ms decode
step (``dynamic-slice_bitcast_fusion[2048x28672]`` 2.53,
``[7168x4096]`` 1.28 and nine smaller ones, the second slice of
``down_proj``'s two K chunks among them) and as much of every prefill.
``int4_matmul`` now takes the stack and a traced ``layer``: the index
is a scalar-prefetch operand, the weight and scale blocks are
``(None, half, bn)`` / ``(None, g, bn)`` at ``(layer, c, j)`` and a K
chunk is the block index ``c``, not a slice. What the chip said: the
slices are gone from the trace (``kvcache.write.weight_slices`` finds
0 in every engine program, 8-9 in the parent's), the step is 19.4 ms,
and the kernel's OWN time fell too, 16.79 -> 15.97 ms a step (gate_up
9.33 -> 8.77, o + down 5.46 -> 5.27, qkv 2.01 -> 1.93; 31.7 -> 33.3 %
of its roofline) with the same tiles and bytes: it now streams from
the parameter buffer and not from a temporary written a moment
before. Not done: Llama-2's ``down_proj`` (K = 11,008: two chunks
of g = 172, not 8-aligned) keeps the sliced 2-D path; a full-K block
at bn = 128 would need its VMEM measured first.

2 Oct 2026 ledger entry (ISSUE 30, PERF.md §5-6; v5e, Mistral-7B
shapes, stacked form, m = 16 unless said; ``tools/exp_int4_body.py``,
slope of a 400-iteration ``fori_loop``, us a call; "ops" = vector-ALU
operations of one gate_up grid step in Mosaic's final LLO, counted
with no chip by ``--count``). **Step 0, where a grid step's 2.45 us
went.** gate_up ``(16, 4096) x (4096, 28672)``, 112 steps, 73.4 MB,
89.6 us at 819 GB/s | down ``(16, 14336) x (14336, 4096)``, 2 x 16
steps, 44.8 us:

    body                                       ops   gate_up  down
    dma      block DMA, trivial body            32    101.6   53.0
    pr29     the body as it was             12,585    277.9  126.8
    noscale  widen/mask/shift/convert+dots   3,808    129.7   62.3
    nocorr   pr29 - the correction dot      11,633    234.4  114.9
    e_in     pr29, E handed in               9,000    279.9  116.3
    s1       float32 scale, round once       9,769    279.3  120.8
    s12      s1 + E in + group sums          5,770    221.9  109.3
    s123     s12 in slabs, E per slab        5,595    271.7  135.6
    new      this file                       5,276    145.8   72.8
    new, slabs of 256 | 1024 | whole K           -    144.9 | 144.6 | 144.0
    new, bn = 512 | 1024                         -    132.2 | 126.5  72.1 | -
    new - correction | one plane | no scale      -    144.0 | 127.5 | 133.5

The block DMA alone runs at 88 / 85 % of the roofline, so the blocks
were never the problem. **The body was MXU-bound, not VPU-bound**:
taking 3,600 ALU ops out (e_in) or the bf16 round trips out (s1) moved
nothing, while each MXU operand did: the expansion ``E @ scales`` is a
(2048, 128) x (128, 256) product, 0.68 us at the MXU's peak against
0.70 us of HBM for the block it serves (s12 -> new: -76 us), and the
correction's third (half, bn) weight operand cost 43 us a call. What
each of the issue's steps gave alone: 1 (float32, round once) nothing;
2 (constants once) nothing for E itself, -58 us for the group sums;
3 (slabs) nothing at any slab size, and with the expansion as a
per-slab K = 32 matmul it LOST 50 us. What worked was taking the
expansion off the MXU altogether: a (G, 1, bn) -> (G, 16, bn) broadcast
of the scale rows, reshaped to (16 G, bn), lowers to a sublane
replicate and a rotate (384 ops a step against the E product's 512 pops
+ 512 adds + 256 pushes), and the scale stays float32, one rounding
fewer. (Written as a concatenate of per-group ``broadcast_to(
scale_ref[g:g+1], (16, bn))`` it lowers to strided loads, 136 ALU ops
fewer, the timings of the table; but 32 loads a slab and four to seven
slabs a kernel made the 32 kernel traces of the engine's eight programs
take 5.7 s against 1.4 s, +3.3 s of ``setup_s`` in six pairs, and the
CPU tests' programs map four times the memory regions. The one-op form
with slabs of 1,024 rows traces in 1.6-2.2 s.) Not available in this Mosaic:
reading the packed tile as int32 words (8 nibble planes from shifts
and masks, no widening unpack: 4,350 ops counted) because a uint8
block is tiled (8,128)(4,1) in VMEM, so its word view is (2,128)-tiled
and every use pays a sublane shuffle (12,379 ops counted); three-deep
buffering (``pl.Buffered(3)`` is refused by ``pallas_call``). The rest
of the gap (146 against the DMA's 102) is the ALU work that is left,
5,276 ops = 1,320 cycles at four slots against a 1,050-cycle block,
and a fixed ~0.25 us a grid step, which bn = 512 pays half as often.
Other shapes, pr29 | new | new bn = 512: qkv 62.1 | 32.9 | 31.5, o
43.0 | 23.3 | 23.6; prefill (``sub8``, bm = 128) m = 512: gate_up
1,451 | 727 | 677, down 659 | 350 | 333, qkv 328 | 175 | 157, o 219 |
118 | 105; m = 2,048: gate_up 5,801 | 3,064 | 2,741, down 2,600 |
1,373 | 1,307. Error against the float32 product (max / max): 0.0050 ->
0.0033 at m = 16, 0.0024 -> 0.0017 at m = 512. **In the served cell**
(traced pair, seed 2444444447, P C C P): the kernel 16.31 -> 8.03 ms a
decode step (gate_up 8.79 -> 4.13, o + down 5.27 -> 2.82, qkv 1.93 ->
0.94, the head 0.33 -> 0.15), ``int4_decode_roofline`` 33.2 -> 67.5 %,
the step 19.58 -> 11.34 ms, ``prefill_dev_tok_s`` 3,356 -> 6,046
(the per-group-load form; the one-op form this file has, traced P C:
the step 19.57 -> 11.19 ms, 33.2 -> 68.8 %, 3,354 -> 5,910), and
``itl_p95_ms`` 25.08 -> 13.48 ms over nine same-seed pairs.

15 Oct 2026 ledger entry (ISSUE 38, PERF.md §5-6; v5e, Mistral-7B
shapes, stacked form, bn = 512, ``tools/exp_int4_body.py``): **the
kernel takes its activations whole.** Until now XLA split each K chunk
of x into its even and odd k-planes (``x[:, 0::2]``, ``x[:, 1::2]``, a
stride-2 lane gather) and took the group sums ahead of every call:
``fusion[2048x16]`` + ``fusion[3584x16]`` 1.45 ms of an 11.27 ms
decode step, 7.9-10.8 us a call for 128-224 KB (traced pair, seed
3800000011). The x operand is now one ``(bm, kc)`` block of x at
``(i, chunk)`` (no slice for ``down_proj``'s chunks either), and at
the first N tile of a row tile the kernel builds both planes and the
group sums in VMEM (:func:`_deinterleave`), the N axis "arbitrary".
**Step 0**, us a call at m = 16; "--vary" rolls x a row each
iteration, without which XLA hoists the parent's split out of the
timed loop (first column: so the parent there is its kernel alone):

    form                                 gate_up  down   qkv    o
    parent, split hoisted (no --vary)     129.9   71.1   31.8  23.0
    parent, XLA split + sums (--vary)     138.1  109.9   39.6  29.5
      the same, sums zeroed (")           136.9   93.7   38.4  29.8
    no split at all (the floor, ")        128.3   66.3   28.7  20.6
    blocks stacked by two loops (")       128.3   68.7   30.1  21.3
    blocks stacked by a reshape (kept)    128.2   68.7   29.8  20.9
    one product a block (no --vary)       133.5   84.0   35.0  26.5
    x transposed, sublane stride 2 (")    133.0   81.2   33.6  25.0
    lane-strided load                      does not lower

(the two no-``--vary`` forms beside the loop form's 131.7 / 79.1 / 32.9
/ 24.8 in that first harness; the lane-strided load: "Strided load
with non 32-bit data", and in float32 "The last dim size is not 128";
the transposed form ran out of scoped VMEM at m = 512 for ``down``).
At m = 512 (``sub8``, bm = 128, --vary) gate_up parent | loops |
reshape 768.8 | 709.3 | 713.4, down 451.0 | 384.3 | 402.0. The split
costs the kernel 0-2.4 us a call against 9-44 us of XLA's (``down``'s
two 7,168-lane chunks paid most: a slice, then the gather, then a
reduce, three fusions a chunk). What the selection product does is
exact: one 1.0 a column, bf16 operands, float32 accumulation; a -0.0
comes out +0.0. **Set-up is part of the cost**: every program that
holds the kernel lowers it, and on the chip machine a lowering's time
swings with the Python stack depth it starts at (six kernels at 20
depths: 135-1,011 ms, mean 241-244, for the parent; 166-1,045, mean
304-306, with the loops; 162-1,054, mean 276, with the reshape, which
nests no region under the ``pl.when``). The loop form read +3.5-4.5 s
of warm ``setup_s`` in the cell (its warm-up 16.3-17.4 s against
12.7-13.6), hence the reshape. Index arithmetic is shifts and masks:
``//``, ``%`` and ``jnp.where`` lowered as 43 nested jitted functions
a call, through ``sign``. The reshape's relayout grows with the row
tile: scoped VMEM is raised to 32 MiB, and a (128, 7,168) chunk
compiles in 3-4 s against 1.6 (a cold compile only).
**In the served cell** (the loop form, which runs the same products;
parent | change, traced seeds 3800000011 and 3800000101): the two
fusions gone, and with them the group sums' reduces and
``down_proj``'s chunk slices; the kernel 7.87 | 8.00 ms a step, the
step 11.27 | 9.22 and 11.38 | 9.26 ms, ``int4_decode_roofline`` 68.90
| 67.79 and 68.79 | 67.86 %, ``prefill_dev_tok_s`` 6,293 | 6,672 and
6,520 | 6,944; ``itl_p95_ms`` over four same-seed pairs 13.30, 13.51,
13.69, 13.25 | 10.95, 11.02, 11.69, 11.04 (PERF.md §6). The reshape
form: the step 11.38 | 9.29 ms, 68.79 | 67.59 %, 6,520 | 6,807 tokens/s
(seed 3800000101), ``itl_p95_ms`` 13.30, 13.24 | 10.88, 10.98,
``setup_s`` 37.81, 38.99 | 38.39.

``interpret=True`` runs the same kernel on CPU for tests (SURVEY.md §4:
golden parity against an independent implementation — here the numpy
dequant reference).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bigdl_tpu.llm.ggml.quantize import QK

HALF = QK // 2          # scale-group size within one nibble plane
_MAX_BK = 8192          # K above this is chunked to bound VMEM
                        # (K=11008 at bm=128 overflowed the 16M scoped
                        # vmem limit on chip with full-K blocks)


def _align_bm(bm: int, m: int) -> int:
    """Round the M tile up to a 16-aligned shape: Mosaic rejects
    non-8/16-aligned second-minor block dims, so bm must be a tile
    multiple even when 16 < m < 128 (e.g. m=100 -> bm=112, pad M)."""
    return min(bm, max(16, -(-m // 16) * 16))


def _scale_expand(scale_ref, half: int, cdt):
    """(G, bn) group scales → (half, bn) per-row scales via an MXU matmul
    against an iota-built expansion matrix (no VPU relayout)."""
    g = half // HALF
    sc = scale_ref[:].astype(cdt)
    row = jax.lax.broadcasted_iota(jnp.int32, (half, g), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (half, g), 1)
    e = jnp.where(row // HALF == col, 1.0, 0.0).astype(cdt)
    return jnp.dot(e, sc, preferred_element_type=jnp.float32).astype(cdt)


_SLAB = 1024            # packed rows a slab: 64 scale groups, eight
                        # 128-deep MXU passes a nibble plane. Bounds
                        # the float32 intermediates (what lets a
                        # (4096, 512) block fit scoped VMEM); the time
                        # is the same from 256 rows to the whole block,
                        # and every slab is traced, so not smaller


_SEL = 256              # lanes of x one selection product de-interleaves
_VMEM_LIMIT = 32 << 20  # scoped VMEM: the split's stacked blocks and
                        # products beside the double-buffered blocks (a
                        # (128, 7,168) chunk in ``corr`` needs 16.06 MB,
                        # over the default 16; the decode kernels' code
                        # is the same either way)


def _selection(rows: int, dtype):
    """(rows, 256) 0/1: column j < 128 picks lane 2j of a block of x,
    column 128 + j lane 2j + 1 (a last block of fewer than 256 lanes
    leaves the columns past its own half zero)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, _SEL), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, _SEL), 1)
    return (r == ((c & 127) << 1) + (c >> 7)).astype(dtype)


def _grouping(rows: int, g: int, first, dtype):
    """(rows, g) 0/1: lane k of a block of x counts towards group
    ``first + k // QK``, or, where ``first`` is None, towards every
    group whose index is ``k // QK`` modulo ``_SEL // QK`` (the blocks
    stacked on the sublanes share one matrix; a mask keeps each row
    block's own groups)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, g), 0) >> 5    # // QK
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, g), 1)
    c = c & 7 if first is None else c - first              # % (_SEL // QK)
    return (r == c).astype(dtype)


def _split_scratch(bm: int, kc: int, sub8: bool):
    """VMEM of :func:`_deinterleave`: the two k-planes and, where the
    zero-point is folded (``corr``), the group sums."""
    out = [pltpu.VMEM((bm, kc // 2), jnp.bfloat16)] * 2
    if not sub8:
        out.append(pltpu.VMEM((bm, kc // QK), jnp.float32))
    return out


def _deinterleave(x_ref, xe_ref, xo_ref, xs_ref, *, cdt):
    """``x_ref`` (bm, kc) -> its even k-plane in ``xe_ref`` and its odd
    one in ``xo_ref`` (bm, kc/2), in VMEM, and (``xs_ref`` not None)
    its float32 sums over each scale group in ``xs_ref`` (bm, kc/QK):
    what the folded zero-point needs.

    Each 256-lane block of x times :func:`_selection` is the block's 128
    even lanes then its 128 odd ones: one 1.0 a column, the product
    accumulated in float32, so each number comes out as it went in.
    The blocks are first stacked on the sublanes, ``(bm, nb, 256) ->
    (nb, bm, 256)`` (Mosaic moves the vregs), so that ONE product takes
    them all and the MXU loads the selection once, not once a block;
    the result is unstacked the same way into the planes. The group
    sums are a second product of the same stack, by :func:`_grouping`:
    each block's own eight groups kept by a mask and the blocks added,
    one non-zero term each. A last block of fewer than 256 lanes (kc =
    5,504, or a single chunk of 224) takes products of its own. No
    loop: every region nested under the kernel's ``pl.when`` costs
    lowering time in each program that holds the kernel (ISSUE 38)."""
    bm, kc = x_ref.shape
    nb, tail = divmod(kc, _SEL)
    g = kc // QK
    if nb:
        s = x_ref[:, :nb * _SEL].reshape(bm, nb, _SEL).swapaxes(0, 1) \
            .reshape(nb * bm, _SEL).astype(cdt)
        if xs_ref is not None:
            y = jnp.dot(s, _grouping(_SEL, g, None, cdt),
                        preferred_element_type=jnp.float32
                        ).reshape(nb, bm, g)
            own = (jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
                   == jax.lax.broadcasted_iota(jnp.int32, y.shape, 2) >> 3)
            xs_ref[...] = jax.lax.reduce_sum(
                jax.lax.select(own, y, jnp.zeros_like(y)), (0,))
        r = jnp.dot(s, _selection(_SEL, cdt),
                    preferred_element_type=jnp.float32
                    ).astype(xe_ref.dtype).reshape(nb, bm, _SEL)
        xe_ref[:, :nb * 128] = r[:, :, :128].swapaxes(0, 1) \
            .reshape(bm, nb * 128)
        xo_ref[:, :nb * 128] = r[:, :, 128:].swapaxes(0, 1) \
            .reshape(bm, nb * 128)
    if tail:
        t = x_ref[:, nb * _SEL:].astype(cdt)
        r = jnp.dot(t, _selection(tail, cdt),
                    preferred_element_type=jnp.float32).astype(xe_ref.dtype)
        xe_ref[:, nb * 128:] = r[:, :tail // 2]
        xo_ref[:, nb * 128:] = r[:, 128:128 + tail // 2]
        if xs_ref is not None:
            part = jnp.dot(t, _grouping(tail, g, nb * _SEL // QK, cdt),
                           preferred_element_type=jnp.float32)
            xs_ref[...] = xs_ref[...] + part if nb else part


def _x_inputs(x, bm: int, k0: int, kc: int):
    """The activations the kernel takes for the K chunk at ``k0`` and
    its BlockSpec (the index map takes the stacked form's
    scalar-prefetch operand too): x itself, whose block ``(i, k0 /
    kc)`` IS the chunk where the chunks tile K (a lane dim of whole
    128s, or the whole K), else the chunk's slice."""
    if k0 % kc == 0 and (kc % 128 == 0 or kc == x.shape[1]):
        return x, pl.BlockSpec((bm, kc),
                               lambda i, j, *_, c=k0 // kc: (i, c))
    return x[:, k0:k0 + kc], pl.BlockSpec((bm, kc), lambda i, j, *_: (i, 0))


def _int4_kernel(x_ref, *refs, sub8: bool, cdt=jnp.bfloat16):
    """One (bm, bn) output tile.

    x: (bm, kc) activations, whole; q: (K/2, bn) packed uint8 (low
    nibble = even k, high = odd k); scale: (G, bn) float32; then the
    scratch of :func:`_split_scratch`. ``cdt`` is the MXU operand dtype
    (f32 under interpret: the CPU thunk cannot execute bf16 x bf16
    dots).

    The even and odd k-planes of x and, for ``corr``, its group sums
    are built in VMEM (:func:`_deinterleave`) at the first N tile of a
    row tile and serve every other: the N axis is "arbitrary", walked
    in order (v5e has one TensorCore, so nothing runs in parallel that
    could).

    K is walked in slabs of ``_SLAB`` packed rows (the last may be
    shorter) with the float32 accumulator carried, so a slab's chain
    unpack -> scale -> dot is all that is live. A group's float32 scale
    row is replicated over the 16 sublanes of its packed rows (Mosaic
    lowers the broadcast + reshape to a sublane replicate and a
    rotate: no expansion matmul, nothing rebuilt per grid step) and
    every weight is ``cdt(q * scale)``: the product taken in float32,
    rounded ONCE."""
    q_ref, scale_ref, o_ref, xe_ref, xo_ref, *scratch = refs
    xs_ref = None if sub8 else scratch.pop(0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        _deinterleave(x_ref, xe_ref, xo_ref, xs_ref, *scratch, cdt=cdt)

    if sub8:
        acc = jnp.zeros(o_ref.shape, jnp.float32)
    else:
        # the q4_0 zero-point: -8 * sum_g (sum_{k in g} x_k) * s[g],
        # float32 sums against float32 scales
        acc = -8.0 * jnp.dot(xs_ref[:], scale_ref[:],
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
    half, bn = q_ref.shape
    for r0 in range(0, half, _SLAB):
        rows = min(_SLAB, half - r0)
        q = q_ref[r0:r0 + rows, :].astype(jnp.int32)
        sc = scale_ref[r0 // HALF:(r0 + rows) // HALF, :]
        s_exp = jnp.broadcast_to(sc[:, None, :], (rows // HALF, HALF, bn)) \
            .reshape(rows, bn)
        lo, hi = q & 0xF, q >> 4
        if sub8:
            lo, hi = lo - 8, hi - 8
        acc += jnp.dot(xe_ref[:, r0:r0 + rows].astype(cdt),
                       (lo.astype(jnp.float32) * s_exp).astype(cdt),
                       preferred_element_type=jnp.float32)
        acc += jnp.dot(xo_ref[:, r0:r0 + rows].astype(cdt),
                       (hi.astype(jnp.float32) * s_exp).astype(cdt),
                       preferred_element_type=jnp.float32)
    o_ref[:] = acc.astype(o_ref.dtype)


def _int4_stacked_kernel(layer_ref, *refs, **kw):
    """:func:`_int4_kernel` behind a scalar-prefetch operand: the layer
    index is spent in the BlockSpecs' index maps, the body never reads
    it."""
    del layer_ref
    _int4_kernel(*refs, **kw)


def _asym_int4_kernel(xe_ref, xo_ref, q_ref, scale_ref, zero_ref, o_ref,
                      *, cdt=jnp.bfloat16):
    """q4_1: w = q * scale + zero (zero = per-group minimum)."""
    q = q_ref[:].astype(jnp.int32)
    half, _ = q.shape
    s_exp = _scale_expand(scale_ref, half, cdt)
    z_exp = _scale_expand(zero_ref, half, cdt)
    lo = (q & 0xF).astype(cdt) * s_exp
    hi = (q >> 4).astype(cdt) * s_exp
    xe = xe_ref[:].astype(cdt)
    xo = xo_ref[:].astype(cdt)
    acc = jnp.dot(xe, lo, preferred_element_type=jnp.float32)
    acc += jnp.dot(xo, hi, preferred_element_type=jnp.float32)
    acc += jnp.dot(xe + xo, z_exp, preferred_element_type=jnp.float32)
    o_ref[:] = acc.astype(o_ref.dtype)


def _int8_kernel(x_ref, q_ref, scale_ref, o_ref, *, cdt=jnp.bfloat16):
    """q8_0: w = q * scale, q int8 (K, bn) — unpack-free stream."""
    q = q_ref[:].astype(jnp.int32)
    k, _ = q.shape
    g = k // QK
    sc = scale_ref[:].astype(cdt)
    row = jax.lax.broadcasted_iota(jnp.int32, (k, g), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (k, g), 1)
    e = jnp.where(row // QK == col, 1.0, 0.0).astype(cdt)
    s_exp = jnp.dot(e, sc, preferred_element_type=jnp.float32).astype(cdt)
    w = q.astype(cdt) * s_exp
    o_ref[:] = jnp.dot(x_ref[:].astype(cdt), w,
                       preferred_element_type=jnp.float32) \
        .astype(o_ref.dtype)


def _pad_nk(q_t, scale_t, bn, pad_byte, extras=()):
    n = q_t.shape[1]
    n_pad = -n % bn
    if n_pad:
        q_t = jnp.pad(q_t, ((0, 0), (0, n_pad)), constant_values=pad_byte)
        scale_t = jnp.pad(scale_t, ((0, 0), (0, n_pad)))
        extras = tuple(jnp.pad(z, ((0, 0), (0, n_pad))) for z in extras)
    return (q_t, scale_t) + extras


def _chunk_k(k: int):
    """Split K into <= _MAX_BK chunks (each a multiple of QK)."""
    if k <= _MAX_BK:
        return [(0, k)]
    n_chunks = -(-k // _MAX_BK)
    per = -(-k // (n_chunks * QK)) * QK
    out, s = [], 0
    while s < k:
        out.append((s, min(per, k - s)))
        s += per
    return out


# the narrow N tile; module-level so A/B harnesses can flip it globally
DEFAULT_BN = 256


def _default_bn(n: int) -> int:
    """The N tile where the caller names none: ``2 * DEFAULT_BN`` where
    that divides N, else ``DEFAULT_BN``. Since the body walks K in
    slabs a (K/2, 512) block fits scoped VMEM at every chunk size, and
    a grid step's fixed cost (~0.25 us) is paid half as often: gate_up
    at m = 16 146 -> 132 us, every prefill shape 5-12 % faster, o and
    down at m = 16 unchanged (header ledger, 2 Oct 2026). Padding N up
    to 512 would copy the weights, so a head of 32,000 keeps 256."""
    return 2 * DEFAULT_BN if n % (2 * DEFAULT_BN) == 0 else DEFAULT_BN


def int4_matmul(x, q_t, scale_t, bm: int = 128, bn: Optional[int] = None,
                interpret: bool = False, out_dtype=jnp.bfloat16,
                mode: str = "auto", layer=None):
    """y = x @ dequant_q4_0(q, scale) in TPU layout.

    x: (M, K) activations; q_t: (K/2, N) packed uint8 (low nibble =
    even k); scale_t: (K/QK, N) float32 (fp16 accepted, converted).
    ``mode``: "corr" folds the -8 zero-point into an extra skinny dot
    (best for decode), "sub8" subtracts on the VPU (best for prefill),
    "auto" picks by M. ``bn=None`` resolves :func:`_default_bn` HERE,
    outside the jit, so flipping the module default retraces.

    **Stacked form**: q_t ``(L, K/2, N)`` and scale_t ``(L, K/QK, N)``
    with ``layer`` a (traced) int32 — the rank of ``q_t`` tells the two
    apart. The kernel reads layer ``layer`` out of the whole stack in
    place (:func:`_int4_matmul_stacked_jit`); a ``q_t[layer]`` slice
    handed to a Mosaic call is a copy of the layer's weights."""
    bn = bn if bn is not None else _default_bn(q_t.shape[-1])
    if q_t.ndim == 3:
        return _int4_matmul_stacked_jit(
            x, q_t, scale_t, jnp.asarray(layer, jnp.int32).reshape(1),
            bm=bm, bn=bn, interpret=interpret, out_dtype=out_dtype,
            mode=mode)
    return _int4_matmul_jit(x, q_t, scale_t, bm=bm, bn=bn,
                            interpret=interpret, out_dtype=out_dtype,
                            mode=mode)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "out_dtype", "mode"))
def _int4_matmul_jit(x, q_t, scale_t, bm: int, bn: int,
                     interpret: bool, out_dtype, mode: str):
    m, k = x.shape
    n = q_t.shape[1]
    if q_t.shape[0] * 2 != k:
        raise ValueError(
            f"q_t {q_t.shape} is not the (K/2, N) TPU layout for K={k}; "
            "convert ggml (N, K/2) dicts with to_tpu_layout() first")
    sub8 = (m >= 256) if mode == "auto" else (mode == "sub8")
    scale_t = scale_t.astype(jnp.float32)
    bm = _align_bm(bm, m)
    m_pad = -m % bm
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    q_t, scale_t = _pad_nk(q_t, scale_t, bn, 0x88)
    mp, np_ = x.shape[0], q_t.shape[1]
    x = x.astype(jnp.bfloat16)

    out = None
    for k0, kc in _chunk_k(k):
        qc = q_t[k0 // 2:(k0 + kc) // 2]
        sc = scale_t[k0 // QK:(k0 + kc) // QK]
        half, g = kc // 2, kc // QK
        xc, xspec = _x_inputs(x, bm, k0, kc)
        part = pl.pallas_call(
            functools.partial(_int4_kernel, sub8=sub8,
                              cdt=jnp.float32 if interpret
                              else jnp.bfloat16),
            grid=(mp // bm, np_ // bn),
            in_specs=[
                xspec,
                pl.BlockSpec((half, bn), lambda i, j: (0, j)),
                pl.BlockSpec((g, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            scratch_shapes=_split_scratch(bm, kc, sub8),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(xc, qc, sc)
        out = part if out is None else out + part
    return out[:m, :n].astype(out_dtype)


def _stack_blocks(k: int, n: int, bn: int):
    """How one layer of a ``(L, K/2, N)`` stack is blocked IN PLACE:
    ``(bn, chunks)``, or None where the shapes allow no such blocking.

    A block's last two dims must be whole tiles (32 sublanes of uint8,
    8 of float32, 128 lanes) or the whole array dim, and a block index
    counts whole blocks: so N tiles by ``bn`` or by 128 where one
    divides it (padding a stack would copy it), and K chunks are all
    of one size with ``half % 32 == 0`` and ``g % 8 == 0``."""
    if n % bn:
        if n % 128 and n > bn:
            return None
        bn = 128 if n % 128 == 0 else n
    chunks = _chunk_k(k)
    if len(chunks) > 1:
        kc = chunks[0][1]
        if kc * len(chunks) != k or (kc // 2) % 32 or (kc // QK) % 8:
            return None
    return bn, chunks


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "out_dtype", "mode"))
def _int4_matmul_stacked_jit(x, q_t, scale_t, layer, bm: int, bn: int,
                             interpret: bool, out_dtype, mode: str):
    """:func:`_int4_matmul_jit` on layer ``layer[0]`` of a stack, the
    stack left where it is: ``layer`` is a scalar-prefetch operand and
    the weight and scale blocks are ``(None, half, bn)`` /
    ``(None, g, bn)`` at block index ``(layer, c, j)``, ``c`` the K
    chunk. Same kernel body, tiles, operands and accumulation as the
    2-D form, so the two agree bit for bit. A stack whose shapes
    cannot be blocked that way (:func:`_stack_blocks`) is sliced and
    takes the 2-D path."""
    m, k = x.shape
    _, half_all, n = q_t.shape
    if half_all * 2 != k:
        raise ValueError(
            f"q_t {q_t.shape} is not the (L, K/2, N) TPU layout for "
            f"K={k}; convert ggml (N, K/2) dicts with to_tpu_layout() "
            "first")
    plan = _stack_blocks(k, n, bn)
    if plan is None:
        return _int4_matmul_jit(
            x, jax.lax.dynamic_index_in_dim(q_t, layer[0], keepdims=False),
            jax.lax.dynamic_index_in_dim(scale_t, layer[0], keepdims=False),
            bm=bm, bn=bn, interpret=interpret, out_dtype=out_dtype,
            mode=mode)
    bn, chunks = plan
    sub8 = (m >= 256) if mode == "auto" else (mode == "sub8")
    scale_t = scale_t.astype(jnp.float32)
    bm = _align_bm(bm, m)
    m_pad = -m % bm
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    mp = x.shape[0]
    x = x.astype(jnp.bfloat16)

    out = None
    for c, (k0, kc) in enumerate(chunks):
        half, g = kc // 2, kc // QK
        xc, xspec = _x_inputs(x, bm, k0, kc)
        part = pl.pallas_call(
            functools.partial(_int4_stacked_kernel, sub8=sub8,
                              cdt=jnp.float32 if interpret
                              else jnp.bfloat16),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(mp // bm, n // bn),
                in_specs=[
                    xspec,
                    pl.BlockSpec((None, half, bn),
                                 lambda i, j, l, c=c: (l[0], c, j)),
                    pl.BlockSpec((None, g, bn),
                                 lambda i, j, l, c=c: (l[0], c, j)),
                ],
                out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
                scratch_shapes=_split_scratch(bm, kc, sub8)),
            out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(layer, xc, q_t, scale_t)
        out = part if out is None else out + part
    return out[:m].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "out_dtype"))
def asym_int4_matmul(x, q_t, scale_t, zero_t, bm: int = 128, bn: int = 256,
                     interpret: bool = False, out_dtype=jnp.bfloat16):
    """y = x @ dequant_q4_1(q, scale, zero) in TPU layout."""
    m, k = x.shape
    n = q_t.shape[1]
    scale_t = scale_t.astype(jnp.float32)
    zero_t = zero_t.astype(jnp.float32)
    bm = _align_bm(bm, m)
    m_pad = -m % bm
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    q_t, scale_t, zero_t = _pad_nk(q_t, scale_t, bn, 0, (zero_t,))
    mp, np_ = x.shape[0], q_t.shape[1]
    x = x.astype(jnp.bfloat16)

    out = None
    for k0, kc in _chunk_k(k):
        xe = x[:, k0:k0 + kc:2]
        xo = x[:, k0 + 1:k0 + kc:2]
        qc = q_t[k0 // 2:(k0 + kc) // 2]
        sc = scale_t[k0 // QK:(k0 + kc) // QK]
        zc = zero_t[k0 // QK:(k0 + kc) // QK]
        half, g = kc // 2, kc // QK
        part = pl.pallas_call(
            functools.partial(_asym_int4_kernel,
                              cdt=jnp.float32 if interpret
                              else jnp.bfloat16),
            grid=(mp // bm, np_ // bn),
            in_specs=[
                pl.BlockSpec((bm, half), lambda i, j: (i, 0)),
                pl.BlockSpec((bm, half), lambda i, j: (i, 0)),
                pl.BlockSpec((half, bn), lambda i, j: (0, j)),
                pl.BlockSpec((g, bn), lambda i, j: (0, j)),
                pl.BlockSpec((g, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(xe, xo, qc, sc, zc)
        out = part if out is None else out + part
    return out[:m, :n].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "out_dtype"))
def int8_matmul(x, q_t, scale_t, bm: int = 128, bn: int = 256,
                interpret: bool = False, out_dtype=jnp.bfloat16):
    """y = x @ dequant_q8_0(q, scale) — the BigQuant INT8 gemm
    equivalent (SURVEY.md §2.2). q_t: (K, N) int8; scale_t: (K/QK, N)."""
    m, k = x.shape
    n = q_t.shape[1]
    if q_t.shape[0] != k:
        raise ValueError(
            f"q_t {q_t.shape} is not the (K, N) TPU layout for K={k}; "
            "convert ggml (N, K) dicts with to_tpu_layout() first")
    scale_t = scale_t.astype(jnp.float32)
    bm = _align_bm(bm, m)
    m_pad = -m % bm
    if m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, 0)))
    q_t, scale_t = _pad_nk(q_t, scale_t, bn, 0)
    mp, np_ = x.shape[0], q_t.shape[1]
    x = x.astype(jnp.bfloat16)

    out = None
    for k0, kc in _chunk_k(k):
        xc = x[:, k0:k0 + kc]
        qc = q_t[k0:k0 + kc]
        sc = scale_t[k0 // QK:(k0 + kc) // QK]
        g = kc // QK
        part = pl.pallas_call(
            functools.partial(_int8_kernel,
                              cdt=jnp.float32 if interpret
                              else jnp.bfloat16),
            grid=(mp // bm, np_ // bn),
            in_specs=[
                pl.BlockSpec((bm, kc), lambda i, j: (i, 0)),
                pl.BlockSpec((kc, bn), lambda i, j: (0, j)),
                pl.BlockSpec((g, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(xc, qc, sc)
        out = part if out is None else out + part
    return out[:m, :n].astype(out_dtype)


# ---------------------------------------------------------------------------
# layout conversion + reference
# ---------------------------------------------------------------------------

def to_tpu_layout(qdict: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """ggml row-major quantize() dict → k-major TPU kernel layout.

    sym_int4/asym_int4: q (N, K/2) → q_t (K/2, N); scale (N, G) →
    scale_t (G, N) f32 (fp16 is not loadable by this Mosaic build).
    sym_int8: q (N, K) → (K, N). Other qtypes pass through (they use the
    XLA dequant fallback).
    """
    qtype = qdict.get("qtype", "sym_int4")
    if qtype not in ("sym_int4", "asym_int4", "sym_int8"):
        return dict(qdict)
    out = {"qtype": qtype,
           "q": np.ascontiguousarray(np.asarray(qdict["q"]).T),
           "scale": np.ascontiguousarray(
               np.asarray(qdict["scale"], np.float32).T)}
    if "zero" in qdict:
        out["zero"] = np.ascontiguousarray(
            np.asarray(qdict["zero"], np.float32).T)
    return out


def quantize_tpu(w: np.ndarray, qtype: str = "sym_int4"
                 ) -> Dict[str, np.ndarray]:
    """quantize() + to_tpu_layout() in one step — what model loaders use."""
    from bigdl_tpu.llm.ggml.quantize import quantize
    return to_tpu_layout(quantize(w, qtype))


def int4_matmul_reference(x: np.ndarray, q_packed: np.ndarray,
                          scale: np.ndarray) -> np.ndarray:
    """Independent numpy implementation for golden-parity tests.
    Takes the ggml (N, K/2)+(N, G) layout."""
    from bigdl_tpu.llm.ggml.quantize import dequantize

    w = dequantize({"qtype": "sym_int4", "q": np.asarray(q_packed),
                    "scale": np.asarray(scale)})
    return np.asarray(x, np.float32) @ w.T
