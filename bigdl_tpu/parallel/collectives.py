"""Collective wrappers — the XLA-native replacement for the reference's comm
backend.

Reference comm (SURVEY.md §2.5): ``AllReduceParameter`` slices the flattened
parameter vector into partition-count chunks; workers put gradient slices
into Spark BlockManager, slice owners fetch+reduce, update, put weights back,
workers re-fetch — with FP16 wire compression (``FP16CompressedTensor``).
Here each of those becomes one XLA collective compiled into the step program
and scheduled over ICI:

- put/fetch+reduce            → ``all_reduce`` (psum) / ``reduce_scatter``
- weight re-fetch             → ``all_gather``
- FP16CompressedTensor        → ``compressed_all_reduce`` (bf16 wire dtype)

These must be called inside ``shard_map``-ed (or manually partitioned jit)
code where ``axis_name`` is bound.

Telemetry: every wrapper bumps ``bigdl_collective_traced_bytes_total``
/ ``bigdl_collective_calls_total`` (labeled by op) with its INPUT
payload size. The count happens at TRACE time — the only host-visible
moment of a compiled collective — so it measures payload bytes per
compiled call site, not per device execution; actual wire traffic is
payload x executions x the op's amplification factor (e.g. an 8-way
all_gather receives ~7 shards per device). Zero per-step cost: nothing
runs on the executed path.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu import observability as obs


def _count_collective(op: str, tree: Any, bytes_per_element=None):
    """Trace-time accounting of a collective's wire payload. For
    compressed/quantized ops ``bytes_per_element`` overrides the carrier
    dtype width (e.g. ~1.02 for int8 blocks incl. scales)."""
    if not obs.enabled():
        return
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = int(getattr(leaf, "size", 0) or 0)
        if bytes_per_element is not None:
            total += int(size * bytes_per_element)
        else:
            dtype = getattr(leaf, "dtype", None)
            itemsize = jnp.dtype(dtype).itemsize if dtype is not None \
                else 4
            total += size * itemsize
    obs.counter("bigdl_collective_traced_bytes_total",
                "Input payload bytes per compiled collective call site "
                "(trace-time accounting: multiply by executions, and by "
                "the op's wire amplification — e.g. ~(n-1) recv copies "
                "for all_gather, ~2(n-1)/n for ring all_reduce — for "
                "actual traffic)",
                labelnames=("op",)).labels(op=op).inc(total)
    obs.counter("bigdl_collective_calls_total",
                "Collective call sites traced", labelnames=("op",)
                ).labels(op=op).inc()


def all_reduce(tree: Any, axis_name: str, mean: bool = False) -> Any:
    """Sum (or mean) a pytree across ``axis_name`` (ref: the gradient
    aggregate in AllReduceParameter.putGradients/getGradients)."""
    _count_collective("all_reduce", tree)
    op = lax.pmean if mean else lax.psum
    return jax.tree_util.tree_map(lambda x: op(x, axis_name), tree)


def compressed_all_reduce(tree: Any, axis_name: str, mean: bool = False,
                          wire_dtype=jnp.bfloat16) -> Any:
    """All-reduce with gradients cast to a 16-bit wire dtype first — the
    analog of the reference's FP16CompressedTensor wire compression
    (optim/parameters/FP16CompressedTensor.scala). Accumulation happens in
    the wire dtype (matching the reference, which sums fp16 buffers), the
    result is cast back to the input dtype."""

    _count_collective("compressed_all_reduce", tree,
                      bytes_per_element=jnp.dtype(wire_dtype).itemsize)

    def _cr(x):
        y = lax.psum(x.astype(wire_dtype), axis_name)
        if mean:
            y = y / lax.psum(jnp.ones((), wire_dtype), axis_name)
        return y.astype(x.dtype)

    return jax.tree_util.tree_map(_cr, tree)


def quantized_all_reduce(tree: Any, axis_name: str, mean: bool = False,
                         block: int = 256) -> Any:
    """INT8 block-quantized all-reduce — the EQuARX-style step past
    FP16CompressedTensor (PAPERS.md: quantized collectives trade wire
    bytes for a dequant/requant at each hop).

    Two-collective formulation (the EQuARX shared-scaling idea): peers
    first agree on a per-block scale via a tiny ``pmax`` of block
    absmaxes (4 B/block on the wire), every peer quantizes against the
    SHARED scale, and the int8 payloads are summed across the axis
    (int32 accumulation). One dequant at the end gives
    sum_i(q_i) * s_shared — the sum of the quantized values exactly, so
    the only error is each peer's own rounding: per element at most
    n * s_shared / 2, i.e. <= n * blockmax / 254. Wire bytes:
    ~1 B/element + 4 B/block vs 4 B/element f32.
    """
    # ~1 B/element int8 payload + 4 B per block of shared f32 scale
    _count_collective("quantized_all_reduce", tree,
                      bytes_per_element=1.0 + 4.0 / block)
    n = lax.axis_size(axis_name)

    def _qr(x):
        orig_dtype = x.dtype
        flat = x.reshape(-1).astype(jnp.float32)
        pad = (-flat.shape[0]) % block
        if pad:
            flat = jnp.pad(flat, (0, pad))
        g = flat.reshape(-1, block)
        local_max = jnp.max(jnp.abs(g), axis=1, keepdims=True)
        scale = lax.pmax(local_max, axis_name) / 127.0   # shared scale
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(g / safe), -127, 127).astype(jnp.int8)
        q_sum = lax.psum(q.astype(jnp.int32), axis_name)
        out = (q_sum.astype(jnp.float32) * scale).reshape(-1)
        if pad:
            out = out[:flat.shape[0] - pad]
        if mean:
            out = out / n
        return out.reshape(x.shape).astype(orig_dtype)

    return jax.tree_util.tree_map(_qr, tree)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """Gather shards along ``axis`` (ref: AllReduceParameter.getWeights)."""
    _count_collective("all_gather", x)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    """Sum across the axis group, scattering result slices — the fused form
    of the reference's put-gradients + owner-reduce."""
    _count_collective("reduce_scatter", x)
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int,
               tiled: bool = True):
    """Transpose sharded layout between two tensor dimensions (used by
    Ulysses sequence parallelism — no reference analog, SURVEY.md §5)."""
    _count_collective("all_to_all", x)
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def ppermute_next(x, axis_name: str, shift: int = 1):
    """Circular shift around the axis ring (ring attention's neighbor
    exchange; rides ICI nearest-neighbor links)."""
    _count_collective("ppermute", x)
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def barrier_sum(axis_name: str):
    """Cheap synchronization point (ref: ParameterSynchronizer barrier)."""
    return lax.psum(jnp.ones((), jnp.int32), axis_name)
