"""Ulysses-style sequence parallelism: all-to-all head/sequence transpose.

No reference analog (SURVEY.md §5 — absent). Alternative to ring attention
for long sequences when head count ≥ mesh axis size: instead of rotating kv
blocks, two ``all_to_all`` collectives re-shard from sequence-sharded to
head-sharded, each device runs *full-sequence* attention over its head
slice, then the layout is transposed back. One big collective pair instead
of n ppermute steps — better when ICI all-to-all bandwidth beats the ring's
latency (short-ish sequences, many heads).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _sdpa(q, k, v, causal: bool, scale: float):
    # q/k/v: (B, S, h_local, D) — full sequence, local heads
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.arange(s_q)[:, None] >= jnp.arange(s_k)[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype))
    return out.astype(q.dtype)


def ulysses_self_attention(q, k, v, axis_name: str = "seq",
                           causal: bool = False,
                           scale: Optional[float] = None,
                           attn_fn: Optional[Callable] = None):
    """Per-device body (inside shard_map). q/k/v: (B, S_local, H, D),
    H divisible by the axis size."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    n = lax.axis_size(axis_name)
    if q.shape[2] % n != 0:
        raise ValueError(f"heads {q.shape[2]} not divisible by axis size {n}")

    def seq_to_head(t):   # (B, S/n, H, D) -> (B, S, H/n, D)
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head_to_seq(t):   # (B, S, H/n, D) -> (B, S/n, H, D)
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    q, k, v = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    # a custom attn_fn receives causal/scale too — it must honor them
    attn = attn_fn or _sdpa
    out = attn(q, k, v, causal=causal, scale=scale)
    return head_to_seq(out)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                      causal: bool = False, scale: Optional[float] = None,
                      batch_axis: Optional[str] = "data"):
    """Global entry mirroring :func:`ring_attention`'s signature."""

    baxis = batch_axis if (batch_axis and batch_axis in mesh.axis_names) \
        else None
    spec = P(baxis, axis, None, None)
    fn = jax.shard_map(
        functools.partial(ulysses_self_attention, axis_name=axis,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
)
    sh = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(t, sh) for t in (q, k, v))
    return fn(q, k, v)
