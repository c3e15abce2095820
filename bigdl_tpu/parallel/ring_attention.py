"""Ring attention — sequence/context parallelism for long sequences.

The reference has NO long-context machinery (SURVEY.md §5: no ring
attention, no context parallel; bigdl-llm only manages kv-cache memory on a
single host). This module is the idiomatic TPU answer: the sequence axis is
sharded over a mesh axis, each device computes blockwise attention for its
query chunk while key/value chunks rotate around the ring via ``ppermute``
(one ICI neighbor hop per step), with flash-style online-softmax
accumulation so the full score matrix never materializes.

Layout convention: ``(batch, seq, heads, head_dim)``, sequence sharded over
the mesh axis (default ``"seq"``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _varying(x, like):
    """Make a locally-created array inherit ``like``'s varying-manual-axes
    type — required by shard_map's VMA typing when the array enters a
    scan carry whose other leg went through a collective. Uses ``lax.pcast``
    (a pure type cast, no data dependence on ``like``'s values, so a
    poisoned inf/NaN in ``like`` cannot corrupt ``x``)."""
    vma = tuple(jax.typeof(like).vma - jax.typeof(x).vma)
    if not vma:
        return x
    return lax.pcast(x, vma, to="varying")


def online_block_update(qg, k, v, mask, acc, row_max, row_sum, *, scale):
    """One kv-block flash-style online-softmax update, GQA grouped layout.

    The single implementation of the max/correction/exp/accumulate
    recurrence shared by the ring kernel here and the cache-window
    blockwise path in ``bigdl_tpu.llm.models.llama._attention``.

    qg: (B, Tq, Hkv, G, D) — query heads grouped onto their kv head
        (q head ``h`` = group ``h % G`` of kv head ``h // G``, the HF/GQA
        convention); repeated K/V is never materialized.
    k, v: (B, Sk, Hkv, D); mask: (B, Tq, Sk) (or broadcastable), True
        where attending is allowed.
    acc: (B, Hkv, G, Tq, D) f32; row_max/row_sum: (B, Hkv, G, Tq) f32.
    """
    logits = jnp.einsum("bthgd,bshd->bhgts", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    blk_max = jnp.max(logits, axis=-1)                 # (B, Hkv, G, Tq)
    new_max = jnp.maximum(row_max, blk_max)
    correction = jnp.exp(row_max - new_max)
    p = jnp.exp(logits - new_max[..., None])
    # rows with no valid key in this block: exp(NEG_INF - max) underflows
    # to 0 except when the row max itself is NEG_INF — zero explicitly
    p = jnp.where(mask[:, None, None], p, 0.0)
    acc = acc * correction[..., None] + jnp.einsum(
        "bhgts,bshd->bhgtd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    row_sum = row_sum * correction + jnp.sum(p, axis=-1)
    return acc, new_max, row_sum


def _block_attn(q, k, v, acc, row_max, row_sum, *, scale,
                q_pos, k_pos, causal):
    """Ring-step wrapper over :func:`online_block_update`.

    q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D)
    acc: (B, Hkv, G, Sq, D); row_max/row_sum: (B, Hkv, G, Sq)
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    if causal:
        mask = jnp.broadcast_to((q_pos[:, None] >= k_pos[None, :]),
                                (b, sq, sk))
    else:
        mask = jnp.ones((b, sq, sk), bool)
    return online_block_update(qg, k, v, mask, acc, row_max, row_sum,
                               scale=scale)


def ring_self_attention(q, k, v, axis_name: str = "seq",
                        causal: bool = False,
                        scale: Optional[float] = None):
    """Per-device body: call inside ``shard_map`` with seq sharded on
    ``axis_name``. q/k/v: (B, S_local, H, D) local chunks."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5

    q_pos = my * s_local + jnp.arange(s_local)
    acc0 = _varying(jnp.zeros((b, hkv, g, s_local, d), jnp.float32), q)
    max0 = _varying(jnp.full((b, hkv, g, s_local), NEG_INF, jnp.float32), q)
    sum0 = _varying(jnp.zeros((b, hkv, g, s_local), jnp.float32), q)

    def step(carry, i):
        k_blk, v_blk, acc, row_max, row_sum = carry
        # after i forward shifts, this device holds chunk (my - i) mod n
        chunk = (my - i) % n
        k_pos = chunk * s_local + jnp.arange(s_local)
        acc, row_max, row_sum = _block_attn(
            q, k_blk, v_blk, acc, row_max, row_sum,
            scale=scale, q_pos=q_pos, k_pos=k_pos, causal=causal)
        # rotate kv to the next device (one ICI hop)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, acc, row_max, row_sum), None

    (k, v, acc, row_max, row_sum), _ = lax.scan(
        step, (k, v, acc0, max0, sum0), jnp.arange(n))
    out = acc / jnp.maximum(row_sum, 1e-30)[..., None]  # (B,Hkv,G,Sq,D)
    return (out.transpose(0, 3, 1, 2, 4)                # (B,Sq,Hkv,G,D)
            .reshape(b, s_local, h, d).astype(q.dtype))


def ring_attention(q, k, v, mesh: Mesh, axis: str = "seq",
                   causal: bool = False, scale: Optional[float] = None,
                   batch_axis: Optional[str] = "data"):
    """Global entry: q/k/v are (B, S, H, D) arrays; S is sharded over
    ``axis`` (and optionally B over ``batch_axis``) by this wrapper."""

    baxis = batch_axis if (batch_axis and batch_axis in mesh.axis_names) \
        else None
    spec = P(baxis, axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_self_attention, axis_name=axis,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
)
    sh = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(t, sh) for t in (q, k, v))
    return fn(q, k, v)
