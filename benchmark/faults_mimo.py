"""Deliberate faults planted in the SERVED program of the ``mimo_v2``
family, to show that the driver's comparison (``drivers/serve_mimo.
reference_check``) comes out ``correct: false`` for each. Never for a
result: ``benchmark/check_mimo.py`` (chip, published widths) and
``benchmark/tests/test_mimo_cell.py`` (CPU, rehearsal widths) are the
only users.

A fault replaces one function of ``bigdl_tpu.llm.models.mimo``, of its
kernels, of the page writers or of the ring's size while an
``LLMServer`` is built and driven, and is taken out again. ISSUE 31's
twelve, then one for precision:

- ``no_sink``: the sink left out of the served softmax (the decode
  merge and the prefill kernel; the dense forward keeps it);
- ``window_127`` / ``window_129``: the served kernels' window one
  short, one long;
- ``swa_theta_full``: the full layers' rotary base on the window
  layers (the program's arithmetic: the dense forward has it too, so
  the reference has to catch it);
- ``rotary_all``: rotary over all of a head, not its first third;
- ``no_value_scale``: the values cached unscaled;
- ``kv_heads_4_for_8``: the window class's decode attention reading
  4 KV heads where 8 are cached (query head ``h`` reads KV head
  ``h // 16``);
- ``window_row_next_slot``: a decode step's window-class row written
  one slot on in its page;
- ``ring_one_page_short``: the ring ``ceil(window / page)`` pages (8,
  128 positions) where a window and the page being filled need one
  more: a page is recycled while 15 of its positions are still in the
  window;
- ``experts_next_share``: the held weights used as experts 16-31's
  (the assignments of the next chip's share computed with this
  chip's weights);
- ``renorm_over_held``: routed weights renormalised over the held
  experts only;
- ``top7``: one expert fewer than ``num_experts_per_tok``;
- ``router_bf16``: the router's scores in bfloat16 (a lower precision
  than the configuration states must fail one check).
"""

from __future__ import annotations

import contextlib
from unittest import mock

REFERENCE_FAULTS = ("swa_theta_full", "rotary_all", "no_value_scale",
                    "experts_next_share", "renorm_over_held", "top7",
                    "router_bf16")
SERVED_ONLY_FAULTS = ("no_sink", "window_127", "window_129",
                      "kv_heads_4_for_8", "window_row_next_slot",
                      "ring_one_page_short")
FAULTS = SERVED_ONLY_FAULTS + REFERENCE_FAULTS


def _route(fault: str):
    import jax
    import jax.numpy as jnp

    def route(router, h, cfg):
        w, x = router["w"].astype(jnp.float32), h.astype(jnp.float32)
        if fault == "router_bf16":
            w, x = w.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
        s = jax.nn.sigmoid(jnp.dot(
            x, w.T, precision=jax.lax.Precision.HIGHEST)
            .astype(jnp.float32))
        k = cfg.num_experts_per_tok - (fault == "top7")
        _, idx = jax.lax.top_k(s + router["bias"], k)
        wts = jnp.take_along_axis(s, idx, -1)
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
        return idx.astype(jnp.int32), wts * cfg.routed_scaling_factor
    return route


@contextlib.contextmanager
def planted(fault: str, cfg):
    """The program with ``fault`` in it; every compiled engine program
    is dropped on the way in and out, since the engine caches them by
    shape and not by what they compute."""
    import jax.numpy as jnp

    from bigdl_tpu.llm import serving
    from bigdl_tpu.llm.kernels import hybrid_attention as ha
    from bigdl_tpu.llm.kernels import moe, paged_attention
    from bigdl_tpu.llm.kvcache import write
    from bigdl_tpu.llm.models import mimo

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    patches = []
    if fault in ("top7", "router_bf16"):
        patches.append(mock.patch.object(mimo, "route", _route(fault)))
    elif fault == "no_sink":
        merge, prefill = paged_attention.merge_attention_partial, \
            ha.prefill_attention
        patches += [
            mock.patch.object(
                paged_attention, "merge_attention_partial",
                lambda *a, sink=None, **k: merge(*a, **k)),
            mock.patch.object(
                ha, "prefill_attention",
                lambda q, ks, vs, kv, bt, off, n, sink=None, **k:
                prefill(q, ks, vs, kv, bt, off, n, None, **k))]
    elif fault in ("window_127", "window_129"):
        delta = -1 if fault == "window_127" else 1
        decode, prefill = ha.attention_decode_stats, ha.prefill_attention

        def moved(inner):
            def call(*a, window=None, **k):
                return inner(*a, window=None if window is None
                             else window + delta, **k)
            return call
        patches += [
            mock.patch.object(ha, "attention_decode_stats", moved(decode)),
            mock.patch.object(ha, "prefill_attention", moved(prefill))]
    elif fault == "swa_theta_full":
        patches.append(mock.patch.object(
            mimo.MimoConfig, "theta", lambda self, kind: self.rope_theta))
    elif fault == "rotary_all":
        patches.append(mock.patch.object(
            mimo.MimoConfig, "rotary_dim",
            property(lambda self: self.head_dim)))
    elif fault == "no_value_scale":
        inner = mimo.project_qkv

        def project_qkv(lp, h, positions, cfg_, kind):
            q, k, v = inner(lp, h, positions, cfg_, kind)
            return q, k, (v.astype(jnp.float32)
                          / cfg_.attention_value_scale).astype(v.dtype)
        patches.append(mock.patch.object(mimo, "project_qkv", project_qkv))
    elif fault == "kv_heads_4_for_8":
        inner = ha.attention_decode_stats

        def decode(q, kv_pages, bt, lens, *, window=None, **k):
            if window is not None:      # the window class: pairs of KV
                half = kv_pages[:, :kv_pages.shape[1] // 2]   # heads read
                kv_pages = jnp.repeat(half, 2, axis=1)        # as one
            return inner(q, kv_pages, bt, lens, window=window, **k)
        patches.append(mock.patch.object(ha, "attention_decode_stats",
                                         decode))
    elif fault == "window_row_next_slot":
        inner = write.write_kv
        heads = cfg.swa_num_key_value_heads

        def write_kv(pool, phys, slots, rows):
            if pool.shape[2] == heads:
                slots = (slots + 1) % pool.shape[3]
            return inner(pool, phys, slots, rows)
        patches.append(mock.patch.object(write, "write_kv", write_kv))
    elif fault == "ring_one_page_short":
        patches.append(mock.patch.object(
            ha, "ring_pages", lambda window, page: -(-window // page)))
    else:           # experts_next_share, renorm_over_held
        inner = moe.grouped_ffn

        def grouped_ffn(x, groups_of, weights, *a, held=None, **k):
            first, count = held
            if fault == "experts_next_share":
                held = (first + count, count)
            else:
                mine = (groups_of >= first) & (groups_of < first + count)
                kept = jnp.where(mine, weights, 0.0)
                weights = kept / (kept.sum(-1, keepdims=True) + 1e-20)
            return inner(x, groups_of, weights, *a, held=held, **k)
        patches.append(mock.patch.object(moe, "grouped_ffn", grouped_ffn))
    serving._PAGED_STEP_CACHE.clear()
    try:
        with contextlib.ExitStack() as stack:
            for p in patches:
                stack.enter_context(p)
            yield
    finally:
        serving._PAGED_STEP_CACHE.clear()
