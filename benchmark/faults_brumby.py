"""Deliberate faults planted in the SERVED program of the ``brumby``
family, to show that the driver's comparison (``drivers/serve_brumby``)
comes out ``correct: false`` for each. Never for a result:
``benchmark/check_brumby.py`` (chip, published widths) and
``benchmark/tests/test_brumby_cell.py`` (CPU, rehearsal widths) are the
only users.

A fault replaces one function or constant of
``bigdl_tpu.llm.models.brumby`` or of its kernels
(``bigdl_tpu.llm.kernels.retention``) while an ``LLMServer`` is built
and driven, and is taken out again; the kernels the engine runs stay
the served ones. ISSUE 33's eight:

- ``no_gate``: the gate left out (``gamma`` = 1: nothing is ever
  forgotten);
- ``power_1``: power 1 for 2 (``phi(x) = x``: the weights ``q . k``
  and not their square);
- ``no_normaliser``: the row's sum left out (``y = sum_s a_ts v_s``);
- ``state_bf16``: the state and the normaliser held in bfloat16 (a
  lower precision than the configuration states must fail one check);
- ``slot_not_zeroed``: a newly seated slot's state NOT taken as zero:
  the request starts from what the slot's last occupant left;
- ``z_not_decayed``: a decode step decays the state and not the
  normaliser;
- ``no_rotary``: the rotary left out of q and k;
- ``no_sqrt2``: the ``sqrt 2`` left off the cross terms of ``phi``.
"""

from __future__ import annotations

import contextlib
import dataclasses
from unittest import mock

FAULTS = ("no_gate", "power_1", "no_normaliser", "state_bf16",
          "slot_not_zeroed", "z_not_decayed", "no_rotary", "no_sqrt2")


@contextlib.contextmanager
def planted(fault: str, cfg=None):
    """The program with ``fault`` in it; every compiled engine program
    is dropped on the way in and out, since the engine caches them by
    shape and not by what they compute."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm import serving
    from bigdl_tpu.llm.kernels import retention
    from bigdl_tpu.llm.models import brumby

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fault == "no_gate":
        inner = brumby.log_gate
        patch = mock.patch.object(
            brumby, "log_gate", lambda lp, h: 0.0 * inner(lp, h))
    elif fault == "power_1":
        def tile(x, x2, d, roll):
            return x if d == 0 else jnp.zeros_like(x)
        patch = mock.patch.object(retention, "_phi_tile", tile)
    elif fault == "no_normaliser":
        patch = mock.patch.object(
            retention, "_finish",
            lambda num, den, n, eps: num / n + 0.0 * den)
    elif fault == "state_bf16":
        inner = brumby.page_classes
        patch = mock.patch.object(
            brumby, "page_classes", lambda c: [
                dataclasses.replace(k, dtype="bfloat16") for k in inner(c)])
    elif fault == "slot_not_zeroed":
        inner = retention.retention_prefill_chunk

        def chunk(state, z, q, k, v, g, slot, fresh, n_live, **kw):
            return inner(state, z, q, k, v, g, slot, False, n_live, **kw)
        patch = mock.patch.object(retention, "retention_prefill_chunk",
                                  chunk)
    elif fault == "z_not_decayed":
        patch = mock.patch.object(retention, "_decay", lambda gam, z: z)
    elif fault == "no_rotary":
        patch = mock.patch.object(
            brumby, "rope", lambda x, positions, theta: x)
    else:       # no_sqrt2
        patch = mock.patch.object(retention, "SQRT2", 1.0)
    # the kernels' own jits remember what they traced, too
    serving._PAGED_STEP_CACHE.clear()
    jax.clear_caches()
    try:
        with patch:
            yield
    finally:
        serving._PAGED_STEP_CACHE.clear()
        jax.clear_caches()
