"""On-device next-token sampling for the serving engine (ISSUE 4).

The synchronous engine sampled on the HOST: every decode step pulled the
(B, V) logits' argmax to python before it could dispatch the next step —
one device→host roundtrip per token, serialized against device compute
(what one costs on this runtime is not measured). Folding sampling INTO
the compiled decode
step means the step consumes the previous step's logits entirely on
device and emits ready-to-drain token ids, so the host only fetches a
small int vector — and, under pipelining, fetches it one step late
while the device is already running the next step.

Everything here is plain XLA (argmax / top_k / categorical): it lowers
to the same fused program on TPU and CPU, no Mosaic kernel needed — the
decode step's cost is the weight stream, not the (B, V) reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_tokens(logits, key, *, do_sample: bool = False,
                  temperature=1.0, top_k: int = 0):
    """``(B, V)`` logits → ``(B,)`` int32 next tokens.

    ``do_sample``/``top_k`` are trace-time constants (they change the
    program); ``temperature`` is a runtime scalar so serving can tune it
    without a recompile. Greedy (``do_sample=False``) is bit-identical
    to the host-side ``argmax`` it replaces — the serving parity tests
    assert served tokens equal ``generate()``'s.
    """
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)
    if 0 < top_k < scaled.shape[-1]:
        # top_k >= vocab is a no-op filter — and lax.top_k rejects
        # k > minor dim outright, so the clamp is correctness, not
        # just a shortcut (locked by tests/test_sampling.py)
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1][:, None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def fence_token(*arrays):
    """A ``(1,)`` int32 whose VALUE is garbage but whose availability
    data-depends on every input array.

    A device→host fetch of data that depends on a computation is a
    valid completion fence on any runtime, and the engine has to fetch
    the sampled tokens anyway: it concatenates this element onto the
    token vector, so ONE small fetch both delivers the tokens and
    bounds the step's pool writes — no second roundtrip.

    The first element of each array is summed (never multiplied by zero:
    XLA may constant-fold ``x*0`` for ints and would sever the data
    dependence), NaN-scrubbed and clipped so the int cast is defined.
    """
    acc = jnp.float32(0.0)
    # a family with one pool has no second one (None: no leaf); one
    # with several page classes hands a tuple of pools
    for a in jax.tree_util.tree_leaves(arrays):
        acc = acc + a.ravel()[0].astype(jnp.float32)
    acc = jnp.clip(jnp.nan_to_num(acc), -1e9, 1e9)
    return acc.astype(jnp.int32)[None]


def spec_accept(ctoks, chunk_logits, n_draft):
    """Fused speculative verify-accept (ISSUE 19), greedy exact-match.

    ``ctoks`` (W,) int32 is the verify chunk — the on-device greedy
    token ``g0`` followed by ``n_draft`` host drafts (zero-padded to the
    bucket width W); ``chunk_logits`` (W, V) f32 are the ragged chunk
    leg's logits, where row ``j`` is the distribution AFTER consuming
    chunk token ``j`` (i.e. it predicts position ``offset + j + 1``).
    Draft ``j`` (= ``ctoks[j]``, j >= 1) is accepted iff it equals
    ``argmax(chunk_logits[j-1])`` — exactly the token greedy decode
    would have emitted there — and every earlier draft was accepted.

    Returns ``(n_acc, new_last)``: the emitted-token count (the
    accepted-draft prefix plus the always-valid ``g0``, so
    ``1 <= n_acc <= n_draft + 1``) and ``chunk_logits[n_acc - 1]`` —
    the distribution following the LAST emitted token, which becomes
    the row's ``last`` for the next engine step. With zero drafts
    accepted this degenerates to a plain decode step: emit ``g0``,
    carry ``chunk_logits[0]``.

    Pad rows (``j >= n_draft``) can never match (the arange mask), so
    garbage logits at padded positions — finite by the kernels'
    masked-lane contract — cannot extend the accepted prefix.

    Greedy only: the rejection-sampling acceptance rule for
    ``temperature > 0`` hangs off this same contract (replace the
    exact-match test with the p/q coin flip) but is gated off with the
    engine's ``do_sample`` path for now.
    """
    w = ctoks.shape[0]
    greedy = jnp.argmax(chunk_logits, axis=-1).astype(jnp.int32)  # (W,)
    match = (ctoks[1:] == greedy[:-1]) & \
        (jnp.arange(w - 1, dtype=jnp.int32) < n_draft)
    # longest all-accepted prefix: cumprod zeroes everything after the
    # first rejection, the sum counts the survivors
    n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32))) + 1
    new_last = jnp.take(chunk_logits, n_acc - 1, axis=0)
    return n_acc.astype(jnp.int32), new_last.astype(jnp.float32)


def make_sampled_step(fam_step):
    """Lift a family ``paged_decode_step`` (toks-in, logits-out) into the
    pipelined engine's step shape (logits-in, sampled-ids-out).

    The lifted step:

    - samples the next token for every row from ``last`` ON DEVICE;
    - masks block-table rows and lengths of inactive rows to the trash
      page (page 0 / length 0), so rows whose dispatch budget is spent
      — or whose slot is empty — dummy-write into the trash page
      exactly like the synchronous engine's zeroed ``bt`` rows did;
    - advances ``lens`` for active rows on device (the host never
      re-uploads the length vector);
    - returns ``(out, logits, k_pages, v_pages, new_lens, key)`` where
      ``out`` is ``(B+1,)`` int32: the B sampled ids plus a
      :func:`fence_token` element bounding the pool writes. A family
      step that returns a fourth value, an int32 vector of the step's
      own counts (its module names them in ``STEP_STATS``), gets it
      appended after the fence, so the drain's one fetch brings it.

    The engine wraps a family's ``paged_decode_step`` with this (a
    family module may expose its own ``paged_decode_step_sampled``
    instead), so it dispatches one compiled program per family with no
    per-family sampling code.
    """

    def sampled_step(params, cfg, k_pages, v_pages, bt, lens, last,
                     active, temperature, key, *, page: int,
                     do_sample: bool = False, top_k: int = 0):
        key, sub = jax.random.split(key)
        toks = sample_tokens(last, sub, do_sample=do_sample,
                             temperature=temperature, top_k=top_k)
        # one table, or one a page class (docs/KVCACHE.md)
        bt_eff = jax.tree_util.tree_map(
            lambda t: jnp.where(active[:, None], t, 0), bt)
        lens_eff = jnp.where(active, lens, 0)
        logits, k_pages, v_pages, *stats = fam_step(
            params, cfg, k_pages, v_pages, bt_eff, lens_eff, toks,
            page=page)
        # inactive rows carry their previous logits forward instead of
        # the trash-page garbage their masked leg computed: a row
        # sitting out passes while its speculative verify is in flight
        # (ISSUE 19) must find its ``last`` intact at the drain, and an
        # empty slot's lane was never read either way
        logits = jnp.where(active[:, None], logits, last)
        new_lens = lens + active.astype(lens.dtype)
        out = jnp.concatenate(
            [toks, fence_token(k_pages, v_pages, logits), *stats])
        return out, logits, k_pages, v_pages, new_lens, key

    return sampled_step
