"""The retention kernels alone on the chip (ISSUE 33): each against its
plain-XLA twin at Brumby's widths, then timed. ``python3
tools/exp_retention.py [--rows 20 --live 11,15 --slabs 640,1664,8320
--out chiprun_out/exp_retention.json]``; prints one line a reading and
writes them to ``--out``. A decode reading holds two times (ISSUE 36):
``entry``, :func:`retention_decode` whole (the kernel and what XLA is
left around it, one jit, the cache donated), and ``kernel``, the Mosaic
call by itself; ``glue_ms`` is their difference. Here, with
``JAX_PLATFORMS=cpu --interpret --rows 3 --live 2 --chunk 256``, it
runs the kernels in interpret mode against the twins (no timing means
anything)."""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import peaks_brumby  # noqa: E402
from bigdl_tpu.llm.kernels import retention as R  # noqa: E402
from bigdl_tpu.llm.models.brumby import BrumbyConfig  # noqa: E402

HKV, GRP, N = 8, 5, 128
P = R.state_width(N)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(((a - b) ** 2).mean() / (b ** 2).mean()))


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def stream(fn, st, zz, reps=30):
    """Seconds a call of ``fn(st, zz) -> (..., st, zz)``, the cache
    donated and handed on from call to call."""
    st, zz = st + 0, zz + 0
    *_, st, zz = fn(st, zz)
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for _ in range(reps):
        *_, st, zz = fn(st, zz)
    jax.block_until_ready((st, zz))
    return (time.perf_counter() - t0) / reps


def kernel_alone(q, k, v, g, slots, live, slab, interp):
    """``fn(st, zz)``: the Mosaic call by itself, what XLA makes for it
    made beforehand."""
    grp = q.shape[2]
    qf = jnp.pad(q.astype(jnp.float32),
                 ((0, 0), (0, 0), (0, -grp % 8), (0, 0)))
    erow, n_live = R._live_first(live)
    rest = jax.block_until_ready((
        qf, k.astype(jnp.float32), v.astype(jnp.float32), jnp.exp(g),
        slots[erow], erow, n_live))
    return jax.jit(lambda st, zz: R._decode_pallas_call(
        st, zz, *rest, slab=slab, interpret=interp), donate_argnums=(0, 1))


def decode(out, a, state, z, q, k, v, g, slots, live):
    """Kernel against twin, then the entry whole (the kernel and what
    XLA is left around it, one jit) beside the kernel alone."""
    interp = a.interpret
    lv = np.asarray(live)
    srows = np.asarray(slots)[lv]
    want = jax.jit(lambda *x: R._decode_xla(*x, 1e-6))(
        state, z, q, k, v, g, slots, live)
    for slab in [int(s) for s in a.slabs.split(",")]:
        # the operands are arguments: closed over, XLA would fold what
        # depends on them alone (phi, the ordering) at compile time
        ops = (q, k, v, g, slots, live)
        entry = lambda st, zz, *x, slab=slab: R.retention_decode(
            st, zz, *x, slab=slab, interpret=interp)
        got = jax.jit(entry)(state, z, *ops)
        dead = np.setdiff1d(np.arange(1, state.shape[0]), srows)
        r = {"y": rel(np.asarray(got[0])[lv], np.asarray(want[0])[lv]),
             "state": rel(np.asarray(got[1])[srows],
                          np.asarray(want[1])[srows]),
             "z": rel(np.asarray(got[2])[srows], np.asarray(want[2])[srows]),
             "untouched": max(
                 float(np.abs(np.asarray(got[i])[dead]
                              - np.asarray(old)[dead]).max())
                 for i, old in ((1, state), (2, z))) if dead.size else 0.0}
        if not interp:
            moved = R.decode_bytes(int(lv.sum()), HKV, N, N)
            whole = jax.jit(entry, donate_argnums=(0, 1))
            for name, fn in (
                    ("entry", lambda st, zz: whole(st, zz, *ops)),
                    ("kernel", kernel_alone(*ops, slab, False))):
                sec = stream(fn, state, z)
                r[name] = {"ms": sec * 1e3, "gb_s": moved / sec / 1e9,
                           "share_of_819": moved / sec / 819e9}
            r["glue_ms"] = r["entry"]["ms"] - r["kernel"]["ms"]
        out[f"decode_live_{int(lv.sum())}_slab_{slab}"] = r
        print(f"decode live {int(lv.sum())} of {lv.size} slab {slab}: {r}",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20)
    ap.add_argument("--live", default="11,15")
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--slabs", default="640,1664,8320")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default="chiprun_out/exp_retention.json")
    a = ap.parse_args()
    interp = a.interpret
    out = {"device": jax.devices()[0].device_kind}
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 32))
    b, rows = a.rows, a.rows + 1
    unit = lambda x: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    state = jax.random.normal(next(ks), (rows, HKV, N, P), jnp.float32)
    z = jnp.abs(jax.random.normal(next(ks), (rows, HKV, P), jnp.float32)) + 30
    q = unit(jax.random.normal(next(ks), (b, HKV, GRP, N))).astype(jnp.bfloat16)
    k = unit(jax.random.normal(next(ks), (b, HKV, N))).astype(jnp.bfloat16)
    v = jax.random.normal(next(ks), (b, HKV, N)).astype(jnp.bfloat16)
    g = jnp.log(jax.nn.sigmoid(4 + jax.random.normal(next(ks), (b, HKV))))
    for n_live in [int(x) for x in a.live.split(",")]:
        live = jnp.roll(jnp.arange(b) < n_live, 2)
        slots = jnp.where(live, 1 + jnp.arange(b), 0).astype(jnp.int32)
        decode(out, a, state, z, q, k, v, g, slots, live)

    # --- prefill chunk: kernel against twin -------------------------
    c = a.chunk
    qc = unit(jax.random.normal(next(ks), (c, HKV, GRP, N))).astype(jnp.bfloat16)
    kc = unit(jax.random.normal(next(ks), (c, HKV, N))).astype(jnp.bfloat16)
    vc = jax.random.normal(next(ks), (c, HKV, N)).astype(jnp.bfloat16)
    gc = jnp.log(jax.nn.sigmoid(4 + jax.random.normal(next(ks), (c, HKV))))
    for fresh, n_live in ((True, c), (False, c - 100)):
        def run(interpret):
            return jax.jit(lambda st, zz: R.retention_prefill_chunk(
                st, zz, qc, kc, vc, gc, jnp.int32(2), fresh,
                jnp.int32(n_live), interpret=interpret))(state, z)
        jax.config.update("jax_default_matmul_precision", "highest")
        want = jax.jit(lambda st, zz: _twin(st, zz, qc, kc, vc, gc, 2, fresh,
                                            n_live))(state, z)
        jax.config.update("jax_default_matmul_precision", None)
        got = run(interp)
        r = {"y": rel(np.asarray(got[0])[:n_live],
                      np.asarray(want[0])[:n_live]),
             "state": rel(got[1][2], want[1]), "z": rel(got[2][2], want[2]),
             "others": float(jnp.abs(got[1][3] - state[3]).max())}
        if not interp:
            fn = jax.jit(lambda st, zz: R.retention_prefill_chunk(
                st, zz, qc, kc, vc, gc, jnp.int32(2), fresh,
                jnp.int32(n_live))[0])
            sec = timed(fn, state, z, reps=10)
            flops = peaks_brumby.prefill_chunk_flops(BrumbyConfig(), c)
            r.update(ms=sec * 1e3, tflops=flops / sec / 1e12,
                     share_of_197=flops / sec / 197e12)
        out[f"chunk_fresh_{fresh}"] = r
        print(f"chunk {c} fresh {fresh} live {n_live}: {r}", flush=True)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f, indent=1)


def _twin(state, z, q, k, v, g, slot, fresh, n_live):
    sub = min(R.SUB, q.shape[0])
    qt, kt, vt, end = R._fold_gates(q, k, v, g, n_live, sub)
    y, s, zz = R._chunk_xla(
        jnp.where(fresh, 0.0, state[slot]), jnp.where(fresh, 0.0, z[slot]),
        qt, kt, vt, end, q.shape[-1], 1e-6, sub)
    return y.transpose(2, 0, 1, 3), s, zz


if __name__ == "__main__":
    main()
