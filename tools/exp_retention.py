"""The retention kernels alone on the chip (ISSUE 33): each against its
plain-XLA twin at Brumby's widths, then timed. ``python3
tools/exp_retention.py [--rows 20 --live 15 --slabs 640,1664,8320]``;
prints one line a reading and writes them to
``chiprun_out/exp_retention.json``. Here, with ``JAX_PLATFORMS=cpu
--interpret --rows 3 --live 2 --chunk 256``, it runs the kernels in
interpret mode against the twins (no timing means anything)."""

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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20)
    ap.add_argument("--live", type=int, default=15)
    ap.add_argument("--chunk", type=int, default=1024)
    ap.add_argument("--slabs", default="640,1664,8320")
    ap.add_argument("--interpret", action="store_true")
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
    live = jnp.arange(b) % 4 != 3 if a.live >= b else jnp.arange(b) < a.live
    live = jnp.roll(live, 2)
    slots = jnp.where(live, 1 + jnp.arange(b), 0).astype(jnp.int32)

    # --- decode: kernel against twin --------------------------------
    want = jax.jit(lambda *x: R._decode_xla(*x, 1e-6))(
        state, z, q, k, v, g, slots, live)
    for slab in [int(s) for s in a.slabs.split(",")]:
        fn = jax.jit(lambda st, zz, *x, slab=slab: R._decode_pallas(
            st, zz, *x, 1e-6, slab, interp), donate_argnums=())
        got = fn(state, z, q, k, v, g, slots, live)
        lv = np.asarray(live)
        srows = np.asarray(slots)[lv]
        r = {"y": rel(np.asarray(got[0])[lv], np.asarray(want[0])[lv]),
             "state": rel(np.asarray(got[1])[srows],
                          np.asarray(want[1])[srows]),
             "z": rel(np.asarray(got[2])[srows], np.asarray(want[2])[srows]),
             "untouched": float(np.abs(
                 np.asarray(got[1])[1:][~lv] - np.asarray(state)[1:][~lv])
                 .max()) if (~lv).any() else 0.0}
        if not interp:
            don = jax.jit(lambda st, zz, *x, slab=slab: R._decode_pallas(
                st, zz, *x, 1e-6, slab, False)[1:], donate_argnums=(0, 1))
            st2, z2 = state + 0, z + 0
            jax.block_until_ready((st2, z2))
            st2, z2 = don(st2, z2, q, k, v, g, slots, live)
            jax.block_until_ready(st2)
            t0 = time.perf_counter()
            reps = 30
            for _ in range(reps):
                st2, z2 = don(st2, z2, q, k, v, g, slots, live)
            jax.block_until_ready(st2)
            sec = (time.perf_counter() - t0) / reps
            moved = R.decode_bytes(int(lv.sum()), HKV, N, N)
            r.update(ms=sec * 1e3, gb_s=moved / sec / 1e9,
                     share_of_819=moved / sec / 819e9)
        out[f"decode_slab_{slab}"] = r
        print(f"decode slab {slab}: {r}", flush=True)

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
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/exp_retention.json", "w") as f:
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
