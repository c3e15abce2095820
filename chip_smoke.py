"""chip_smoke.py — does bigdl_tpu still start on the chip?

One process drives the two main paths once, through the entry points a
user calls, at the full width of models the repo supports, with seeded
random weights:

- *train*: ResNet-50 (bf16, NHWC, batch 256, ImageNet shapes) through
  ``Engine.init()`` + ``Optimizer(...).optimize()`` over every local
  device, a few iterations;
- *serve*: ``LlamaConfig.mistral_7b()`` (hidden 4096, FFN 14336, 32
  layers, GQA 32/8, head_dim 128) with sym_int4 weights built on device,
  behind ``LLMServer(model, max_batch=8, max_seq_len=2048)`` in its
  default configuration, answering requests of 30-1,500 prompt tokens
  submitted in two waves so that prefills are admitted while other rows
  decode; then the first prompt again, alone.

It checks what comes out (see ``check`` calls), shows that the Pallas
kernels are inside the programs the engine compiled by reading those
executables' HLO, and prints compile seconds and phase wall times as
set-up facts — it measures no speed. Any failed check or exception ends
the process with a traceback and a non-zero code. Before either phase
it refuses to run unless ``jax.devices()[0].platform == "tpu"``.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The train phase runs first: what it leaves on the device is small, and
the serve phase then has the chip's memory for 4.4 GB of weights and a
2.1 GB page pool.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from importlib import metadata

import numpy as np

#: prompt lengths of the first wave (submitted together, so decode runs
#: with several live rows) and of the second (submitted once the first
#: is decoding, so their prefills are admitted between decode passes);
#: new-token budgets ride along. Second-wave lengths fall in suffix
#: buckets the first wave already compiled.
FIRST_WAVE = ((30, 64), (400, 56), (1500, 48), (120, 64))
SECOND_WAVE = ((1100, 40), (500, 32))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def fact(text: str) -> None:
    print(f"setup: {text}", flush=True)


class CacheEvents:
    """Counts JAX's persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def require_tpu() -> dict:
    """The device as JAX reports it; raises unless it is a TPU."""
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform="
            f"{device['platform']}, device_kind={device['kind']}, "
            f"count={device['count']}); nothing was run")
    return device


def report_compiles() -> None:
    from bigdl_tpu import observability as obs
    for rec in obs.compile_stats():
        for h in rec["history"]:
            fact(f"compile {rec['fn']} {h['compile_s']:.2f} s "
                 f"…{h['signature'][-96:]}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

class LossLog:
    """The optimizer's train-summary hook: keeps every step's loss."""

    def __init__(self):
        self.losses = []

    def add_scalar(self, tag: str, value: float, step: int):
        if tag == "Loss":
            self.losses.append(float(value))


def train_phase(depth: int = 50, image: int = 224, classes: int = 1000,
                batch: int = 256, iterations: int = 4) -> None:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import Engine
    from bigdl_tpu.models import resnet
    from bigdl_tpu.nn import ClassNLLCriterion
    from bigdl_tpu.optim import SGD, Optimizer, Trigger

    t0 = time.perf_counter()
    mesh = Engine.init()
    n_dev = len(jax.devices())
    check(mesh.devices.size == n_dev,
          f"Engine.init() mesh covers {mesh.devices.size} of {n_dev} "
          "devices")
    model = resnet.resnet_imagenet(depth=depth, class_num=classes,
                                   format="NHWC")
    model.load_parameters_dict(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        model.parameters_dict()))
    rs = np.random.RandomState(0)
    n = 2 * batch
    x = rs.random_sample((n, image, image, 3)).astype(jnp.bfloat16)
    y = (rs.randint(0, classes, n) + 1).astype(np.int32)

    opt = Optimizer(model, (x, y), ClassNLLCriterion(), batch_size=batch,
                    end_trigger=Trigger.max_iteration(iterations))
    opt.set_optim_method(SGD(learning_rate=0.1, momentum=0.9))
    log = LossLog()
    opt.set_train_summary(log)
    fact(f"train: {type(opt).__name__} over {n_dev} device(s), "
         f"ResNet-{depth} bf16 NHWC batch {batch}")

    # where one step's inputs live: the batch as the optimizer placed
    # it, the parameters as the step returned them
    seen = {}
    place, after = opt._place_batch, opt._after_iteration

    def place_and_note(xb, tb):
        out = place(xb, tb)
        seen["batch"] = out[0]
        return out

    def after_and_note(params, states, opt_state, state):
        seen["params"] = jax.tree_util.tree_leaves(params)[0]
        return after(params, states, opt_state, state)

    opt._place_batch, opt._after_iteration = place_and_note, after_and_note
    opt.optimize()

    for name in ("batch", "params"):
        devs = {s.device for s in seen[name].addressable_shards}
        fact(f"train: {name} shards on {len(devs)} device(s): "
             f"{sorted(d.id for d in devs)}")
        check(len(devs) == n_dev,
              f"{name} has shards on {len(devs)} devices, not on all "
              f"{n_dev}")
    fact(f"train: losses {[round(v, 4) for v in log.losses]}")
    check(len(log.losses) == iterations,
          f"{len(log.losses)} losses for {iterations} iterations")
    check(bool(np.all(np.isfinite(log.losses))), "a loss is not finite")
    check(log.losses[-1] != log.losses[0], "the loss did not change")
    fact(f"phase train wall {time.perf_counter() - t0:.1f} s")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def pallas_calls_in_engine_programs(pool_shape, layers) -> None:
    """Read the HLO of the executables the engine compiled: count the
    Mosaic custom calls in them, the copies of a whole page pool (a
    step that writes new K/V in place makes none; ``pool_shape`` is
    one shape or, for a family of several page classes, a list) and
    the slices of one layer out of a quantised weight stack (a step
    whose INT4 kernel indexes the stack makes none)."""
    from bigdl_tpu.llm.kvcache.write import (pool_shaped_copies,
                                             weight_slices)
    from bigdl_tpu.llm.serving import compiled_steps
    found = {}
    shapes = pool_shape if isinstance(pool_shape, list) else [pool_shape]
    for kind, detail, fn in compiled_steps():
        for _, exe in fn.executables():
            text = exe.as_text()
            n = text.count("tpu_custom_call")
            copies = [c for shape in shapes
                      for c in pool_shaped_copies(text, shape)]
            slices = weight_slices(text, layers)
            fact(f"serve: {fn.name} {detail} has {n} tpu_custom_call "
                 f"site(s), {len(copies)} copies shaped like the page "
                 f"pool {[tuple(x) for x in shapes]} and {len(slices)} slices of "
                 "one layer out of a quantised weight stack in its "
                 "compiled HLO")
            check(not copies,
                  f"{fn.name} {detail} copies the whole page pool: "
                  f"{[c[:200] for c in copies[:1]]}")
            check(not slices,
                  f"{fn.name} {detail} copies a layer's weights out of "
                  f"their stack: {[c[:200] for c in slices[:1]]}")
            found[kind] = min(found.get(kind, n), n)
    for kind in ("decode", "prefill_ragged"):
        check(kind in found,
              f"the engine recorded no executable of kind {kind!r} "
              f"(have {sorted(found)})")
        check(found[kind] > 0,
              f"a compiled {kind} program holds no tpu_custom_call: "
              "the Pallas kernels are not in it")


def serve_phase(cfg=None, max_seq_len: int = 2048, first=FIRST_WAVE,
                second=SECOND_WAVE, expect_pallas: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.llama import (LlamaConfig, LlamaForCausalLM,
                                            synthetic_q4_params)
    from bigdl_tpu.llm.serving import LLMServer

    t0 = time.perf_counter()
    if cfg is None:
        cfg = LlamaConfig.mistral_7b()
    params = jax.block_until_ready(synthetic_q4_params(cfg, seed=0))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(params))
    fact(f"serve: {cfg.num_hidden_layers} layers, hidden "
         f"{cfg.hidden_size}, FFN {cfg.intermediate_size}, heads "
         f"{cfg.num_attention_heads}/{cfg.num_key_value_heads}, window "
         f"{cfg.sliding_window}; {nbytes / 2**30:.2f} GiB of sym_int4 "
         f"params built on device in {time.perf_counter() - t0:.1f} s")
    fact("serve: LLMServer is one chip by design (several chips are "
         f"served by one replica each); it runs on {jax.devices()[0]}")
    # the dense (unpaged) forward below needs a cache no longer than
    # the shortest prompt's bucket; the paged server sizes its own pool
    model = LlamaForCausalLM(cfg, params, max_cache_len=64)

    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in first + second]
    budgets = [m for _, m in first + second]

    srv = LLMServer(model, max_batch=8, max_seq_len=max_seq_len).start()
    try:
        reqs = [srv.submit(p, max_new_tokens=m)
                for p, m in zip(prompts[:len(first)], budgets)]
        # second wave once the first is decoding
        deadline = time.monotonic() + 900
        while not all(r.tokens for r in reqs):
            check(time.monotonic() < deadline and
                  not any(r.error for r in reqs),
                  f"first wave never started decoding: "
                  f"{[r.error for r in reqs]}")
            time.sleep(0.005)
        late = [srv.submit(p, max_new_tokens=m)
                for p, m in zip(prompts[len(first):],
                                budgets[len(first):])]
        reqs += late
        # watch the engine from outside: rows decoding at once, and
        # whether a late request got its first token (its prefill ran)
        # while an earlier one was still decoding
        peak_live, overlapped, started = 0, False, set()
        while not all(r.done.is_set() for r in reqs):
            live = [r for r in reqs if r.tokens and not r.done.is_set()]
            peak_live = max(peak_live, len(live))
            for r in late:
                if r.tokens and r.id not in started:
                    started.add(r.id)
                    overlapped |= any(e is not r for e in live)
            check(time.monotonic() < deadline, "requests timed out")
            time.sleep(0.002)
        outs = [r.get(timeout=60) for r in reqs]
        fact(f"serve: peak rows decoding at once {peak_live}; a prefill "
             f"was admitted while others decoded: {overlapped}")
        again = srv.submit(prompts[0],
                           max_new_tokens=budgets[0]).get(timeout=600)
        pass_errors = srv.pass_errors
        pool_shape = srv._k_pages.shape
    finally:
        srv.stop()

    for i, (out, m) in enumerate(zip(outs, budgets)):
        check(len(out) == m, f"request {i} returned {len(out)} ids, "
              f"asked for {m}")
        check(all(0 <= t < cfg.vocab_size for t in out),
              f"request {i} returned an id outside [0, {cfg.vocab_size})")
    check(peak_live >= 3, f"only {peak_live} rows ever decoded together")
    check(overlapped, "no prefill was admitted while others decoded")
    check(again == outs[0],
          f"the repeated prompt gave {again[:8]}…, first time "
          f"{outs[0][:8]}…")
    check(pass_errors == 0, f"{pass_errors} engine passes raised")

    # reference: the dense forward (XLA attention over a contiguous
    # cache, no page pool, no attention kernel) must put the server's
    # first token of the shortest prompt at the top of its logits
    logits, _ = model(jnp.asarray(prompts[0])[None])
    ref = np.asarray(logits[0, -1], np.float32)
    check(bool(np.all(np.isfinite(ref))), "reference logits not finite")
    margin = float((ref.max() - ref[outs[0][0]]) / ref.std())
    fact(f"serve: server's first token {outs[0][0]}, reference argmax "
         f"{int(ref.argmax())}; the server's token sits {margin:.4f} "
         "logit-sigmas below the reference maximum")
    check(margin <= 0.1, f"the server's first token is {margin:.3f} "
          "sigmas below the dense reference's best")

    if expect_pallas:
        pallas_calls_in_engine_programs(pool_shape, params["layers"])
    fact(f"phase serve wall {time.perf_counter() - t0:.1f} s")


#: the hybrid phase's waves: prompts on both sides of a prefill chunk
#: (1,024 tokens) and of the ring (256 positions), decode past a wrap
HYBRID_FIRST = ((300, 300), (5000, 48), (1100, 64), (40, 64))
HYBRID_SECOND = ((2500, 40), (700, 32))


def serve_hybrid_phase(first=HYBRID_FIRST, second=HYBRID_SECOND,
                       cfg=None, num_pages: int = 6000,
                       max_seq_len: int = 8192, max_batch: int = 32,
                       expect_pallas: bool = True) -> None:
    """ISSUE 31: the ``mimo_v2`` family (window and full layers in two
    page classes, an expert-parallel share) at MiMo-V2.5's published
    widths, layer 0 and one period, 16 of 256 experts held, bf16,
    through ``LLMServer`` in its default configuration."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models import mimo
    from bigdl_tpu.llm.serving import LLMServer, _PAGED_STEP_CACHE

    t0 = time.perf_counter()
    if cfg is None:
        cfg = mimo.MimoConfig(
            num_hidden_layers=7, hybrid_layer_pattern=(0, 1, 1, 1, 1, 0, 1),
            moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), experts_held=16)
    _PAGED_STEP_CACHE.clear()       # the Llama phase's programs
    params = jax.block_until_ready(mimo.init_params(cfg, seed=0))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(params))
    fact(f"hybrid: {cfg.num_hidden_layers} layers "
         f"{cfg.hybrid_layer_pattern}, hidden {cfg.hidden_size}, heads "
         f"{cfg.num_attention_heads}/{cfg.num_key_value_heads} full and /"
         f"{cfg.swa_num_key_value_heads} window {cfg.sliding_window}, K "
         f"{cfg.head_dim} / V {cfg.v_head_dim}, experts "
         f"{cfg.experts_held} of {cfg.n_routed_experts} held; "
         f"{nbytes / 2**30:.2f} GiB of bfloat16 params built on device in "
         f"{time.perf_counter() - t0:.1f} s")
    # the dense forward below takes the first prompt and its answer
    model = mimo.MimoForCausalLM(cfg, params,
                                 max_cache_len=sum(first[0]) + 8)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n, _ in first + second]
    budgets = [m for _, m in first + second]
    # 32 slots, as the benchmark's engine: the window class's pool is
    # then 252 MB. With 8 slots it is 63 MB, under the 128 MiB of VMEM,
    # and XLA's memory-space assignment moves it there and back around
    # every prefill, which the check below reads as a copy of the pool
    # (seen on the chip and in a compile for it made without one)
    srv = LLMServer(model, max_batch=max_batch, max_seq_len=max_seq_len,
                    num_pages=num_pages).start()
    try:
        reqs = [srv.submit(p, max_new_tokens=m)
                for p, m in zip(prompts[:len(first)], budgets)]
        deadline = time.monotonic() + 1500
        while not all(r.tokens for r in reqs):
            check(time.monotonic() < deadline and
                  not any(r.error for r in reqs),
                  f"first wave never started decoding: "
                  f"{[r.error for r in reqs]}")
            time.sleep(0.005)
        reqs += [srv.submit(p, max_new_tokens=m)
                 for p, m in zip(prompts[len(first):],
                                 budgets[len(first):])]
        outs = [r.get(timeout=1500) for r in reqs]
        while not srv.engine_idle():    # a request is done before its
            time.sleep(0.005)           # pages are back
        held = dict(srv.pages_in_use_by_class)
        counters = dict(srv.step_counters)
        pass_errors = srv.pass_errors
        pool_shapes = [list(p.shape) for p in srv._k_pages]
        ring = srv._rings[0].ring
    finally:
        srv.stop()
    for i, (out, m) in enumerate(zip(outs, budgets)):
        check(len(out) == m, f"request {i} returned {len(out)} ids, "
              f"asked for {m}")
    check(pass_errors == 0, f"{pass_errors} engine passes raised")
    check(held == {"full": 0, "window": 0},
          f"pages still held after every request finished: {held}")
    k = cfg.num_experts_per_tok
    check(counters["moe_assignments_total"]
          + counters["moe_assignments_elsewhere_total"]
          == k * counters["moe_token_layers_total"],
          f"assignments here and elsewhere do not add up to {k} a "
          f"token and expert layer: {counters}")
    check(counters["window_pages_held_total"]
          <= ring * counters["decode_rows_total"],
          f"a row held more window-class pages than its ring's {ring}")
    fact(f"hybrid: counters {counters}")
    # the dense forward (contiguous caches, no page, no ring, no
    # kernel) over the prompt that decoded past the ring's wrap
    ids = np.concatenate([prompts[0], np.asarray(outs[0][:-1], np.int32)])
    logits, _ = model(jnp.asarray(ids)[None])
    ref = np.asarray(logits[0, len(prompts[0]) - 1:], np.float32)
    check(bool(np.all(np.isfinite(ref))), "reference logits not finite")
    picked = ref[np.arange(len(outs[0])), np.asarray(outs[0])]
    margin = (ref.max(-1) - picked) / ref.std(-1)
    fact(f"hybrid: {len(outs[0])} served tokens after a "
         f"{len(prompts[0])}-token prompt sit at most {margin.max():.4f} "
         f"(mean {margin.mean():.4f}) logit-sigmas below the dense "
         f"forward's maximum; {int((margin == 0).sum())} are its argmax")
    check(float(margin.max()) <= 1.0 and float(margin.mean()) <= 0.1,
          f"served tokens are {margin.max():.3f} sigmas (mean "
          f"{margin.mean():.3f}) below the dense forward's best")
    if expect_pallas:
        pallas_calls_in_engine_programs(
            pool_shapes + [[s[0] * s[1]] + s[2:] for s in pool_shapes],
            dict(enumerate(params["layers"])))
    fact(f"phase hybrid wall {time.perf_counter() - t0:.1f} s")


def main() -> int:
    t0 = time.perf_counter()
    import bigdl_tpu  # noqa: F401 — places the compile cache first
    import jax
    import jaxlib

    cache = CacheEvents()
    device = require_tpu()
    fact(f"platform={device['platform']} device_kind={device['kind']} "
         f"devices={device['count']} jax={jax.__version__} "
         f"jaxlib={jaxlib.__version__} "
         f"libtpu={metadata.version('libtpu')}")
    fact(f"compile cache dir {jax.config.jax_compilation_cache_dir}")

    train_phase()
    gc.collect()
    in_use = jax.devices()[0].memory_stats()["bytes_in_use"]
    fact(f"device memory in use between the phases: "
         f"{in_use / 2**20:.0f} MiB")
    serve_phase()
    gc.collect()
    in_use = jax.devices()[0].memory_stats()["bytes_in_use"]
    fact(f"device memory in use between the serving phases: "
         f"{in_use / 2**20:.0f} MiB")
    serve_hybrid_phase()

    report_compiles()
    fact(f"compile cache: {cache.hits} hit(s), {cache.misses} miss(es)")
    fact(f"total wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
