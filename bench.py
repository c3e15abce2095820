"""Benchmark entry point — prints ONE JSON line for the driver.

Headline: ResNet-50 ImageNet-shape synchronous training throughput in
images/sec/chip (BASELINE.json north-star config 2) in bf16, with MFU
computed from XLA's compiled cost analysis and asserted ``<= 1.0``.
Every timed window closes with a device-to-host fetch of a value that
data-depends on the whole loop (donated params chain each step to the
next): the fetch cannot complete before the compute has run, on any
runtime.

The default run also folds in the second north star (BASELINE config 5,
Llama-2-7B q4_0 decode tokens/sec) plus an int4-vs-dense matmul kernel
micro-bench under ``extra``, so one driver invocation records all of it.

The reference published no harvestable numbers (BASELINE.md):
``vs_baseline`` is ``null``. ``--quick`` shrinks configs for CPU smoke
runs and prefixes metric names with ``smoke_`` so dashboards never ingest
smoke numbers as flagship results; ``--cpu`` forces the CPU backend
(the same as ``JAX_PLATFORMS=cpu``). The process exits non-zero when any
phase recorded an error.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np

def _peak_flops(device) -> float | None:
    """Peak dense bf16 FLOP/s of ``device`` from the repo's one peaks
    table (observability/utilization.py); None off-TPU, an error for a
    TPU kind the table does not hold."""
    from bigdl_tpu.observability.utilization import peak_spec
    spec = peak_spec(device)
    return spec[0] * 1e12 if spec else None


def _cost_analysis(compiled) -> dict:
    """XLA cost analysis as a plain dict (version-tolerant)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca or {}


def _flops_of(compiled) -> float | None:
    flops = _cost_analysis(compiled).get("flops")
    return float(flops) if flops else None


def _bench_train(model, make_batch, metric: str, batch_size: int,
                 warmup: int, iters: int, lr: float, optim,
                 extra: dict, unit: str = "images/sec/chip",
                 n_batches: int = 4) -> dict:
    """Shared train-step timing harness: jit+donate, warmup, timed loop.

    The timed window ends with a host fetch of the final loss scalar; the
    loss of iteration i depends (via donated params) on every iteration
    before it, so the fetch bounds the true wall-clock of all ``iters``
    steps regardless of how the runtime implements readiness.
    """
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn import ClassNLLCriterion

    criterion = ClassNLLCriterion()
    params = jax.tree_util.tree_map(jnp.asarray, model.parameters_dict())
    states = jax.tree_util.tree_map(jnp.asarray, model.states_dict())
    opt_state = jax.tree_util.tree_map(jnp.asarray,
                                       optim.init_state(params))

    def train_step(params, states, opt_state, x, t, rng):
        def loss_fn(p):
            y, s2 = model.apply(p, states, x, training=True, rng=rng)
            return criterion.apply_loss(y.astype(jnp.float32), t), s2

        (loss, new_states), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_opt = optim.step(params, grads, opt_state, lr)
        return new_params, new_states, new_opt, loss

    # ISSUE 3: the flight-recorder wrapper records this step's compile
    # time and cost/memory analysis under bench/<metric> in the
    # telemetry block, so the MFU below is attributed to the executable
    # that actually ran (the bigdl_xla_* gauges carry the same numbers)
    from bigdl_tpu import observability as obs
    step = obs.compiled(train_step, name=f"bench/{metric}",
                        donate_argnums=(0, 1, 2))
    # rotate over several distinct batches so the loop is not single-batch
    # memorization (VERDICT r1 weak #10)
    batches = [make_batch() for _ in range(n_batches)]
    from bigdl_tpu.utils.engine import train_rng_key
    key = train_rng_key(0)   # hardware RBG on TPU: threefry dropout
    # masks alone cost ~40% of a BERT step (see engine.train_rng_key)

    for i in range(warmup):
        key, sub = jax.random.split(key)
        x, t = batches[i % n_batches]
        params, states, opt_state, loss = step(params, states, opt_state,
                                               x, t, sub)
    float(loss)  # full sync before the timed window opens

    t0 = time.perf_counter()
    for i in range(iters):
        key, sub = jax.random.split(key)
        x, t = batches[i % n_batches]
        params, states, opt_state, loss = step(params, states, opt_state,
                                               x, t, sub)
    final_loss = float(loss)  # host fetch closes the window
    dt = time.perf_counter() - t0

    # per-step latency, synchronously (separate from the pipelined window)
    sync_times = []
    for _ in range(min(10, iters)):
        key, sub = jax.random.split(key)
        s0 = time.perf_counter()
        params, states, opt_state, loss = step(params, states, opt_state,
                                               *batches[0], sub)
        float(loss)
        sync_times.append(time.perf_counter() - s0)

    # cost analysis comes from the flight recorder's ledger — i.e. from
    # the very executable the loop above dispatched (attributed, and no
    # duplicate compile). Manual lower+compile only as the fallback when
    # the recorder saw nothing (observability disabled).
    entry = {}
    stats_fn = getattr(step, "stats", None)
    if stats_fn is not None:
        hist = stats_fn()["history"]
        entry = hist[0] if hist else {}
    flops_per_step = entry.get("flops")
    bytes_per_step = entry.get("bytes_accessed")
    if flops_per_step is None:
        key, sub = jax.random.split(key)
        ca = _cost_analysis(step.lower(params, states, opt_state,
                                       *batches[0], sub).compile())
        flops_per_step = float(ca.get("flops") or 0) or None
        bytes_per_step = float(ca.get("bytes accessed") or 0) or None

    dev = jax.devices()[0]
    peak = _peak_flops(dev)
    mfu = None
    if peak and flops_per_step:
        mfu = flops_per_step * iters / dt / peak
        assert mfu <= 1.0, (
            f"measured MFU {mfu:.2%} exceeds hardware peak — the timing is "
            f"broken (flops/step={flops_per_step:.3e}, steps/s={iters/dt:.2f}, "
            f"peak={peak:.3e} FLOP/s on {dev.device_kind}); refusing to "
            f"report an impossible number")

    return {
        "metric": metric,
        "value": round(batch_size * iters / dt, 2),
        "unit": unit,
        "vs_baseline": None,  # no reference number harvestable (BASELINE.md)
        "extra": {**extra, "batch_size": batch_size, "iters": iters,
                  "step_ms": round(dt / iters * 1e3, 3),
                  "step_ms_sync_median": round(
                      float(np.median(sync_times)) * 1e3, 3),
                  "flops_per_step": flops_per_step,
                  "bytes_per_step": bytes_per_step,
                  "implied_hbm_gbs": (round(
                      bytes_per_step * iters / dt / 1e9, 1)
                      if bytes_per_step else None),
                  "achieved_tflops": (round(flops_per_step * iters / dt / 1e12,
                                            2) if flops_per_step else None),
                  "mfu": round(mfu, 4) if mfu is not None else None,
                  "peak_flops": peak,
                  "device_kind": getattr(dev, "device_kind", str(dev)),
                  "backend": jax.default_backend(),
                  "final_loss": final_loss},
    }


def bench_lenet_train(batch_size: int = 512, warmup: int = 5,
                      iters: int = 50) -> dict:
    import jax.numpy as jnp

    from bigdl_tpu.models import lenet
    from bigdl_tpu.optim.optim_method import SGD

    rs = np.random.RandomState(0)

    def make_batch():
        x = jnp.asarray(rs.rand(batch_size, 28 * 28).astype(np.float32))
        t = jnp.asarray((rs.randint(0, 10, batch_size) + 1)
                        .astype(np.int32))
        return x, t

    return _bench_train(lenet.build_model(10), make_batch,
                        "lenet_mnist_train_throughput", batch_size,
                        warmup, iters, 0.05, SGD(learning_rate=0.05),
                        extra={})


def bench_resnet50_train(batch_size: int = 256, warmup: int = 5,
                         iters: int = 40, image: int = 224,
                         depth: int = 50, classes: int = 1000,
                         smoke: bool = False,
                         format: str = "NHWC",
                         remat: bool = False) -> dict:
    """North-star: ResNet train-step throughput, bf16 params/compute.

    Default NHWC (channels on the TPU lane dim) at batch 256. The step
    is HBM-traffic-bound (cost analysis: ~43 GB accessed / 3.0 TFLOP at
    batch 128 — the byte roofline, not the MXU, sets the ceiling), so
    the wins came from single-pass f32 BN stats + fused scale/shift BN
    (bigdl_tpu.nn BatchNormalization) and batch size; remat=True trades
    FLOPs for bytes but measured net-negative on this model, so it
    stays opt-in.

    Round-5 close-out of the bytes diet (VERDICT r4 item 7): the batch
    sweep is complete — 256 → 2545-2559 img/s (768-773 GB/s implied,
    94% of the 819 GB/s spec); 288 → 2343; 320 → 2378; 384 → 2451;
    512 → 2402. Non-256 batches tile worse, every activation is
    already bf16, BN is a single fused pass, and remat is
    net-negative, so the residual ~6% between implied and spec
    bandwidth is scheduling overhead XLA owns, not removable bytes.
    The ~2550 img/s figure is this model/chip's measured ceiling."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models import resnet
    from bigdl_tpu.optim.optim_method import SGD

    model = resnet.resnet_imagenet(depth=depth, class_num=classes,
                                   format=format, remat=remat)
    rs = np.random.RandomState(0)
    shape = ((batch_size, 3, image, image) if format == "NCHW"
             else (batch_size, image, image, 3))

    def make_batch():
        x = jnp.asarray(rs.rand(*shape), jnp.bfloat16)
        t = jnp.asarray((rs.randint(0, classes, batch_size) + 1)
                        .astype(np.int32))
        return x, t

    # bf16 params: the MXU-native dtype
    model.load_parameters_dict(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        model.parameters_dict()))
    name = "resnet50_imagenet_train_throughput"
    return _bench_train(model, make_batch,
                        ("smoke_" + name) if smoke else name,
                        batch_size, warmup, iters, 0.1,
                        SGD(learning_rate=0.1, momentum=0.9),
                        extra={"image": image, "depth": depth,
                               "dtype": "bfloat16", "format": format,
                               "remat": remat})


def bench_bert_finetune(batch_size: int = 64, seq_len: int = 128,
                        warmup: int = 5, iters: int = 50,
                        smoke: bool = False) -> dict:
    """BASELINE config 4: BERT-base fine-tune step throughput on OUR nn
    stack (not a host torch loop), bf16 params. Batch sweep closed out
    in r5: 64 → 1514-1554 samples/s (MFU 0.52-0.53), 96 → 1489,
    128 → 1438 — 64 is the measured optimum."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.models.bert import BertConfig, build_classifier
    from bigdl_tpu.nn.module import set_seed
    from bigdl_tpu.optim.optim_method import AdamWeightDecay

    set_seed(0)
    cfg = BertConfig.tiny() if smoke else BertConfig.base()
    model = build_classifier(cfg, num_labels=2)
    model.load_parameters_dict(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 else a, model.parameters_dict()))
    rs = np.random.RandomState(0)
    sl = min(seq_len, cfg.max_position_embeddings)

    def make_batch():
        x = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch_size, sl)),
                        jnp.int32)
        t = jnp.asarray((rs.randint(0, 2, batch_size) + 1), jnp.int32)
        return x, t

    name = "bert_base_finetune_throughput"
    return _bench_train(model, make_batch,
                        ("smoke_" + name) if smoke else name,
                        batch_size, warmup, iters, 2e-5,
                        AdamWeightDecay(learning_rate=2e-5),
                        extra={"seq_len": sl, "dtype": "bfloat16"},
                        unit="samples/sec/chip")


def bench_lenet_convergence(epochs: int = 16, batch: int = 256,
                            lr: float = 1e-3) -> dict:
    """BASELINE config 1 as a TRAINING TARGET with a FALSIFIABLE metric
    (VERDICT r4 missing #2): LeNet-5 through the full Optimizer facade
    on the Bayes-calibrated hard synthetic set — nearest-prototype
    (≈Bayes) tops out at ~0.96 by construction, so a healthy run lands
    in [0.90, 0.99) and a subtly broken optimizer/loss/init falls out
    of the band (the lr=0 lamed control is asserted failing in
    tests/test_convergence_falsifiable.py). Real MNIST is read from
    disk when present; this environment has no network."""
    from bigdl_tpu.feature.dataset import DataSet
    from bigdl_tpu.feature.mnist import (load_mnist,
                                         nearest_prototype_accuracy,
                                         normalize)
    from bigdl_tpu.models import lenet
    from bigdl_tpu.optim import (Adam, Optimizer, Top1Accuracy, Trigger)
    import bigdl_tpu.nn as nn

    xtr, ytr = load_mnist(train=True, synthetic_size=16384, hard=True)
    xte, yte = load_mnist(train=False, synthetic_size=2048, hard=True)
    bayes_ref = nearest_prototype_accuracy(xte, yte)
    xtr = normalize(xtr).reshape(-1, 784)
    xte = normalize(xte).reshape(-1, 784)
    model = lenet.build_model(10)
    opt = Optimizer(model, DataSet.array(xtr, ytr),
                    nn.ClassNLLCriterion(), batch_size=batch,
                    end_trigger=Trigger.max_epoch(epochs),
                    distributed=False)
    opt.set_optim_method(Adam(learning_rate=lr))
    t0 = time.perf_counter()
    trained = opt.optimize()
    dt = time.perf_counter() - t0
    from bigdl_tpu.optim import Evaluator
    acc = Evaluator(trained).evaluate((xte, yte), [Top1Accuracy()])[0]
    val = round(float(acc.result), 4)
    band = [0.90, 0.99]
    return {"metric": "lenet_convergence_top1", "value": val,
            "unit": "accuracy", "vs_baseline": None,
            "extra": {"epochs": epochs, "train_s": round(dt, 1),
                      "train_size": len(xtr), "test_size": len(xte),
                      "dataset": "synthetic-mnist-hard (Bayes-calibrated "
                                 "sigma, ceiling ~0.96; no network)",
                      "bayes_ref_top1": round(bayes_ref, 4),
                      "band": band,
                      "in_band": bool(band[0] <= val < band[1]),
                      "final_loss": opt.state["loss"]}}


def bench_cifar_convergence(epochs: int = 12, batch: int = 256,
                            lr: float = 2e-3) -> dict:
    """BASELINE config 2's cheap accuracy twin: ResNet-20/CIFAR through
    the Optimizer facade on the Bayes-calibrated hard synthetic set
    (same falsifiable-band design as bench_lenet_convergence; test draw
    is disjoint from train — seed+1)."""
    from bigdl_tpu.feature.cifar import (load_cifar,
                                         nearest_prototype_accuracy)
    from bigdl_tpu.feature.dataset import DataSet
    from bigdl_tpu.models import resnet
    from bigdl_tpu.optim import (Adam, Evaluator, Optimizer, Top1Accuracy,
                                 Trigger)
    import bigdl_tpu.nn as nn

    xtr, ytr = load_cifar(train=True, synthetic_size=8192, hard=True)
    xte, yte = load_cifar(train=False, synthetic_size=2048, hard=True)
    bayes_ref = nearest_prototype_accuracy(xte, yte)
    model = resnet.resnet_cifar(depth=20, class_num=10)
    opt = Optimizer(model, DataSet.array(xtr, ytr),
                    nn.ClassNLLCriterion(), batch_size=batch,
                    end_trigger=Trigger.max_epoch(epochs),
                    distributed=False)
    opt.set_optim_method(Adam(learning_rate=lr))
    t0 = time.perf_counter()
    trained = opt.optimize()
    dt = time.perf_counter() - t0
    acc = Evaluator(trained).evaluate((xte, yte), [Top1Accuracy()])[0]
    val = round(float(acc.result), 4)
    band = [0.90, 0.99]
    return {"metric": "cifar_resnet20_convergence_top1", "value": val,
            "unit": "accuracy", "vs_baseline": None,
            "extra": {"epochs": epochs, "train_s": round(dt, 1),
                      "train_size": len(xtr), "test_size": len(xte),
                      "dataset": "synthetic-cifar-hard (Bayes-calibrated "
                                 "sigma, ceiling ~0.96; no network)",
                      "bayes_ref_top1": round(bayes_ref, 4),
                      "band": band,
                      "in_band": bool(band[0] <= val < band[1]),
                      "final_loss": opt.state["loss"]}}


def _q4_param_bytes(cfg) -> int:
    """On-device bytes of the quantized decoder weights that each decoded
    token must stream from HBM (q nibbles + f32 scales), for the
    bandwidth-roofline sanity number."""
    from bigdl_tpu.llm.ggml.quantize import QK
    from bigdl_tpu.llm.models.llama import _LAYER_LINEARS, linear_shapes

    shapes = linear_shapes(cfg)
    L = cfg.num_hidden_layers
    total = 0
    for name in _LAYER_LINEARS:
        n, k = shapes[name]
        total += L * (n * k // 2 + n * (k // QK) * 4)
    # lm_head quantized too
    h = cfg.hidden_size
    total += cfg.vocab_size * h // 2 + cfg.vocab_size * (h // QK) * 4
    return total


def bench_llama_int4_decode(model_size: str = "7b", batch: int = 1,
                            prompt_len: int = 128, decode_tokens: int = 96,
                            max_cache: int = 512,
                            smoke: bool = False) -> dict:
    """North-star 2: Llama q4_0 decode throughput.

    The token loop is llama.decode_scan — ONE compiled program per
    window, donated kv cache. The harness decodes two windows of
    different lengths and reports the SLOPE (per-token time net of the
    fixed dispatch/fetch overhead of one call), threading the rng key +
    cache through from window to window."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.llama import (
        LlamaConfig, LlamaForCausalLM, synthetic_q4_params)

    cfg = {"7b": LlamaConfig.llama2_7b,
           "8b": LlamaConfig.llama3_8b,
           "tiny": LlamaConfig.tiny}[model_size]()
    limit = min(max_cache, cfg.max_position_embeddings)
    n_small = max(decode_tokens // 4, 8)
    need = 2 * (decode_tokens + n_small) + 4
    prompt_len = max(8, min(prompt_len, limit - need))
    params = synthetic_q4_params(cfg)
    model = LlamaForCausalLM(cfg, params, max_cache_len=limit)

    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, prompt_len)),
                      jnp.int32)

    # prefill throughput as a SLOPE between two prompt lengths, netting
    # out the fixed dispatch/fetch overhead of one call exactly like the
    # decode windows below
    def prefill_time(plen):
        pids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, plen)),
                           jnp.int32)
        lg, ch = model(pids)            # compile for this length
        int(np.asarray(jnp.argmax(lg[0, -1])))
        pids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, plen)),
                           jnp.int32)
        t0 = time.perf_counter()
        lg, ch = model(pids)
        int(np.asarray(jnp.argmax(lg[0, -1])))
        return time.perf_counter() - t0, lg, ch

    p_small = max(prompt_len // 4, 8)
    t_small, _, _ = prefill_time(p_small)
    t_full, logits, cache = prefill_time(prompt_len)
    # wall number includes the dispatch/fetch overhead AND the
    # one-off 4 GB weight stream; the marginal slope shows the per-token
    # cost once weights are flowing (prefill is weight-stream-bound at
    # these lengths, so the two differ by orders of magnitude)
    prefill_tok_s = batch * prompt_len / max(t_full, 1e-9)
    marginal = (batch * (prompt_len - p_small) / (t_full - t_small)
                if t_full > t_small else None)
    prefill_s = t_full

    key = jax.random.PRNGKey(0)
    last = logits[:, -1]
    temp = jnp.float32(1.0)

    if model.paged_decode:
        # the product-default decode path (round 5): dense prefill
        # bridged into the paged token loop — attention reads live
        # pages, not the max_cache window
        from bigdl_tpu.llm.models.llama import pageify_cache
        kp, vp, bt = pageify_cache(cache, page=model.page_size)
        state = [kp, vp, cache["pos"], last, key]
        del cache

        def window(n):
            kp, vp, pos, last, key = state
            t0 = time.perf_counter()
            toks, kp, vp, pos, last, key, _ = model._decode_scan_paged(
                model.params, kp, vp, bt, pos, last, key, temp,
                page=model.page_size, num_tokens=n, do_sample=True,
                top_k=0, eos_token_id=None)
            int(np.asarray(toks)[0, -1])  # host fetch closes the window
            state[:] = [kp, vp, pos, last, key]
            return time.perf_counter() - t0

        decode_mode = "paged_scan"
    else:
        state = [cache, last, key]

        def window(n):
            cache, last, key = state
            t0 = time.perf_counter()
            toks, cache, last, key, _ = model._decode_scan(
                model.params, cache, last, key, temp, num_tokens=n,
                do_sample=True, top_k=0, eos_token_id=None)
            int(np.asarray(toks)[0, -1])
            state[:] = [cache, last, key]
            return time.perf_counter() - t0

        decode_mode = "fused_scan"

    # compile both window sizes before timing
    for n in (n_small, decode_tokens):
        window(n)
    t_small = window(n_small)
    t_big = window(decode_tokens)

    per_tok = (t_big - t_small) / (decode_tokens - n_small)
    if per_tok <= 0:  # noisy tenancy: fall back to the big-window mean
        per_tok = t_big / decode_tokens
    tok_s = batch / per_tok
    weight_bytes = _q4_param_bytes(cfg)
    hbm_gbs = tok_s * weight_bytes / 1e9  # lower bound: weights re-read/token

    name = "llama2_7b_int4_decode_throughput"
    return {
        "metric": ("smoke_" + name) if smoke else name,
        "value": round(tok_s, 2),
        "unit": "tokens/sec",
        "vs_baseline": None,  # no reference number harvestable (BASELINE.md)
        "extra": {
            "model": model_size, "batch": batch, "prompt_len": prompt_len,
            "decode_tokens": decode_tokens, "qtype": "sym_int4",
            "step_ms": round(per_tok * 1e3, 3),
            "window_s": [round(t_small, 3), round(t_big, 3)],
            "weight_bytes": weight_bytes,
            "implied_hbm_gbs": round(hbm_gbs, 1),
            "prefill_tokens_per_s": round(prefill_tok_s, 1),
            "prefill_marginal_tokens_per_s": (round(marginal, 1)
                                              if marginal else None),
            "prefill_s": round(prefill_s, 3),
            "decode_mode": decode_mode,
            "matmuls_per_layer": 4,     # qkv, o, gate_up, down (fused)
            "layer_scan_unroll": 1,     # rolled scan measured fastest
            # measured in-context matmul-only floor on v5e: 28.6 ms/tok
            # (34.9 tok/s) — the m=1 kernel is dequant-rate-bound at
            # ~200 GB/s packed (see int4_matmul.py header); fusion and
            # unrolling are perf-neutral/negative within tenancy noise
            "matmul_floor_ms": 28.6,
            "backend": jax.default_backend(),
        },
    }


def bench_llama_longctx_prefill(prompt_len: int = 4096,
                                model_size: str = "7b") -> dict:
    """Long-context north star: 7B q4_0 prefill at 4k on one chip via
    the blockwise online-softmax attention path (the (T, S) score
    matrix never materializes past one attn_block_size column — what
    lets 4k+ fit beside 4.1 GB of weights). Throughput reported as the
    slope between half- and full-length prompts so the fixed
    dispatch/fetch roundtrip cancels."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.models.llama import (
        LlamaConfig, LlamaForCausalLM, synthetic_q4_params)

    cfg = {"7b": LlamaConfig.llama2_7b,
           "tiny": LlamaConfig.tiny}[model_size]()
    limit = min(prompt_len, cfg.max_position_embeddings)
    params = synthetic_q4_params(cfg)
    model = LlamaForCausalLM(cfg, params, max_cache_len=limit)
    rs = np.random.RandomState(0)

    def run(plen):
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, plen)),
                          jnp.int32)
        lg, _ = model(ids)              # compile
        int(np.asarray(jnp.argmax(lg[0, -1])))
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, plen)),
                          jnp.int32)
        t0 = time.perf_counter()
        lg, _ = model(ids)
        int(np.asarray(jnp.argmax(lg[0, -1])))
        return time.perf_counter() - t0

    t_half = run(limit // 2)
    t_full = run(limit)
    marginal = ((limit - limit // 2) / (t_full - t_half)
                if t_full > t_half else None)   # dispatch-dominated:
    # a noise-driven slope would print nonsense throughput
    name = f"llama_{model_size}_int4_prefill_{limit}"
    return {"metric": ("llama2_7b_int4_prefill_4k"
                       if model_size == "7b" and limit == 4096
                       else name),
            "value": round(limit / t_full, 1),
            "unit": "tokens/sec",
            "vs_baseline": None,
            "extra": {"prompt_len": limit,
                      "wall_s": round(t_full, 3),
                      "marginal_tokens_per_s": (round(marginal, 1)
                                                if marginal else None),
                      "attn_block_size": cfg.attn_block_size,
                      "backend": jax.default_backend()}}


def bench_paged_decode_step(batch: int = 8, ctx_len: int = 256,
                            page_size: int = 16,
                            model_size: str = "7b") -> dict:
    """Paged-KV serving decode at 7B scale ON CHIP — EXACTLY the step
    LLMServer compiles (serving.paged_decode_step: rolled layer scan,
    read-only pools inside the scan, one post-scan scatter), timed as K
    greedy-feedback steps inside one jit: the device cost of the step
    alone, with no host in the loop. What the live server achieves per
    token (it fetches a token vector every pass) is a different number
    and is not measured here.

    Round-4's version python-unrolled 32 layers inside the fori body —
    the compile alone outran a 20-minute budget and the structure was
    the ledger's measured -18% shape (int4_matmul.py header). The shared
    scanned step compiles in seconds and pipelines the weight stream
    like the fused-scan path; ``compile_s`` is reported so the warm-up
    cost is itself evidence."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.kernels.paged_attention import LANE
    from bigdl_tpu.llm.models.llama import (LlamaConfig,
                                            paged_decode_step,
                                            synthetic_q4_params)

    cfg = {"7b": LlamaConfig.llama2_7b,
           "tiny": LlamaConfig.tiny}[model_size]()
    params = synthetic_q4_params(cfg)
    ppb = LANE // page_size
    cap = -(-(ctx_len + 160) // page_size)
    pages_cap = -(-cap // ppb) * ppb
    num_pages = 1 + batch * pages_cap
    nl, hkv, hd = (cfg.num_hidden_layers, cfg.num_key_value_heads,
                   cfg.head_dim)
    # pools built directly on device (host randn at 7B scale costs
    # minutes and ~9 GB of host RAM for values that don't matter)
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    shape = (nl, num_pages, hkv, page_size, hd)
    k_pages = jax.random.normal(kk, shape, jnp.bfloat16) * 0.1
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16) * 0.1
    rs = np.random.RandomState(0)
    # each row owns a disjoint page run (the allocator's layout)
    bt = np.zeros((batch, pages_cap), np.int32)
    for b in range(batch):
        bt[b] = 1 + b * pages_cap + np.arange(pages_cap)
    bt = jnp.asarray(bt)
    lens0 = jnp.full((batch,), ctx_len, jnp.int32)
    toks0 = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch,)), jnp.int32)

    # params/bt are explicit jit ARGS, not closures: a closure capture
    # lowers 4.4 GB of weights as HLO *constants*, which the remote
    # compile endpoint must serialize — a large share of round-4's
    # >20-minute compile wall
    @functools.partial(jax.jit, static_argnames=("steps",),
                       donate_argnums=(1, 2))
    def run(params, kp, vp, bt, lens, toks, steps: int):
        def body(i, carry):
            kp, vp, lens, toks = carry
            logits, kp, vp = paged_decode_step(params, cfg, kp, vp, bt,
                                               lens, toks, page=page_size)
            return (kp, vp, lens + 1,
                    jnp.argmax(logits, -1).astype(jnp.int32))
        return jax.lax.fori_loop(0, steps, body, (kp, vp, lens, toks))

    def window(n, kp, vp):
        t0 = time.perf_counter()
        kp, vp, lens, toks = run(params, kp, vp, bt, lens0, toks0, n)
        int(np.asarray(toks)[0])
        return time.perf_counter() - t0, kp, vp

    t0 = time.perf_counter()
    for n in (8, 32):
        _, k_pages, v_pages = window(n, k_pages, v_pages)
    compile_s = time.perf_counter() - t0
    t_small, k_pages, v_pages = window(8, k_pages, v_pages)
    t_big, k_pages, v_pages = window(32, k_pages, v_pages)
    per = (t_big - t_small) / 24
    if per <= 0:
        per = t_big / 32
    pool_gb = 2 * k_pages.nbytes / 1e9
    return {"metric": f"llama_{model_size}_paged_decode_step",
            "value": round(batch / per, 2),
            "unit": "tokens/sec",
            "vs_baseline": None,
            "extra": {"batch": batch, "ctx_len": ctx_len,
                      "page_size": page_size,
                      "step_ms": round(per * 1e3, 3),
                      "compile_s": round(compile_s, 1),
                      "kv_pool_gb": round(pool_gb, 2),
                      "num_pages": num_pages,
                      "decode_mode": "shared_scan_readonly_pool",
                      "attn_kernel": "page_major",
                      "backend": jax.default_backend()}}


def bench_int4_kernel_micro(m: int = 1, k: int = 4096, n: int = 11008,
                            iters: int = 2000) -> dict:
    """Kernel roofline check: Pallas q4_0 matmul vs dense bf16 matmul at a
    7B ffn shape. Decode (m=1) should be HBM-bound, so int4 at ~4.5
    bits/weight targets >2.5x the dense bf16 step time.

    Timing is a device-side fori_loop whose carry data-depends on every
    kernel output, closed by a host fetch of a loop-final scalar, and
    reported as the slope between two loop lengths so fixed
    dispatch/fetch overhead cancels."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.llm.ggml.quantize import QK
    from bigdl_tpu.llm.models.llama import _linear

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x0 = jax.random.normal(k1, (m, k), jnp.bfloat16)
    q = jax.random.randint(k2, (k // 2, n), 0, 256, jnp.uint8)
    scale = jax.random.uniform(k3, (k // QK, n), jnp.float32, 0.001, 0.02)
    w_dense = jax.random.normal(k4, (k, n), jnp.bfloat16)

    # distinct input buffers per timed call
    xs = [x0 * (1.0 + 1e-3 * i) for i in range(8)]
    xs = [jnp.asarray(v) for v in jax.block_until_ready(xs)]

    def slope_time(fn, weights):
        def loop_for(n_it):
            @jax.jit
            def loop(x, *ws):
                def body(i, carry):
                    x, acc = carry
                    y = fn(x, *ws)
                    return (x + y.sum().astype(x.dtype)
                            * jnp.asarray(1e-30, x.dtype), acc + y.sum())
                return jax.lax.fori_loop(0, n_it, body,
                                         (x, jnp.float32(0)))
            return loop
        pts, xi = [], 0
        for n_it in (iters // 4, iters):
            loop = loop_for(n_it)
            float(loop(xs[xi], *weights)[1])  # compile + warm
            best = 1e9
            for rep in range(3):
                xi += 1
                t0 = time.perf_counter()
                float(loop(xs[xi % len(xs)], *weights)[1])
                best = min(best, time.perf_counter() - t0)
            pts.append((n_it, best))
        (a1, b1), (a2, b2) = pts
        sl = (b2 - b1) / (a2 - a1)
        return sl if sl > 0 else b2 / a2

    # same dispatch the model uses: Pallas q4_0 kernel on TPU, dequant
    # matmul elsewhere
    t_int4 = slope_time(
        lambda x, qq, ss: _linear({"q": qq, "scale": ss}, x), (q, scale))
    t_dense = slope_time(lambda x, w: (x @ w).astype(jnp.bfloat16),
                         (w_dense,))
    packed_gb = (q.size + scale.size * 4) / 1e9
    return {
        "shape": [m, k, n], "iters": iters,
        "int4_us": round(t_int4 * 1e6, 1),
        "dense_bf16_us": round(t_dense * 1e6, 1),
        "int4_speedup_vs_dense": round(t_dense / t_int4, 2),
        "int4_packed_gbs": round(packed_gb / t_int4, 1),
        "dense_gbs": round(w_dense.nbytes / 1e9 / t_dense, 1),
    }


def _compact_northstar(out: dict) -> dict:
    """A SMALL final record duplicating the north-star numbers. The
    driver keeps only the output tail, and BENCH_r04's single huge JSON
    line was truncated from the HEAD — losing the ResNet and b1 records
    (VERDICT r4 weak #4). The last printed line is this compact one, so
    whatever survives tail-capture always contains the headlines."""
    ex = out.get("extra", {})

    def g(key, *fields):
        d = ex.get(key) or {}
        if "error" in d:
            return {"error": str(d["error"])[:80]}
        r = {"v": d.get("value"), "unit": d.get("unit")}
        for f in fields:
            r[f] = (d.get("extra") or {}).get(f)
        return r

    ns = {
        "resnet_img_s": out.get("value"),
        "resnet_mfu": ex.get("mfu"),
        "resnet_hbm_gbs": ex.get("implied_hbm_gbs"),
        "llama_b1": g("llama_int4_decode", "step_ms"),
        "llama_b8": g("llama_int4_decode_b8", "step_ms"),
        "paged_b8": g("paged_decode", "step_ms", "compile_s",
                      "kv_pool_gb"),
        "bert": g("bert_finetune", "mfu"),
        "prefill_4k": g("llama_longctx_prefill"),
        "lenet_top1": g("lenet_convergence", "bayes_ref_top1", "in_band"),
        "cifar_top1": g("cifar_convergence", "bayes_ref_top1", "in_band"),
    }
    # ISSUE 4: per-depth live-engine decode step time (host overlap win)
    mb = ((ex.get("telemetry") or {}).get("microbench_decode") or {})
    if "error" in mb:
        ns["decode_pipeline"] = {"error": str(mb["error"])[:80]}
    else:
        ns["decode_pipeline"] = {
            k: (v or {}).get("step_ms") for k, v in mb.items()
            if k.startswith("depth")}
        if mb.get("speedup_vs_depth1") is not None:
            ns["decode_pipeline"]["speedup"] = mb["speedup_vs_depth1"]
    # ISSUE 5: prefix-cache headline — TTFT off/on + prefill tokens the
    # radix cache deleted on the shared-prompt workload
    pb = ((ex.get("telemetry") or {}).get("microbench_prefix") or {})
    if "error" in pb:
        ns["prefix_cache"] = {"error": str(pb["error"])[:80]}
    else:
        ns["prefix_cache"] = {
            "ttft_off_ms": (pb.get("cache_off") or {}).get("ttft_ms"),
            "ttft_on_ms": (pb.get("cache_on") or {}).get("ttft_ms"),
            "tokens_saved": pb.get("prefill_tokens_saved"),
            "speedup": pb.get("ttft_speedup"),
        }
    # ISSUE 6: host-tier headline — evicted chains served from the
    # arena instead of re-prefilled on the oversized working set
    tb = ((ex.get("telemetry") or {}).get("microbench_tier") or {})
    if "error" in tb:
        ns["kvtier"] = {"error": str(tb["error"])[:80]}
    else:
        ns["kvtier"] = {
            "ttft_off_ms": (tb.get("tier_off") or {}).get("ttft_ms"),
            "ttft_on_ms": (tb.get("tier_on") or {}).get("ttft_ms"),
            "tokens_saved": tb.get("prefill_tokens_saved_vs_off"),
            "fetches": (tb.get("tier_on") or {}).get("fetches"),
            "hit_rate": (tb.get("tier_on") or {}).get("hit_rate"),
        }
    # ISSUE 14: unified-dispatch headline — the decode stream's p99
    # inter-token gap while a long prompt is admitted, split vs mixed
    # (the spike the chunked admission deletes), plus the TTFT trade
    xb = ((ex.get("telemetry") or {}).get("mixed_dispatch") or {})
    if "error" in xb:
        ns["mixed_dispatch"] = {"error": str(xb["error"])[:80]}
    else:
        ns["mixed_dispatch"] = {
            "itl_p99_off_ms": (xb.get("mixed_off") or {}).get(
                "itl_p99_ms"),
            "itl_p99_on_ms": (xb.get("mixed_on") or {}).get(
                "itl_p99_ms"),
            "ttft_off_ms": (xb.get("mixed_off") or {}).get("ttft_ms"),
            "ttft_on_ms": (xb.get("mixed_on") or {}).get("ttft_ms"),
            "chunks": (xb.get("mixed_on") or {}).get("chunks"),
            "p99_ratio": xb.get("itl_p99_ratio_off_on"),
        }
    # ISSUE 19: self-speculative decoding headline — batch-1 tok/s with
    # drafts verified in bulk vs plain decode, the accepted-tokens-per-
    # tick the ROADMAP bar is stated in, and the bit-parity verdict
    sb = ((ex.get("telemetry") or {}).get("spec_decode") or {})
    if "error" in sb:
        ns["spec_decode"] = {"error": str(sb["error"])[:80]}
    else:
        ns["spec_decode"] = {
            "tok_s_off": (sb.get("spec_off") or {}).get("tokens_per_s"),
            "tok_s_on": (sb.get("spec_on") or {}).get("tokens_per_s"),
            "accepted_per_tick": sb.get("accepted_tokens_per_tick"),
            "accept_rate": sb.get("accept_rate"),
            "speedup": sb.get("tokens_per_s_ratio"),
            "bit_identical": sb.get("bit_identical"),
        }
    # ISSUE 20: OpenAI-gateway headline — streaming TTFT through the
    # SSE leg vs the native stream, the gateway's added latency, and
    # the parity tally (must stay 0)
    ab = ((ex.get("telemetry") or {}).get("openai_api") or {})
    if "error" in ab:
        ns["api"] = {"error": str(ab["error"])[:80]}
    else:
        ns["api"] = {
            "ttft_direct_ms": ab.get("ttft_direct_p50_ms"),
            "ttft_gateway_ms": ab.get("ttft_gateway_p50_ms"),
            "overhead_ms": ab.get("gateway_overhead_ms"),
            "mismatches": ab.get("output_mismatches"),
        }
    return {"metric": out["metric"], "value": out["value"],
            "unit": out["unit"], "vs_baseline": out.get("vs_baseline"),
            "extra": {"northstar_summary": ns,
                      "note": "compact tail record; full record printed "
                              "on the line above"}}


def _telemetry_block() -> dict:
    """Snapshot of the observability registry + span distributions after
    the benches ran (the convergence benches drive the instrumented
    BaseOptimizer loop, so step-time histograms and loss/grad-norm
    gauges land here; see tools/telemetry_report.py). Also folds in one
    seeded chaos smoke run (tools/chaos_check.py): injected faults must
    recover to the clean run's final loss, and its reliability counters
    land in the same registry snapshot."""
    from bigdl_tpu import observability as obs
    from tools.telemetry_report import (summarize_registry,
                                        summarize_trace)
    # snapshot the bench telemetry FIRST: the chaos smoke trains its own
    # tiny model through the instrumented loop and must not pollute the
    # step-time/loss numbers this block reports for the benches
    out = {
        "metrics": summarize_registry(),
        "spans": summarize_trace(
            {"traceEvents": obs.TRACE.spans()})["spans"],
        # ISSUE 3 flight recorder: per-jit-entry-point compile history
        # (count, seconds, cost/memory analysis, recompile signatures)
        # — the MFU numbers above are attributed to these executables
        "compiles": obs.compile_stats(),
    }
    # ISSUE 16: arm the decision-event flight recorder for the
    # serving-driven microbenches below — the same gate enables the
    # per-dispatch wall-time sampler whose join with the obs.compiled
    # cost analyses yields the live roofline block captured at the end.
    # Restored before return so the gate stays default-off elsewhere.
    from bigdl_tpu.observability import utilization
    from bigdl_tpu.utils.conf import conf as _conf
    _flight_prior = _conf.get("bigdl.observability.flight.enabled")
    _conf.set("bigdl.observability.flight.enabled", "true")
    try:
        # ISSUE 7 satellite: every chaos suite in one block — train
        # recovery, kvcache eviction races, kvtier migration faults,
        # and the router kill-storm (zero lost requests, bit-identical
        # resume). One record per pass; a failing pass lands as an
        # error entry without hiding the others.
        from tools.chaos_check import run_all_chaos
        out["chaos_all"] = run_all_chaos(seed=0)
    except Exception as e:  # never lose the telemetry to the chaos run
        out["chaos_all"] = {"error": repr(e)}
    try:
        # ISSUE 11: the static-analysis gate summary — finding counts
        # by rule, zero-unbaselined verdict, baseline hygiene — lands
        # in every bench round (+ one PROGRESS.jsonl breadcrumb) so
        # finding-count drift across PRs is visible in telemetry
        out["static_analysis"] = _static_analysis_block()
    except Exception as e:
        out["static_analysis"] = {"error": repr(e)}
    try:
        # ISSUE 4: live-engine decode latency across pipeline depths —
        # the host-overlap win (and its host/stall attribution) lands in
        # every bench round next to the device-side decode numbers
        from tools.microbench_decode import run_microbench
        out["microbench_decode"] = run_microbench(
            depths=(1, 2, 4), batch=4, tokens=24)
    except Exception as e:
        out["microbench_decode"] = {"error": repr(e)}
    try:
        # ISSUE 5: shared-system-prompt replay with the prefix cache
        # off/on — TTFT and prefill-tokens-saved (bench_regress diffs
        # the ttft_ms pair across rounds)
        from tools.microbench_prefix import run_prefix_bench
        out["microbench_prefix"] = run_prefix_bench()
    except Exception as e:
        out["microbench_prefix"] = {"error": repr(e)}
    try:
        # ISSUE 6: working set sized past the HBM pool, tier off/on —
        # host-arena fetches must reappear as deleted prefill tokens
        # (bench_regress diffs the ttft_ms pair and the savings)
        from tools.microbench_tier import run_tier_bench
        out["microbench_tier"] = run_tier_bench()
    except Exception as e:
        out["microbench_tier"] = {"error": repr(e)}
    try:
        # ISSUE 14: mixed-load microbench — steady decode streams with
        # a long admission mid-run, unified dispatch off/on. The p99
        # inter-token spike the split engine pays for the admission
        # must be gone in the on mode (bench_regress diffs
        # mixed.itl_p99_ms / mixed.ttft_ms and the off/on pairs)
        from tools.microbench_mixed import run_mixed_bench
        out["mixed_dispatch"] = run_mixed_bench(
            prompt_len=192, stream_tokens=24)
    except Exception as e:
        out["mixed_dispatch"] = {"error": repr(e)}
    try:
        # ISSUE 19: self-speculative decoding on/off — batch-1 tok/s on
        # a repetitive-suffix workload, accepted-tokens/tick and the
        # bit-parity verdict (bench_regress diffs spec.tokens_per_s /
        # spec.accept_rate and the off/on itl_p99 pair)
        from tools.microbench_decode import run_spec_bench
        out["spec_decode"] = run_spec_bench(tokens=48)
    except Exception as e:
        out["spec_decode"] = {"error": repr(e)}
    try:
        # ISSUE 12: the fleet telemetry plane — two live workers behind
        # a federation+SLO router; merged sketch percentiles
        # (ttft_p50/p95/p99_ms, itl_p99_ms — bench_regress diffs them)
        # plus the counter-additivity verdict
        from tools.fleet_report import run_fleet_micro
        out["fleet"] = run_fleet_micro()
    except Exception as e:
        out["fleet"] = {"error": repr(e)}
    try:
        # ISSUE 15: the elastic-fleet soak — spike -> autoscaler
        # scale-out -> graceful drain-and-scale-in, fault-free. The
        # numbers the fleet is judged on land in every round: p99
        # TTFT/ITL under soak (SLO sketch windows), requests lost
        # (must stay 0) and the scale-event counts (bench_regress
        # diffs fleet_elastic.*; the killing variant runs inside
        # chaos_all above)
        from tools.loadgen import run_fleet_soak
        out["fleet_elastic"] = run_fleet_soak()
    except Exception as e:
        out["fleet_elastic"] = {"error": repr(e)}
    try:
        # ISSUE 20: the OpenAI gateway — client-visible streaming TTFT
        # through /v1/completions SSE vs the native stream on the same
        # seeded prompts, and the gateway's added latency. The
        # output_mismatches tally must pin at 0 (bench_regress diffs
        # api.ttft_gateway_p50_ms / api.gateway_overhead_ms)
        from tools.loadgen import run_openai_bench
        out["openai_api"] = run_openai_bench()
    except Exception as e:
        out["openai_api"] = {"error": repr(e)}
    try:
        # ISSUE 18: the time-series plane — windowed-store sampling
        # cost over the live post-bench registry (every series the
        # benches above created, so the number tracks real cardinality)
        # plus one default-rule evaluation pass. bench_regress lifts
        # ts.sample_overhead_us / alerts.transitions: overhead creeping
        # up means snapshot cost regressed; transitions going nonzero
        # means the bench round itself tripped an SLO page
        out["alerts"] = _alerts_block()
    except Exception as e:
        out["alerts"] = {"error": repr(e)}
    try:
        # ISSUE 16: the live roofline — per-dispatch wall time sampled
        # while the serving microbenches above ran, joined with the
        # XLA cost analyses into achieved GB/s, MFU and bandwidth
        # utilization plus the per-program table (bench_regress lifts
        # util.mfu / util.hbm_bw_gbps; on real TPU the headline
        # hbm_bw_gbps should land near the decode bench's
        # implied_hbm_gbs weight-stream lower bound)
        out["utilization"] = utilization.snapshot()
    except Exception as e:
        out["utilization"] = {"error": repr(e)}
    finally:
        if _flight_prior is None:
            _conf.unset("bigdl.observability.flight.enabled")
        else:
            _conf.set("bigdl.observability.flight.enabled", _flight_prior)
    return out


def _alerts_block() -> dict:
    """ISSUE 18 micro-measurement: periodic-sampler overhead against
    the full live registry and one alert-engine pass over the built-in
    burn-rate rules. The gate is raised only for the measurement and
    restored on the way out (the plane stays default-off elsewhere)."""
    from bigdl_tpu.observability import alerts as _alerts
    from bigdl_tpu.observability import timeseries as _ts
    from bigdl_tpu.utils.conf import conf as _conf
    keys = ("bigdl.observability.timeseries.enabled",
            "bigdl.observability.timeseries.interval")
    prior = {k: _conf.get(k) for k in keys}
    _conf.set("bigdl.observability.timeseries.enabled", "true")
    # park the background thread: the synchronous samples below are the
    # measurement, a concurrent wall-clock tick would just add noise
    _conf.set("bigdl.observability.timeseries.interval", "3600")
    try:
        st = _ts.acquire()
        if st is None:
            return {"error": "store unavailable (observability off?)"}
        overheads = []
        for _ in range(8):
            st.sample_now()
            overheads.append(st.last_overhead_us)
        eng = _alerts.engine()
        if eng is not None:
            eng.evaluate(st.clock())
        status = st.status()
        overheads.sort()
        return {
            "sample_overhead_us": round(
                overheads[len(overheads) // 2], 1),
            "sample_overhead_max_us": round(overheads[-1], 1),
            "samples": status["samples"],
            "rules": len(eng.rules) if eng is not None else 0,
            "evaluations": eng.evaluations if eng is not None else 0,
            "transitions": eng.transitions if eng is not None else 0,
            "firing": eng.firing() if eng is not None else [],
        }
    finally:
        _ts.release()
        for k in keys:
            if prior[k] is None:
                _conf.unset(k)
            else:
                _conf.set(k, prior[k])


def _static_analysis_block() -> dict:
    """Run the ISSUE 11 analyzer over the repo and compress its record
    to the counts worth tracking round-over-round; append one
    breadcrumb line to PROGRESS.jsonl (the bench_regress idiom)."""
    import json as _json
    import os
    import time as _time
    from bigdl_tpu.analysis import check as static_check
    root = os.path.dirname(os.path.abspath(__file__))
    sa = static_check(root)
    block = {"ok": sa["ok"], "by_rule": sa["by_rule"],
             # per-pass finding counts (ISSUE 13): bench_regress diffs
             # these so a finding-count regression in any one pass
             # (donation/gatecheck/httpdrift included) is a visible
             # delta in PROGRESS.jsonl, not a buried by_rule reshuffle
             "by_pass": sa.get("by_pass", {}),
             "new": len(sa["new"]), "suppressed": sa["suppressed"],
             "stale_baseline": len(sa["stale_baseline"]),
             "baseline_errors": len(sa["baseline_errors"])}
    try:
        with open(os.path.join(root, "PROGRESS.jsonl"), "a") as f:
            f.write(_json.dumps({"ts": _time.time(),
                                 "kind": "static_analysis",
                                 **block}) + "\n")
    except OSError:
        pass                      # the breadcrumb never fails the bench
    return block


def _regress_block() -> dict:
    """Optional north-star regression diff (ISSUE 3 satellite): compare
    the newest two driver-recorded BENCH_r*.json rounds and flag moves
    past the warn threshold; one compact breadcrumb line is appended to
    PROGRESS.jsonl. Never fails the bench."""
    import os
    root = os.path.dirname(os.path.abspath(__file__))
    try:
        from tools.bench_regress import compare_latest
        out = compare_latest(
            root, progress_path=os.path.join(root, "PROGRESS.jsonl"))
        if out is None:
            return {"note": "fewer than two BENCH_r*.json rounds"}
        # compact: the full per-metric table is reproducible offline via
        # tools/bench_regress.py; the record keeps only the verdict
        return {"base": out["base"], "head": out["head"],
                "warn_pct": out["warn_pct"],
                "metrics": len(out["deltas"]), "warned": out["warned"]}
    except Exception as e:
        return {"error": repr(e)}


def _phase_errors(node, where: str = "result") -> list:
    """Every ``{"error": ...}`` a phase left anywhere in the result
    tree, as ``(path, message)`` pairs — the phases keep going after a
    failure so one run reports them all, and ``__main__`` turns a
    non-empty list into a non-zero exit."""
    if not isinstance(node, dict):
        return []
    found = [(where, node["error"])] if node.get("error") else []
    for key, child in node.items():
        found += _phase_errors(child, f"{where}.{key}")
    return found


def _default_run(quick: bool) -> dict:
    """The driver-captured output: resnet headline + llama decode +
    kernel micro-bench folded into one JSON object."""
    from bigdl_tpu import observability as obs
    if quick:
        with obs.span("bench/resnet"):
            out = bench_resnet50_train(batch_size=4, warmup=1, iters=5,
                                       image=64, depth=18, classes=100,
                                       smoke=True, format="NCHW",
                                       remat=False)
        try:
            with obs.span("bench/llama_int4_decode"):
                out["extra"]["llama_int4_decode"] = \
                    bench_llama_int4_decode(model_size="tiny", smoke=True)
        except Exception as e:  # never lose the headline to a side metric
            out["extra"]["llama_int4_decode"] = {"error": repr(e)}
        try:
            with obs.span("bench/paged_decode"):
                out["extra"]["paged_decode"] = bench_paged_decode_step(
                    model_size="tiny", batch=2, ctx_len=32)
        except Exception as e:
            out["extra"]["paged_decode"] = {"error": repr(e)}
        try:
            out["extra"]["telemetry"] = _telemetry_block()
        except Exception as e:
            out["extra"]["telemetry"] = {"error": repr(e)}
        out["extra"]["regress"] = _regress_block()
        return out
    out = bench_resnet50_train()
    try:
        out["extra"]["llama_int4_decode"] = bench_llama_int4_decode()
    except Exception as e:
        out["extra"]["llama_int4_decode"] = {"error": repr(e)}
    try:
        out["extra"]["llama_int4_decode_b8"] = bench_llama_int4_decode(
            batch=8)
    except Exception as e:
        out["extra"]["llama_int4_decode_b8"] = {"error": repr(e)}
    try:
        out["extra"]["paged_decode"] = bench_paged_decode_step()
    except Exception as e:
        out["extra"]["paged_decode"] = {"error": repr(e)}
    try:
        out["extra"]["int4_kernel_micro"] = bench_int4_kernel_micro()
    except Exception as e:
        out["extra"]["int4_kernel_micro"] = {"error": repr(e)}
    try:
        out["extra"]["bert_finetune"] = bench_bert_finetune()
    except Exception as e:
        out["extra"]["bert_finetune"] = {"error": repr(e)}
    try:
        out["extra"]["llama_longctx_prefill"] = bench_llama_longctx_prefill()
    except Exception as e:
        out["extra"]["llama_longctx_prefill"] = {"error": repr(e)}
    try:
        out["extra"]["lenet_convergence"] = bench_lenet_convergence()
    except Exception as e:
        out["extra"]["lenet_convergence"] = {"error": repr(e)}
    try:
        out["extra"]["cifar_convergence"] = bench_cifar_convergence()
    except Exception as e:
        out["extra"]["cifar_convergence"] = {"error": repr(e)}
    try:
        out["extra"]["telemetry"] = _telemetry_block()
    except Exception as e:
        out["extra"]["telemetry"] = {"error": repr(e)}
    out["extra"]["regress"] = _regress_block()
    return out


if __name__ == "__main__":
    import os
    import sys

    if "--cpu" in sys.argv or os.environ.get("BIGDL_TPU_BENCH_CPU"):
        import jax
        jax.config.update("jax_platforms", "cpu")
    if "--profile" in sys.argv:
        import jax
        jax.profiler.start_trace("/tmp/bigdl_tpu_trace")
    quick = "--quick" in sys.argv or bool(os.environ.get(
        "BIGDL_TPU_BENCH_QUICK"))
    failed = []
    if "--lenet" in sys.argv:
        print(json.dumps(bench_lenet_train()))
    elif "--paged" in sys.argv:
        if quick:
            print(json.dumps(bench_paged_decode_step(
                model_size="tiny", batch=2, ctx_len=32)))
        else:
            print(json.dumps(bench_paged_decode_step()))
    elif "--llama" in sys.argv:
        if quick:
            print(json.dumps(bench_llama_int4_decode(
                model_size="tiny", smoke=True)))
        else:
            print(json.dumps(bench_llama_int4_decode()))
    elif "--kernels" in sys.argv:
        print(json.dumps(bench_int4_kernel_micro()))
    elif "--bert" in sys.argv:
        print(json.dumps(bench_bert_finetune(smoke=quick)))
    else:
        res = _default_run(quick)
        print(json.dumps(res))
        print(json.dumps(_compact_northstar(res)))
        failed = _phase_errors(res)
    if "--profile" in sys.argv:
        import jax
        jax.profiler.stop_trace()
    if failed:
        for where, err in failed:
            print(f"bench phase failed: {where}: {err}", file=sys.stderr)
        sys.exit(1)
