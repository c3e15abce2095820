"""ISSUE 28: the INT4 matmul reads its layer out of the weight stack in
place. The stacked form of ``int4_matmul`` against the 2-D form on
``q[l], scale[l]`` (bit for bit) and the numpy reference; the llama
family's one layer walk (``hold_stacks``) against a scan that slices
every leaf, as the family did before; the HLO-text checker that the
chip tests hold the engine's programs to."""

import dataclasses
import functools
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.llm.ggml.quantize import quantize
from bigdl_tpu.llm.kernels import (int4_matmul, int4_matmul_reference,
                                   to_tpu_layout)
from bigdl_tpu.llm.kvcache.write import weight_slices

# the module, which the package hides behind the function of its name
im = importlib.import_module("bigdl_tpu.llm.kernels.int4_matmul")

L = 3


def _stack(k, n, seed=0):
    """L layers of seeded q4_0 weights: the ggml dicts (for the
    reference) and the stacked TPU layout."""
    rs = np.random.RandomState(seed)
    qds = [quantize(rs.randn(n, k).astype(np.float32) * 0.1, "sym_int4")
           for _ in range(L)]
    tds = [to_tpu_layout(qd) for qd in qds]
    return (qds, jnp.asarray(np.stack([t["q"] for t in tds])),
            jnp.asarray(np.stack([t["scale"] for t in tds])))


def _shape(chunked, monkeypatch):
    """(K, N) of a stack that is one K block, or two when ``_MAX_BK``
    is brought down to 256 (half 128 = 4 x 32 sublanes, g 8). The
    shapes are this file's own, so no other test's trace is reused."""
    if chunked:
        monkeypatch.setattr(im, "_MAX_BK", 256)
        assert im._stack_blocks(512, 384, 256) == (
            128, [(0, 256), (256, 256)])
        return 512, 384
    return 224, 384


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("m", [1, 16, 100, 300])
@pytest.mark.parametrize("layer", range(L))
def test_stacked_equals_2d_and_reference(layer, m, chunked, monkeypatch):
    """Both zero-point strategies (m < 256 ``corr``, m >= 256 ``sub8``),
    padded and unpadded M tiles, with and without K chunking."""
    k, n = _shape(chunked, monkeypatch)
    qds, q, scale = _stack(k, n, seed=m)
    x = np.random.RandomState(7 + m).randn(m, k).astype(np.float32)
    got = np.asarray(int4_matmul(jnp.asarray(x), q, scale, layer=layer,
                                 interpret=True, out_dtype=jnp.float32))
    flat = np.asarray(int4_matmul(jnp.asarray(x), q[layer], scale[layer],
                                  interpret=True, out_dtype=jnp.float32))
    np.testing.assert_array_equal(got, flat)
    ref = int4_matmul_reference(x, qds[layer]["q"], qds[layer]["scale"])
    assert np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-6) < 0.02


@pytest.mark.parametrize("chunked", [False, True])
def test_layer_traced_in_a_scan(chunked, monkeypatch):
    """The stack closed over, ``layer`` the scan's own index: every
    step reads its own layer."""
    k, n = _shape(chunked, monkeypatch)
    _, q, scale = _stack(k, n, seed=11)
    x = jnp.asarray(np.random.RandomState(3).randn(16, k), jnp.float32)

    def step(carry, l):
        return carry, int4_matmul(x, q, scale, layer=l, interpret=True,
                                  out_dtype=jnp.float32)

    _, ys = jax.jit(lambda: jax.lax.scan(
        step, 0, jnp.arange(L, dtype=jnp.int32)))()
    for l in range(L):
        np.testing.assert_array_equal(
            np.asarray(ys[l]),
            np.asarray(int4_matmul(x, q[l], scale[l], interpret=True,
                                   out_dtype=jnp.float32)))


@pytest.mark.parametrize("k,n,max_bk", [
    (320, 384, 256),      # two chunks of half 80: not whole sublane tiles
    (224, 300, 8192),     # N neither a multiple of bn nor of 128, above bn
])
def test_unblockable_stack_falls_back(k, n, max_bk, monkeypatch):
    monkeypatch.setattr(im, "_MAX_BK", max_bk)
    assert im._stack_blocks(k, n, 256) is None
    qds, q, scale = _stack(k, n, seed=5)
    x = np.random.RandomState(9).randn(16, k).astype(np.float32)
    for layer in range(L):
        got = np.asarray(int4_matmul(
            jnp.asarray(x), q, scale, layer=jnp.int32(layer),
            interpret=True, out_dtype=jnp.float32))
        np.testing.assert_array_equal(got, np.asarray(int4_matmul(
            jnp.asarray(x), q[layer], scale[layer], interpret=True,
            out_dtype=jnp.float32)))
        ref = int4_matmul_reference(x, qds[layer]["q"],
                                    qds[layer]["scale"])
        assert (np.abs(got - ref).max()
                / max(np.abs(ref).max(), 1e-6)) < 0.02


@pytest.mark.parametrize("k,n,plan", [
    (4096, 6144, (256, [(0, 4096)])),                  # Mistral qkv
    (4096, 4096, (256, [(0, 4096)])),                  # o
    (4096, 28672, (256, [(0, 4096)])),                 # gate_up
    (14336, 4096, (256, [(0, 7168), (7168, 7168)])),   # down: half 3584
    (11008, 4096, None),      # Llama-2 down: g = 172 is not 8-aligned
    (4096, 33 * 128, (128, [(0, 4096)])),    # N: 128 divides it, bn not
    (64, 48, (48, [(0, 64)])),                         # N below bn: whole
])
def test_stack_blocks_decides_from_the_shapes(k, n, plan):
    assert im._stack_blocks(k, n, 256) == plan


# ---------------------------------------------------------------------------
# the family's layer walk
# ---------------------------------------------------------------------------

def _slice_every_leaf(layers):
    """``hold_stacks`` as the family walked its layers before: every
    leaf sliced by the scan, the quantised ones too, so ``_linear``
    sees 2-D ``q`` and ``scale``."""
    return layers, lambda lp: lp


def _kernel_linear(monkeypatch):
    """Route ``_linear`` through the Pallas kernel under interpret, as
    on the chip, and nothing else of the program."""
    from bigdl_tpu.llm import kernels
    from bigdl_tpu.llm.models import llama
    linear = llama._linear
    monkeypatch.setattr(kernels, "int4_matmul",
                        functools.partial(int4_matmul, interpret=True))

    def on_kernel(wd, x, layer=None):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            return linear(wd, x, layer)

    monkeypatch.setattr(llama, "_linear", on_kernel)


@pytest.fixture(scope="module")
def tiny_q4():
    """A 3-layer quantised llama with biased q/k/v (the bias leaves
    stay scanned beside held stacks), pools with some history in them
    and two rows' block tables."""
    from bigdl_tpu.llm.models.llama import (LlamaConfig, init_params,
                                            quantize_params)
    cfg = dataclasses.replace(LlamaConfig.tiny_qwen2(),
                              num_hidden_layers=L)
    params = quantize_params(init_params(cfg, seed=0))
    assert params["layers"]["qkv_proj"]["q"].ndim == 3
    assert "b" in params["layers"]["qkv_proj"]
    page, pages = 16, 1 + 2 * 8
    rs = np.random.RandomState(0)
    shape = (L, pages, cfg.num_key_value_heads, page,
             cfg.hidden_size // cfg.num_attention_heads)
    kp = jnp.asarray(rs.randn(*shape) * 0.1, jnp.bfloat16)
    vp = jnp.asarray(rs.randn(*shape) * 0.1, jnp.bfloat16)
    bt = jnp.asarray(1 + np.arange(16).reshape(2, 8), jnp.int32)
    return cfg, params, kp, vp, bt, page


def _run(program, tiny):
    cfg, params, kp, vp, bt, page = tiny
    if program == "decode":
        from bigdl_tpu.llm.models.llama import paged_decode_step
        return paged_decode_step(
            params, cfg, kp, vp, bt, jnp.asarray([21, 5], jnp.int32),
            jnp.asarray([7, 200], jnp.int32), page=page)
    from bigdl_tpu.llm.models.llama import paged_prefill_ragged
    bucket, offset, length = 32, 19, 27
    pos = offset + np.arange(bucket)
    real = np.arange(bucket) < length
    phys = np.where(real, np.asarray(bt[0])[pos // page], 0)
    kp, vp, last = paged_prefill_ragged(
        params, cfg, kp, vp,
        jnp.asarray(np.random.RandomState(1).randint(0, 256, (1, bucket)),
                    jnp.int32),
        jnp.int32(length), jnp.int32(offset), bt[0],
        jnp.asarray(phys, jnp.int32), jnp.asarray(pos % page, jnp.int32),
        jnp.int32(0), jnp.int32(0), page=page)
    return last, kp, vp


@pytest.mark.parametrize("linear", ["xla_dequant", "pallas_interpret"])
@pytest.mark.parametrize("program", ["decode", "prefill_ragged"])
def test_programs_unchanged_by_the_held_stacks(program, linear, tiny_q4,
                                               monkeypatch):
    """Logits and the written K/V of the decode step and the ragged
    prefill, stacks held whole and indexed by the kernel, against the
    same program over a scan that slices them: equal bit for bit, off
    the chip (XLA dequant of ``q[l]``) and through the kernel."""
    from bigdl_tpu.llm.models import llama
    if linear == "pallas_interpret":
        _kernel_linear(monkeypatch)
    got = _run(program, tiny_q4)
    monkeypatch.setattr(llama, "hold_stacks", _slice_every_leaf)
    want = _run(program, tiny_q4)
    assert np.isfinite(np.asarray(got[0])).all()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # the step wrote something: the pools differ from what went in
    assert not np.array_equal(np.asarray(got[1], np.float32),
                              np.asarray(tiny_q4[2], np.float32))


# ---------------------------------------------------------------------------
# the HLO-text checker
# ---------------------------------------------------------------------------

_LAYERS = {
    "o_proj": {"q": jax.ShapeDtypeStruct((32, 2048, 4096), jnp.uint8),
               "scale": jax.ShapeDtypeStruct((32, 128, 4096),
                                             jnp.float32)},
    "input_layernorm": jax.ShapeDtypeStruct((32, 4096), jnp.bfloat16),
    "router": {"w": jax.ShapeDtypeStruct((32, 8, 4096), jnp.bfloat16)},
}

_HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.3 (param_0.1: u8[32,2048,4096], param_1.2: s32[]) -> u8[2048,4096] {
  %param_0.1 = u8[32,2048,4096]{2,1,0:T(8,128)(4,1)} parameter(0)
  %param_1.2 = s32[]{:T(128)} parameter(1)
  %constant.5 = s32[]{:T(128)} constant(0)
  BODY
}

%region_0.12 (arg_tuple.1: (s32[], bf16[16,1,4096], u8[32,2048,4096], f32[32,128,4096])) -> (s32[], bf16[16,1,4096], u8[32,2048,4096], f32[32,128,4096]) {
  %x32 = f32[1,128,4096]{1,2,0:T(8,128)S(1)} copy(%slice_convert_fusion.3)
  %norm = bf16[1,4096]{1,0:T(2,128)(2,1)} dynamic-slice(%norms, %l, %zero), dynamic_slice_sizes={1,4096}
  STEP
}
"""

_SLICED = (
    "%dynamic_slice.74 = u8[1,2048,4096]{2,1,0:T(8,128)(4,1)} "
    "dynamic-slice(%param_0.1, %param_1.2, %constant.5, %constant.5), "
    "dynamic_slice_sizes={1,2048,4096}\n"
    "  ROOT %bitcast.9 = u8[2048,4096]{1,0:T(8,128)(4,1)} "
    "bitcast(%dynamic_slice.74)",
    "%dynamic-slice_bitcast_fusion = u8[2048,4096]{1,0:T(8,128)(4,1)} "
    "fusion(%stack, %l), kind=kLoop, calls=%fused_computation.3\n"
    "  %_int4_matmul_jit.2 = f32[16,4096]{1,0:T(8,128)} custom-call("
    "%xe, %xo, %dynamic-slice_bitcast_fusion, %scale_l), "
    "custom_call_target=\"tpu_custom_call\"")
_IN_PLACE = (
    "ROOT %bitcast.9 = u8[32,2048,4096]{2,1,0:T(8,128)(4,1)} "
    "bitcast(%param_0.1)",
    "%_int4_matmul_stacked_jit.2 = f32[16,4096]{1,0:T(8,128)} "
    "custom-call(%l, %xe, %xo, %stack, %scales), "
    "custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("body,step,found", [
    (_SLICED[0], _SLICED[1], ["dynamic_slice.74"]),
    (_IN_PLACE[0], _IN_PLACE[1], []),
    (_IN_PLACE[0],
     "%scale_l = f32[1,128,4096]{2,1,0:T(8,128)} dynamic-slice(%scales, "
     "%l, %zero, %zero), dynamic_slice_sizes={1,128,4096}",
     ["scale_l"]),
    (_IN_PLACE[0],
     "%copy-done = u8[2048,4096]{1,0:T(8,128)(4,1)} copy-done(%copy-start)",
     ["copy-done"]),
    (_IN_PLACE[0],
     "%slice.3 = u8[1,2048,4096]{2,1,0:T(8,128)(4,1)} slice(%stack), "
     "slice={[5:6], [0:2048], [0:4096]}", ["slice.3"]),
])
def test_weight_slices_reads_the_hlo_text(body, step, found):
    """A hand-written scheduled module: the slice is found where it is
    (alone, fused, asynchronous, static), and neither the float
    activation that happens to have a scale's shape nor a norm's slice
    is mistaken for one."""
    text = _HLO.replace("BODY", body).replace("STEP", step)
    got = weight_slices(text, _LAYERS)
    assert [line.split(" = ")[0].replace("ROOT ", "").lstrip("%")
            for line in got] == found
    assert weight_slices(text, {"norm": _LAYERS["input_layernorm"]}) == []


def strided_reads(text):
    """The ops of a lowered (StableHLO) module that read an array at a
    stride or through a gather: ``x[:, 0::2]`` lowers to either. A
    Mosaic call's body is serialised into its ``backend_config``, so
    only what stands OUTSIDE the kernel is in the text."""
    made = re.compile(r"stablehlo\.gather|stablehlo\.slice\s.*\[[^\]]*"
                      r"\d+:\d+:([2-9]|\d\d)")
    return [line.strip() for line in text.splitlines() if made.search(line)]


@pytest.mark.parametrize("line,found", [
    ("%0 = stablehlo.slice %arg0 [0:16, 0:512:2] : (tensor<16x512xbf16>)"
     " -> tensor<16x256xbf16>", True),
    ('%8 = "stablehlo.gather"(%arg0, %7) <{dimension_numbers = '
     "#stablehlo.gather<offset_dims = [0]>}>", True),
    ("%1 = stablehlo.slice %arg0 [0:16, 3:40] : (tensor<16x512xbf16>) "
     "-> tensor<16x37xbf16>", False),
])
def test_strided_reads_reads_the_text(line, found):
    """The parent's even/odd split (a stride-2 slice, or the gather jnp
    makes of ``x[:, 0::2]``) is found; a K chunk's contiguous slice is
    not."""
    assert bool(strided_reads("module {\n  " + line + "\n}")) == found


@pytest.mark.parametrize("form", ["2d", "stack"])
@pytest.mark.parametrize("mode", ["corr", "sub8"])
@pytest.mark.parametrize("k,max_bk", [
    (224, 8192),      # one chunk, a partial selection block
    (512, 256),       # two chunks, each a block of x in place
    (320, 256),       # two chunks of 160 lanes: sliced (the stack: 2-D)
])
def test_no_split_outside_the_kernel(form, mode, k, max_bk, monkeypatch):
    """ISSUE 38's witness: ``int4_matmul`` lowered for the TPU (no chip
    needed) holds its Mosaic calls, one a K chunk, and nothing beside
    them that reads the activations at a stride."""
    monkeypatch.setattr(im, "_MAX_BK", max_bk)
    x = jax.ShapeDtypeStruct((16, k), jnp.float32)
    q = jax.ShapeDtypeStruct((L, k // 2, 384), jnp.uint8)
    s = jax.ShapeDtypeStruct((L, k // 32, 384), jnp.float32)
    if form == "stack":
        traced = jax.jit(lambda x, q, s, l: int4_matmul(
            x, q, s, layer=l, mode=mode)).trace(
                x, q, s, jax.ShapeDtypeStruct((), jnp.int32))
    else:
        traced = jax.jit(functools.partial(int4_matmul, mode=mode)).trace(
            x, jax.ShapeDtypeStruct(q.shape[1:], q.dtype),
            jax.ShapeDtypeStruct(s.shape[1:], s.dtype))
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == \
        len(im._chunk_k(k))
    assert strided_reads(text) == []
