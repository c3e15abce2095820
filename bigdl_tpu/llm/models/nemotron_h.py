"""Nemotron-H decoders (``model_type`` ``nemotron_h``; the configuration
the chip runs is NVIDIA-Nemotron-3-Super-120B-A12B, ISSUE 37): **one
mixer a layer**, a Mamba-2 block, an attention block or an expert block
by ``hybrid_override_pattern`` (``M`` | ``*`` | ``E``), not
attention-then-FFN.

Every layer ``l``: ``x <- x + Mixer_l(RMSNorm_l(x))``, epsilon
``layer_norm_epsilon``, the residual in the stream's dtype, no biases
but the convolution's; a final RMSNorm and an untied head.

**M, Mamba-2** (``H`` heads of ``P`` channels, ``G`` groups, ``N`` the
state size, ``d_inner = H P``, head ``h`` in group ``h // (H / G)``):
``[z | xBC | dt] = W_in u`` (``d_inner | d_inner + 2 G N | H``); ``xBC'
= silu(conv(xBC) + b)``, depthwise, causal, ``conv_kernel`` taps, zeros
before position 0, split ``x`` (H, P), ``B``, ``C`` (G, N); ``dt =
softplus(dt + dt_bias)``, ``a = -exp(A_log)``, float32; ``S_t = exp(dt_t
a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
(:mod:`bigdl_tpu.llm.kernels.ssm`: one token at a time when decoding,
the chunked form over a prompt); ``g = RMSNorm_grouped(y * silu(z))``,
the gate first, then RMS over each of the ``G`` groups of ``d_inner /
G`` channels, a learned weight of ``d_inner``; ``Mixer = W_out g``.

**\\*, attention**: GQA, causal softmax at ``head_dim ** -0.5``, **no
rotary and no other position signal** (the Nemotron-H report; the
config's ``rope_theta`` is not read); ``Mixer = W_o concat``.

**E, LatentMoE**: ``s = sigmoid(W_r u)`` over all ``n_routed_experts``;
the ``num_experts_per_tok`` largest of ``s + b``; weights ``s`` over
their sum times ``routed_scaling_factor``
(:func:`kernels.moe.route_sigmoid`). ``l = W_down u`` (``hidden ->
moe_latent_size``); expert ``i``: ``relu(l W_up,i)^2 W_down,i`` (not
gated), in the latent; the shared expert on the stream itself:
``relu(u W_s,up)^2 W_s,down``; ``Mixer = W_up (sum of the chosen
experts held here) + shared``. **An expert-parallel share**: the chip
holds the experts ``first_expert .. first_expert + experts_held - 1``
of every expert layer, as the ``mimo`` family does
(``kernels.moe.grouped_ffn(held=...)``; no code stands in for the other
chips or their exchange).

Parameters (:func:`init_params`): ``layers`` is a list with one dict a
layer, by kind (nothing is stacked, the layers are unrolled).

The paged engine caches in **a page class and a state class**
(:func:`page_classes`): ``kv``, the attention layers' K and V rows for
every token, and ``ssm``, a slot's Mamba-2 state of every ``M`` layer:
the matrix ``S`` (H, P, N) float32 and the convolution's last
``conv_kernel - 1`` inputs (bfloat16). docs/KVCACHE.md "State classes".

**The names a planted fault replaces** (``benchmark/
faults_nemotron_h.py``): :func:`time_step`, :func:`skip_of`,
:func:`conv_bias_of`, :func:`gated_norm`, :func:`position_signal`,
:func:`route`, :func:`held_range`, :func:`shared_input`,
:func:`taken_as_zero`. Each is reached through this module's global at
trace time: do not inline them or bind them at import.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.llm.kernels import moe, ssm
from bigdl_tpu.llm.models._facade import CausalLMFacade
from bigdl_tpu.llm.models.llama import _linear, rms_norm
from bigdl_tpu.llm.models.mimo import _flat, attend_dense

PATTERN_88 = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
              "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
EXPERT_ACTIVATION = "relu2"


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    # a layer's mixer: M Mamba-2 | * attention | E experts
    hybrid_override_pattern: str = PATTERN_88
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    # the chip's share of every expert layer
    first_expert: int = 0
    experts_held: int = 512
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    # tokens of a prompt one pass of the layers takes (the engine's
    # prefill program loops over a longer prompt's chunks itself)
    prefill_chunk: int = 1024

    def __post_init__(self):
        pat = self.hybrid_override_pattern
        if len(pat) != self.num_hidden_layers or set(pat) - set("M*E"):
            raise ValueError(
                f"hybrid_override_pattern {pat!r} must name all "
                f"{self.num_hidden_layers} layers, each M, * or E")
        if not 0 <= self.first_expert <= self.first_expert \
                + self.experts_held <= self.n_routed_experts:
            raise ValueError(
                f"experts {self.first_expert} .. +{self.experts_held} "
                f"are not among {self.n_routed_experts}")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} Mamba heads are not "
                             f"whole groups of {self.n_groups}")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def attn_scale(self) -> float:
        return self.head_dim ** -0.5

    def layers_of(self, kind: str) -> List[int]:
        return [l for l, k in enumerate(self.hybrid_override_pattern)
                if k == kind]

    @property
    def num_moe_layers(self) -> int:
        return len(self.layers_of("E"))

    @classmethod
    def tiny(cls, vocab: int = 256, **over) -> "NemotronHConfig":
        """Tiny widths that keep the published shape: every kind of
        layer, several query heads a KV head, Mamba heads in groups, a
        latent narrower than the stream, a quarter of the experts held,
        a chunk shorter than a test prompt."""
        keys = dict(
            vocab_size=vocab, hidden_size=64, num_hidden_layers=5,
            hybrid_override_pattern="ME*ME", num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16,
            chunk_size=8, moe_intermediate_size=24, moe_latent_size=32,
            moe_shared_expert_intermediate_size=48, n_routed_experts=16,
            num_experts_per_tok=4, first_expert=4, experts_held=4,
            max_position_embeddings=2048, prefill_chunk=32)
        keys.update(over)
        return cls(**keys)

    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "NemotronHConfig":
        """From the keys of a ``nemotron_h`` ``config.json``.
        ``n_routed_experts`` may be the chip's share, with the published
        count under ``published`` and the share's start under
        ``first_expert``. The multi-token-prediction module
        (``num_nextn_predict_layers``) is not built. What the equations
        above do not cover is refused by name."""
        g = hf.get
        heads, p = g("mamba_num_heads", 128), g("mamba_head_dim", 64)
        unsupported = {
            "a bias (use_bias, mlp_bias, attention_bias, mamba_proj_bias)":
                any(g(k, False) for k in ("use_bias", "mlp_bias",
                                          "attention_bias",
                                          "mamba_proj_bias")),
            "use_conv_bias false": not g("use_conv_bias", True),
            "n_group/topk_group != 1":
                (g("n_group", 1), g("topk_group", 1)) != (1, 1),
            "mlp_hidden_act != relu2": g("mlp_hidden_act", "relu2")
                != "relu2",
            "mamba_hidden_act != silu": g("mamba_hidden_act", "silu")
                != "silu",
            "n_shared_experts != 1": g("n_shared_experts", 1) != 1,
            "moe_shared_expert_overlap": bool(
                g("moe_shared_expert_overlap", False)),
            "tie_word_embeddings": bool(g("tie_word_embeddings", False)),
            "sliding_window": bool(g("sliding_window")),
            "residual_in_fp32": bool(g("residual_in_fp32", False)),
            "expand * hidden_size != mamba_num_heads * mamba_head_dim":
                g("expand", 2) * g("hidden_size", 4096) != heads * p,
            "a dense MLP layer ('-' in the pattern)":
                "-" in g("hybrid_override_pattern", ""),
        }
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise NotImplementedError(
                f"nemotron_h config uses {bad}, which this family does "
                "not implement")
        names = {f.name for f in dataclasses.fields(cls)}
        keys = {k: v for k, v in hf.items() if k in names and v is not None}
        keys["layer_norm_epsilon"] = float(
            g("layer_norm_epsilon", g("norm_eps", 1e-5)))
        held = int(g("n_routed_experts"))
        keys["n_routed_experts"] = int(
            (g("published") or {}).get("n_routed_experts", held))
        keys["experts_held"] = int(g("experts_held", held))
        keys["first_expert"] = int(g("first_expert", 0))
        keys["routed_scaling_factor"] = float(
            g("routed_scaling_factor") or 1.0)
        return cls(**keys)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def qkv_widths(cfg: NemotronHConfig) -> Tuple[int, int, int]:
    d = cfg.head_dim
    return (cfg.num_attention_heads * d, cfg.num_key_value_heads * d,
            cfg.num_key_value_heads * d)


def in_proj_widths(cfg: NemotronHConfig) -> Tuple[int, int, int]:
    """Rows of ``in_proj``: the gate ``z``, ``xBC``, the time steps."""
    return cfg.d_inner, cfg.conv_dim, cfg.mamba_num_heads


def decay_params(key, cfg: NemotronHConfig, low: float = 1e-3,
                 high: float = 0.1):
    """A Mamba-2 layer's ``A_log`` and ``dt_bias`` (float32, a head):
    ``A`` evenly in 1 .. 16 (the family's initialiser) and the bias such
    that ``dt A`` at a zero projection is log-evenly in ``low .. high``:
    ``exp(dt a)`` in about 0.9 .. 0.999 a step, a state that remembers
    ten to a thousand tokens."""
    ka, kd = jax.random.split(key)
    heads = cfg.mamba_num_heads
    a = jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(kd, (heads,), jnp.float32,
                                    math.log(low), math.log(high))) / a
    return {"A_log": jnp.log(a), "dt_bias": jnp.log(jnp.expm1(dt))}


def init_params(cfg: NemotronHConfig, seed: int = 0, dtype=jnp.bfloat16,
                back: float = None) -> Dict[str, Any]:
    """Seeded parameters, drawn where JAX's default device is: every
    linear zero-mean at unit gain (the time steps' rows of ``in_proj``
    at half), the projections back into the stream (``out_proj``,
    ``o_proj``, ``latent_up``, the shared expert's down) at ``back``
    (default ``1 / sqrt(2 L)``); the decays as :func:`decay_params`,
    ``D`` and the norms 1, the convolution's taps N(0, 1/K), its bias
    N(0, 0.1^2), the router's correction bias N(0, 0.05^2)."""
    h = cfg.hidden_size
    if back is None:
        back = 1.0 / math.sqrt(2 * cfg.num_hidden_layers)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed),
                                 8 * cfg.num_hidden_layers + 8))

    def mk(shape, fan_in, gain=1.0):
        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * (gain / math.sqrt(fan_in))).astype(dtype)
        if len(shape) < 3:
            return draw(next(keys), shape)
        # an expert at a time: the float32 draw of a layer's experts
        # never exists
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(next(keys), shape[0]))

    nq = qkv_widths(cfg)[0]
    lat, i = cfg.moe_latent_size, cfg.moe_intermediate_size
    si, k = cfg.moe_shared_expert_intermediate_size, cfg.conv_kernel
    layers = []
    for kind in cfg.hybrid_override_pattern:
        lp = {"norm": jnp.ones((h,), dtype)}
        if kind == "M":
            nz, nc, nh = in_proj_widths(cfg)
            w_in = mk((nz + nc + nh, h), h)
            lp.update({
                "in_proj": {"w": w_in.at[nz + nc:].multiply(0.5)},
                "conv_w": mk((k, cfg.conv_dim), k).astype(jnp.float32),
                "conv_b": 0.1 * jax.random.normal(
                    next(keys), (cfg.conv_dim,), jnp.float32),
                **decay_params(next(keys), cfg),
                "D": jnp.ones((nh,), jnp.float32),
                "gate_norm": jnp.ones((cfg.d_inner,), dtype),
                "out_proj": {"w": mk((h, cfg.d_inner), cfg.d_inner, back)}})
        elif kind == "*":
            lp.update({"qkv_proj": {"w": mk((sum(qkv_widths(cfg)), h), h)},
                       "o_proj": {"w": mk((h, nq), nq, back)}})
        else:
            lp.update({
                "router": {
                    "w": mk((cfg.n_routed_experts, h), h),
                    "bias": 0.05 * jax.random.normal(
                        next(keys), (cfg.n_routed_experts,), jnp.float32)},
                "latent_down": {"w": mk((lat, h), h)},
                "latent_up": {"w": mk((h, lat), lat, back)},
                "shared_up": {"w": mk((si, h), h)},
                "shared_down": {"w": mk((h, si), si, back)},
                "experts": {"w_up": mk((cfg.experts_held, lat, i), lat),
                            "w_down": mk((cfg.experts_held, i, lat), i)}})
        layers.append(lp)
    return {"embed_tokens": mk((cfg.vocab_size, h), 1.0),
            "norm": jnp.ones((h,), dtype),
            "lm_head": {"w": mk((cfg.vocab_size, h), h)},
            "layers": layers}


# ---------------------------------------------------------------------------
# layer math
# ---------------------------------------------------------------------------

def time_step(dt_raw, dt_bias):
    """``softplus(dt + dt_bias)``, float32 (no clamp: the family's
    ``time_step_limit`` default is (0, inf))."""
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)


def skip_of(lp):
    """The skip's weight ``D``, one a head."""
    return lp["D"]


def conv_bias_of(lp):
    return lp["conv_b"]


def position_signal(q, k, positions, cfg: NemotronHConfig):
    """What an attention layer does to q and k for their positions:
    nothing. The Nemotron-H report trains without position embeddings
    (the Mamba-2 layers carry the order); the config's ``rope_theta``
    and ``partial_rotary_factor`` are keys the family's code does not
    read."""
    del positions, cfg
    return q, k


def taken_as_zero(array: str, fresh):
    """Whether a newly seated slot's ``array`` (``"state"`` |
    ``"conv"``) is taken as zero by the chunk that first writes it:
    ``fresh``, for both."""
    del array
    return fresh


def split_in_proj(lp, h, cfg: NemotronHConfig):
    """h (..., hidden) -> the gate z (..., d_inner), xBC (..., conv_dim)
    and the time steps (..., H) float32."""
    nz, nc, _ = in_proj_widths(cfg)
    zxd = _linear(lp["in_proj"], h)
    return (zxd[..., :nz], zxd[..., nz:nz + nc],
            time_step(zxd[..., nz + nc:], lp["dt_bias"]))


def causal_conv(lp, window, xbc):
    """The depthwise causal convolution and its silu over ``xbc`` (...,
    T, C), ``window`` (..., K - 1, C) the inputs before it: ``(xBC'
    (..., T, C) float32, the inputs with the window in front (..., K -
    1 + T, C))``, of which the last K - 1 before a position are the
    window from there on."""
    w = lp["conv_w"].astype(jnp.float32)                    # (K, C)
    full = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=-2)
    t = xbc.shape[-2]
    f32 = full.astype(jnp.float32)
    out = sum(w[j] * f32[..., j:j + t, :] for j in range(w.shape[0]))
    return jax.nn.silu(out + conv_bias_of(lp)), full


def split_xbc(xbc, cfg: NemotronHConfig):
    """xBC' (..., C) -> x (..., H, P), B and C (..., G, N)."""
    lead = xbc.shape[:-1]
    di, gn = cfg.d_inner, cfg.n_groups * cfg.ssm_state_size
    return (xbc[..., :di].reshape(lead + (cfg.mamba_num_heads, -1)),
            xbc[..., di:di + gn].reshape(lead + (cfg.n_groups, -1)),
            xbc[..., di + gn:].reshape(lead + (cfg.n_groups, -1)))


def gated_norm(y, z, w, cfg: NemotronHConfig):
    """``RMSNorm_grouped(y * silu(z))``: the gate first
    (``norm_before_gate`` false), then RMS over each of the ``G`` groups
    of ``d_inner / G`` channels, times the learned weight. (...,
    d_inner) float32 in, the weight's dtype out."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[:-1] + (cfg.n_groups, -1))
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + cfg.layer_norm_epsilon)
              ).reshape(g.shape)
    return normed.astype(w.dtype) * w


def mamba_mixer(lp, h, cfg: NemotronHConfig, core):
    """A Mamba-2 layer's mixer over ``h`` (B, T, hidden). ``core(xbc (B,
    T, conv_dim), dt (B, T, H))`` -> ``y (B, T, H, P)`` float32: the
    convolution and the recurrence, from whatever holds their state."""
    z, xbc, dt = split_in_proj(lp, h, cfg)
    y = core(xbc, dt)
    g = gated_norm(y.reshape(y.shape[:2] + (-1,)), z, lp["gate_norm"], cfg)
    return _linear(lp["out_proj"], g.astype(h.dtype))


def project_qkv(lp, h, positions, cfg: NemotronHConfig):
    """h (B, T, hidden) -> q (B, T, nh, D), k and v (B, T, hkv, D)."""
    b, t, _ = h.shape
    nq, nk, _ = qkv_widths(cfg)
    qkv = _linear(lp["qkv_proj"], h)
    q = qkv[..., :nq].reshape(b, t, -1, cfg.head_dim)
    k = qkv[..., nq:nq + nk].reshape(b, t, -1, cfg.head_dim)
    v = qkv[..., nq + nk:].reshape(b, t, -1, cfg.head_dim)
    q, k = position_signal(q, k, positions, cfg)
    return q, k, v


def route(router, h, cfg: NemotronHConfig):
    """The family's router: :func:`kernels.moe.route_sigmoid` over all
    ``n_routed_experts``, whatever the chip holds."""
    return moe.route_sigmoid(router, h, cfg.num_experts_per_tok,
                             cfg.norm_topk_prob, cfg.routed_scaling_factor)


def held_range(cfg: NemotronHConfig) -> Tuple[int, int]:
    """The experts this chip's stack holds: ``(first, count)``."""
    return cfg.first_expert, cfg.experts_held


def shared_input(lp, h, latent):
    """What the shared expert reads: the stream itself."""
    del lp, latent
    return h


def relu2_mlp(up, down, h):
    """``relu(h W_up)^2 W_down``: an expert that is not gated."""
    a = jnp.square(jax.nn.relu(_linear(up, h).astype(jnp.float32)))
    return _linear(down, a.astype(h.dtype))


def expert_mixer(lp, h, live, cfg: NemotronHConfig):
    """The expert layer's mixer for ``h`` (T, hidden); ``live`` (T,) the
    rows that count: the held experts' part of the routed sum, computed
    in the latent and projected up, and the shared expert. Returns ``(y
    (T, hidden) in h's dtype, stats (4,) int32 as the ``mimo`` family's:
    assignments computed here, assignments left to the chips that hold
    the other experts, held experts with a token, the fullest held
    expert's tokens; chosen experts (T, k))``."""
    idx, w = route(lp["router"], h, cfg)
    latent = _linear(lp["latent_down"], h)
    first, count = held_range(cfg)
    y, sizes = moe.grouped_ffn(
        latent, idx, w, live, lp["experts"]["w_up"],
        lp["experts"]["w_down"], 0, count, held=(first, count),
        activation=EXPERT_ACTIVATION)
    out = _linear(lp["latent_up"], y.astype(h.dtype)) + relu2_mlp(
        lp["shared_up"], lp["shared_down"], shared_input(lp, h, latent))
    return (out.astype(h.dtype),
            moe.share_stats(sizes, live, idx.shape[1]), idx)


def _decoder(params, cfg: NemotronHConfig, x, positions, mamba, attend,
             live):
    """The unrolled layers over the stream ``x`` (B, T, hidden) at
    ``positions`` (B, T). ``mamba(i, lp)`` -> the ``core`` of the
    ``i``-th Mamba-2 layer (:func:`mamba_mixer`); ``attend(i, q, k,
    v)`` -> the ``i``-th attention layer's heads (B, T, nh, D). Returns ``(x after the final
    norm, the attention layers' (k, v), stats (4,), chosen experts an
    expert layer)``."""
    b, t, hid = x.shape
    eps = cfg.layer_norm_epsilon
    kept, chosen = [], []
    stats = jnp.zeros(4, jnp.int32)
    seen = {"M": 0, "*": 0}
    for kind, lp in zip(cfg.hybrid_override_pattern, params["layers"]):
        h = rms_norm(x, lp["norm"], eps)
        if kind == "M":
            y = mamba_mixer(lp, h, cfg, mamba(seen["M"], lp))
            seen["M"] += 1
        elif kind == "*":
            q, k, v = project_qkv(lp, h, positions, cfg)
            o = attend(seen["*"], q, k, v).astype(x.dtype)
            kept.append((k, v))
            seen["*"] += 1
            y = _linear(lp["o_proj"], o.reshape(b, t, -1))
        else:
            y, s, idx = expert_mixer(lp, h.reshape(-1, hid),
                                     live.reshape(-1), cfg)
            y = y.reshape(b, t, hid)
            stats = stats + s
            chosen.append(idx)
        x = x + y
    return rms_norm(x, params["norm"], eps), kept, stats, chosen


# ---------------------------------------------------------------------------
# dense forward (generate(), the parity tests' golden)
# ---------------------------------------------------------------------------

def init_cache(cfg: NemotronHConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """A row's whole cache: contiguous K and V of every attention layer,
    and of every Mamba-2 layer the state (float32) and the
    convolution's window."""
    n_m = len(cfg.layers_of("M"))
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    return {"kv": [(jnp.zeros((batch, max_len, hkv, d), dtype),
                    jnp.zeros((batch, max_len, hkv, d), dtype))
                   for _ in cfg.layers_of("*")],
            "ssm": jnp.zeros((n_m, batch, cfg.mamba_num_heads,
                              cfg.mamba_head_dim, cfg.ssm_state_size),
                             jnp.float32),
            "conv": jnp.zeros((n_m, batch, cfg.conv_kernel - 1,
                               cfg.conv_dim), dtype),
            "pos": jnp.zeros((), jnp.int32)}


def forward(params: Dict[str, Any], cfg: NemotronHConfig,
            tokens: jnp.ndarray, cache: Dict[str, Any],
            positions: jnp.ndarray, routes: bool = False):
    """(B, T) tokens at ``positions`` from ``cache``: logits (B, T, V)
    float32 and the cache after them (the chunked form over the whole
    of ``T``); with ``routes`` also the experts every token chose, a
    list of (B·T, k) an expert layer."""
    x = params["embed_tokens"][tokens]
    start, t = cache["pos"], tokens.shape[1]
    keep = cfg.conv_kernel - 1
    s_new, w_new, kv_new = [], [], []

    def mamba(i, lp):
        def core(xbc, dt):
            act, full = causal_conv(lp, cache["conv"][i], xbc)
            xs, bm, cm = split_xbc(act, cfg)
            a = -jnp.exp(lp["A_log"])
            y, s = jax.vmap(lambda s0, *r: ssm.ssd_dense(
                s0, *r, a, skip_of(lp), sub=cfg.chunk_size))(
                cache["ssm"][i], xs, bm, cm, dt)
            s_new.append(s)
            w_new.append(full[:, -keep:].astype(cache["conv"].dtype))
            return y
        return core

    def attend(i, q, k, v):
        k_all, v_all = cache["kv"][i]
        k_all = jax.lax.dynamic_update_slice(
            k_all, k.astype(k_all.dtype), (0, start, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            v_all, v.astype(v_all.dtype), (0, start, 0, 0))
        kv_new.append((k_all, v_all))
        return attend_dense(q, k_all, v_all, positions, start + t, cfg, 0,
                            None)

    x, _, _, chosen = _decoder(params, cfg, x, positions, mamba, attend,
                               jnp.ones(tokens.shape, bool))
    logits = _linear(params["lm_head"], x).astype(jnp.float32)
    cache = {"kv": kv_new, "ssm": jnp.stack(s_new),
             "conv": jnp.stack(w_new), "pos": start + t}
    if routes:
        return logits, cache, chosen
    return logits, cache


# ---------------------------------------------------------------------------
# the paged engine's entry points
# ---------------------------------------------------------------------------

def page_classes(cfg: NemotronHConfig):
    """A page class and a state class side by side (docs/KVCACHE.md):
    ``kv``, the attention layers' K and V rows for every token; ``ssm``,
    of every Mamba-2 layer the state a head (float32) and the
    convolution's last inputs (bfloat16), a slot."""
    from bigdl_tpu.llm.kvcache.classes import PageClass, StateClass
    return [PageClass("kv", len(cfg.layers_of("*")),
                      cfg.num_key_value_heads, cfg.head_dim, cfg.head_dim),
            StateClass("ssm", len(cfg.layers_of("M")), holds=(
                ("state", (cfg.mamba_num_heads, cfg.mamba_head_dim,
                           cfg.ssm_state_size), "float32"),
                ("conv", (cfg.conv_kernel - 1, cfg.conv_dim),
                 "bfloat16")))]


# the decode step's stats vector, appended to the fetched token vector:
# summed over the step's expert layers (kernels.sampling), the ``mimo``
# family's four under their names
STEP_STATS = ("moe_assignments_total", "moe_assignments_elsewhere_total",
              "moe_experts_touched_total", "moe_max_load_total")


def state_bytes_a_row(cfg: NemotronHConfig) -> int:
    """What one decode step must move of one live row's state: every
    Mamba-2 layer's matrix, read and written."""
    return len(cfg.layers_of("M")) * ssm.decode_bytes(
        1, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)


def host_step_stats(cfg: NemotronHConfig, ctx_lens) -> Dict[str, int]:
    """What the host knows of a decode step it dispatches: the expert
    and Mamba-2 layers it runs, its live rows, the cached tokens they
    attend, and the bytes of state they make :func:`kernels.ssm.
    ssm_decode` read and write (whatever their contexts)."""
    rows = len(ctx_lens)
    return {"moe_layer_steps_total": cfg.num_moe_layers,
            "moe_token_layers_total": rows * cfg.num_moe_layers,
            "kv_ctx_tokens_total": int(ctx_lens.sum()),
            "ssm_rows_total": rows,
            "ssm_layer_steps_total": len(cfg.layers_of("M")),
            "ssm_state_bytes_moved_total": rows * state_bytes_a_row(cfg)}


def host_prefill_stats(cfg: NemotronHConfig, tokens: int,
                       bucket: int) -> Dict[str, int]:
    """Of a prefill of ``tokens`` positions the host dispatches in the
    program of ``bucket``: the chunks its state is carried through, a
    Mamba-2 layer, and the positions the chunked form computes for them
    (a chunk is computed whole, its padding too; a bucket shorter than
    a chunk is one chunk of its own length)."""
    chunk = min(bucket, cfg.prefill_chunk)
    chunks = -(-tokens // chunk) * len(cfg.layers_of("M"))
    return {"prefill_ssm_chunks_total": chunks,
            "prefill_ssm_positions_total": chunks * chunk}


def paged_decode_step(params, cfg: NemotronHConfig, pools, others, bt, lens,
                      toks, *, page: int):
    """One decode step over both classes. ``pools`` is ``(K pool, the
    states)``, ``others`` ``(V pool, the convolution windows)``, ``bt``
    ``(the block table (B, maxp), the state row of each batch row (B,
    1))``, by class. The attention layers read their pool inside the
    layers and write the new token's K and V after them
    (``llama.paged_decode_step``'s shape); a Mamba-2 layer updates its
    rows of the state in place (:func:`kernels.ssm.ssm_decode`) and
    writes the window back. Rows with ``lens == 0`` are the sampled
    step's masked lanes: they route to no expert and their state row is
    the trash row. Returns ``(logits (B, V) f32, pools, others, stats
    (4,))``."""
    from bigdl_tpu.llm.kvcache.prefill import paged_attend
    from bigdl_tpu.llm.kvcache.write import scatter_new_kv
    (k_pages, state), (v_pages, conv) = pools, others
    table, rows = bt[0], bt[1][:, 0]
    b = toks.shape[0]
    shapes = state.shape, conv.shape
    per_layer = state.shape[1]
    state, conv = _flat(state), _flat(conv)
    live = rows > 0
    x = params["embed_tokens"][toks][:, None]
    attend_kv = paged_attend(k_pages, v_pages, table, lens, page=page)

    def mamba(i, lp):
        def core(xbc, dt):
            nonlocal state, conv
            at = rows + i * per_layer
            act, full = causal_conv(lp, conv[at], xbc)
            conv = conv.at[at].set(full[:, 1:].astype(conv.dtype))
            xs, bm, cm = split_xbc(act[:, 0], cfg)
            y, state = ssm.ssm_decode(
                state, xs, bm, cm, dt[:, 0], -jnp.exp(lp["A_log"]),
                skip_of(lp), at, live)
            return y[:, None]
        return core

    def attend(i, q, k, v):
        return attend_kv(i, q, k, v).reshape(b, 1, -1, cfg.head_dim)

    x, kept, stats, _ = _decoder(
        params, cfg, x, lens[:, None].astype(jnp.int32), mamba, attend,
        (lens > 0)[:, None])
    logits = _linear(params["lm_head"], x)
    k_new = jnp.stack([k[:, 0] for k, _ in kept])
    v_new = jnp.stack([v[:, 0] for _, v in kept])
    k_pages, v_pages = scatter_new_kv(k_pages, v_pages, table, lens, k_new,
                                      v_new, page=page)
    return (logits[:, 0].astype(jnp.float32),
            (k_pages, state.reshape(shapes[0])),
            (v_pages, conv.reshape(shapes[1])), stats)


from bigdl_tpu.llm.kernels.sampling import make_sampled_step  # noqa: E402

paged_decode_step_sampled = make_sampled_step(paged_decode_step)


def _prefill_chunk(params, cfg: NemotronHConfig, k_pages, v_pages, state,
                   conv, toks, n_live, start, bt_row, row, fresh, phys,
                   slots, *, page: int):
    """One pass of the layers over ``toks`` (1, C) at positions ``start
    ..``, of which the first ``n_live`` count: attention over what the
    pool holds below ``start`` and the chunk itself, then the chunk's K
    and V written page by page; the row's state and window carried
    through it (each taken as zero where ``fresh``). ``state`` and
    ``conv`` are flat. Returns ``(k_pages, v_pages, state, conv, x (C,
    hidden) after the final norm)``."""
    from bigdl_tpu.llm.kvcache.prefill import (ragged_prefill_attend,
                                               scatter_suffix_kv)
    c = toks.shape[1]
    per_layer = state.shape[0] // len(cfg.layers_of("M"))
    keep = cfg.conv_kernel - 1
    x = params["embed_tokens"][toks]
    attend_kv = ragged_prefill_attend(k_pages, v_pages, bt_row, start,
                                      n_live, page=page)

    def mamba(i, lp):
        def core(xbc, dt):
            nonlocal state, conv
            at = row + i * per_layer
            window = jnp.where(taken_as_zero("conv", fresh), 0, conv[at])
            act, full = causal_conv(lp, window[None], xbc)
            # the last inputs before position n_live: the window from
            # there on
            conv = jax.lax.dynamic_update_slice_in_dim(
                conv, jax.lax.dynamic_slice_in_dim(
                    full[0], n_live, keep).astype(conv.dtype)[None], at, 0)
            xs, bm, cm = split_xbc(act[0], cfg)
            y, state = ssm.ssd_prefill_chunk(
                state, xs, bm, cm, dt[0], -jnp.exp(lp["A_log"]),
                skip_of(lp), at, taken_as_zero("state", fresh), n_live,
                sub=cfg.chunk_size)
            return y[None]
        return core

    def attend(i, q, k, v):
        # attend at pool precision, as a later decode step will read it
        return attend_kv(i, q, k.astype(k_pages.dtype),
                         v.astype(v_pages.dtype))

    positions = (start + jnp.arange(c, dtype=jnp.int32))[None]
    x, kept, _, _ = _decoder(params, cfg, x, positions, mamba, attend,
                             positions - start < n_live)
    k_pages, v_pages = scatter_suffix_kv(
        k_pages, v_pages, phys, slots,
        jnp.stack([k[0] for k, _ in kept]),
        jnp.stack([v[0] for _, v in kept]))
    return k_pages, v_pages, state, conv, x[0]


def paged_prefill_ragged(params, cfg: NemotronHConfig, pools, others, toks,
                         length, offset, bt_row, phys, slots, fork_dst,
                         fork_src, *, page: int):
    """Prefill of one whole prompt in the engine's ragged-prefill shape.
    ``pools``, ``others``, ``bt_row`` and ``phys`` are pairs, the page
    class then the state class, whose ``bt_row`` (1,) is the state row
    of the slot the request was seated in. What that row holds, in BOTH
    arrays, is its last occupant's and is **taken as zero by the first
    chunk** (``offset`` 0: the features that would resume a prompt
    refuse this family): a convolution window left behind would poison
    the first ``conv_kernel - 1`` positions and nothing after them. A
    bucket longer than ``cfg.prefill_chunk`` is taken a chunk at a time
    inside the program: chunk ``c`` reads the K and V chunks ``< c``
    wrote through the table and carries state and window on, and only
    as many chunks run as ``length`` needs. Returns ``(pools, others,
    last_logits (V,) f32)``."""
    del fork_dst, fork_src
    (k_pages, state), (v_pages, conv) = pools, others
    table, row = bt_row[0], bt_row[1][0]
    bucket = toks.shape[1]
    chunk = min(bucket, cfg.prefill_chunk)
    shapes = state.shape, conv.shape
    state, conv = _flat(state), _flat(conv)

    def one(c, k_pages, v_pages, state, conv):
        at = c * chunk
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, chunk, axis=-1)
        *carry, x = _prefill_chunk(
            params, cfg, k_pages, v_pages, state, conv, cut(toks),
            jnp.clip(length - at, 0, chunk), offset + at, table, row,
            (offset == 0) & (c == 0), cut(phys[0]), cut(slots), page=page)
        return (*carry, jax.lax.dynamic_index_in_dim(
            x, jnp.clip(length - 1 - at, 0, chunk - 1), 0, keepdims=True))

    if bucket == chunk:
        k_pages, v_pages, state, conv, last = one(
            jnp.int32(0), k_pages, v_pages, state, conv)
    else:
        k_pages, v_pages, state, conv, last = jax.lax.fori_loop(
            0, (length + chunk - 1) // chunk,
            lambda c, carry: one(c, *carry[:4]),
            (k_pages, v_pages, state, conv,
             jnp.zeros((1, cfg.hidden_size), params["embed_tokens"].dtype)))
    logits = _linear(params["lm_head"], last)
    return ((k_pages, state.reshape(shapes[0])),
            (v_pages, conv.reshape(shapes[1])),
            logits[0].astype(jnp.float32))


class NemotronHForCausalLM(CausalLMFacade):
    """Generation facade — shared driver (see models._facade)."""

    _forward = staticmethod(forward)
    _init_cache = staticmethod(init_cache)
    _init_params = staticmethod(init_params)

    @staticmethod
    def _quantize_params(params, qtype):
        raise NotImplementedError(
            "the nemotron_h family runs bf16: its state is float32 and "
            "its expert stacks are not ggml-quantized yet")
