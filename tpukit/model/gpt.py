"""Pure-JAX GPT-style decoder-only language model.

TPU-native twin of reference `models/gpt.py` (`TransformerDecoderLM`,
models/gpt.py:187-231). The model is a pure function over a parameter pytree:
`init_params(rng, config)` builds the pytree, `forward(params, config, ...)`
computes logits. There are no modules, no wrappers — parallelism is applied
from the outside as sharding on the pytree (see tpukit/shardings.py) or as a
pipeline schedule over the stacked layer parameters (see tpukit/pipeline.py).

Architecture (matching the reference layer by layer):
  - Embeddings: token + learned absolute position embeddings, summed
    (models/gpt.py:169-185). The reference's `Embeddings.__init__` reads
    `self.dim` before assigning it (models/gpt.py:177, AttributeError);
    the intended behavior — embed to `dim` — is implemented here.
  - DecoderLayer, pre-LN: `x + attn(norm1(x))`, `x + ffn(norm2(x))`
    (models/gpt.py:124-135).
  - SelfAttention: separate q/k/v projections without bias
    (`qkv_bias=False` default, models/gpt.py:50,60-62), output projection
    with bias (models/gpt.py:64), scale `1/sqrt(head_dim)` (models/gpt.py:66).
    Attention math lives in tpukit/ops/attention.py.
  - FeedForward: up-proj x4 -> relu -> down-proj -> **relu again** -> dropout
    (models/gpt.py:33-41). The second activation after down_proj is unusual
    but deliberate reference behavior; twinned faithfully.
  - Final LayerNorm then untied `lm_head = Linear(dim, vocab, bias=False)`
    (models/gpt.py:217-219).
  - `forward(input_ids, position_ids, mask)` twin of models/gpt.py:221-231;
    the reference passes an undefined `x` into embeddings (models/gpt.py:227)
    — intended `input_ids`, implemented as intended.

Layer parameters are **stacked** along a leading `num_layers` axis and the
decoder trunk is a `lax.scan` — one compiled layer body regardless of depth,
and a layout that reshapes directly into `[stages, layers_per_stage, ...]`
for pipeline parallelism.

Numerics: parameters are float32; matmuls run in `config.compute_dtype`
(bfloat16 by default — the TPU-native equivalent of the reference's
`torch.autocast(dtype=bfloat16)`, main-single.py:88-90); LayerNorm/softmax/
loss run in float32, matching autocast's op policy.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from tpukit.ops.attention import causal_attention
from tpukit.ops.layers import dropout, linear
from tpukit.ops.layers import layer_norm as _layer_norm
from tpukit.ops.moe_dispatch import moe_ffn_a2a, moe_ffn_xla

Params = Any  # nested dict pytree of jax.Array


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Model hyper-parameters.

    Defaults mirror the reference CLI defaults (main-single.py:156-162):
    dim 256, head_dim 32, heads 8, num_layers 8, seq 256, GPT-2 vocab.
    """

    dim: int = 256
    head_dim: int = 32
    heads: int = 8
    num_layers: int = 8
    vocab_size: int = 50257
    max_position_embeddings: int = 256
    dropout: float = 0.0
    ffn_mult: int = 4  # reference FeedForward mult=4 (models/gpt.py:14)
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # "auto" picks per shape: XLA's fused attention below 512 tokens, the
    # Pallas flash kernel (tpukit/ops/pallas_attention.py) at 512 and above.
    # "ring" runs sequence-sharded ring attention (tpukit/ring_attention.py)
    # over the `ring_axis` mesh axis — only valid inside shard_map.
    attention_impl: str = "auto"  # "auto" | "xla" | "flash" | "ring" | "ulysses"
    ring_axis: str = "seq"
    # Sequence layout of the ring shards: "contiguous" (device d holds rows
    # [d*Sl, (d+1)*Sl)) or "zigzag" (device d holds chunks d and 2P-1-d of
    # 2P — causally load-balanced; see tpukit/ring_attention.py). Only
    # meaningful with attention_impl="ring"; ContextParallel sets it and
    # permutes the batch to match.
    ring_layout: str = "contiguous"
    # TPU perf: the embedding table and lm_head are padded so the vocab
    # dimension is a multiple of this (50257 -> 50304, a 128-lane multiple —
    # the dominant matmul of the small-dim reference shape tiles cleanly
    # onto the MXU). Logits for pad columns are forced to -1e9, so softmax,
    # loss, accuracy, and argmax sampling are unchanged; pad rows/columns
    # receive zero gradient. Set to 1 to disable.
    vocab_pad_multiple: int = 128
    # Layer-stack execution. scan_layers=False unrolls the trunk into
    # num_layers inlined blocks: measured on v5e this cuts the train step
    # ~20% at the reference depth (the scan's stacked-residual saves — a
    # dynamic-update-slice plus copy per layer — were the single largest
    # item in the profile). scan_layers=True keeps one compiled layer body:
    # use it for depths where compile time or code size matters.
    scan_layers: bool = False
    # remat_layers=True checkpoints each decoder layer: backward recomputes
    # the layer forward instead of loading saved residuals — less HBM
    # traffic AND less memory (slightly faster on v5e, and required for the
    # larger ladder configs at long sequence).
    remat_layers: bool = False
    # Compute q/k/v as one fused [dim, 3*inner] matmul (bitwise-identical
    # column blocks, better MXU tiling). TensorParallel disables this: its
    # kernels are column-sharded and concatenating along the sharded axis
    # would re-lay-out the weights every step.
    fuse_qkv: bool = True
    # Mixture-of-experts FFN (beyond-reference: the cookbook has no MoE,
    # SURVEY §2.4 marks EP "not required"). num_experts > 0 replaces every
    # layer's FFN with a Switch-style top-1 routed expert bank: a linear
    # router picks one expert per token, tokens dispatch into fixed-size
    # per-expert buffers (capacity = ceil(tokens/E * capacity_factor) —
    # STATIC shapes, the TPU requirement), overflow tokens fall through the
    # residual with zero FFN output, and a load-balance aux loss
    # (Switch Transformer eq. 4: E * sum(frac_tokens_e * mean_prob_e))
    # keeps routing uniform. Each expert applies the reference FFN
    # (up -> relu -> down -> relu, the double-relu quirk preserved). See
    # tpukit/shardings.py ExpertParallel for the expert-sharded execution.
    num_experts: int = 0
    expert_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Compute the load-balance statistics over REAL tokens only (the Switch
    # paper's convention), excluding pad positions from frac_tokens /
    # mean_prob and normalizing by each row's real-token count (ADVICE r5
    # #2). False restores the previous behavior — statistics averaged over
    # every position including pads — for comparing against pre-round-8
    # training curves. Only the aux-loss VALUE changes; routing, dispatch,
    # and the FFN outputs are identical either way, and unpadded batches
    # produce the same aux under both settings.
    moe_aux_mask_pads: bool = True
    # routed experts per token: 1 = Switch (default), 2 = GShard/Mixtral-
    # style top-2. Gates stay the RAW router probabilities (GShard
    # convention) so top_k=1 is bit-identical to the Switch path.
    router_top_k: int = 1
    # Expert dispatch dataflow (tpukit/ops/moe_dispatch.py). "xla": global
    # one-hot einsums, partitioning left to GSPMD — the right spelling on
    # one device / pure DP, and the default so the parity goldens and the
    # single-chip bench path are untouched. "a2a": explicit shard_map
    # dispatch — tokens pack into per-expert capacity buffers and move
    # through a hand-placed lax.all_to_all pair over `moe_mesh`'s `expert`
    # axis in BOTH forward and backward. "pallas" (tpukit/ops/moe_gemm.py,
    # round 11): the fused grouped-expert GEMM — sort tokens by expert and
    # run a blocked segment GEMM, no capacity buffer, dropless unless
    # moe_capacity is set; under ExpertParallel it composes after the a2a
    # exchange. ExpertParallel injects its dispatch (and the mesh) at loss
    # time; plain model calls see only what the caller configured.
    moe_dispatch: str = "xla"  # "xla" | "a2a" | "pallas"
    moe_mesh: Any = None  # jax Mesh with an 'expert' axis (a2a/pallas under EP)
    # `(mesh, batch_axes, head_axes)`: how the strategy's GSPMD jit shards
    # batch and heads, for the flash kernel's per-shard call
    # (pallas_attention.per_shard). Injected at loss time like `moe_mesh`
    # (Strategy.kernel_shard); None on one device and in plain model calls.
    kernel_shard: Any = None
    # Explicit per-row expert capacity. 0 (default) keeps the derived
    # capacity (ceil(max_position * top_k * capacity_factor / E)) for the
    # buffer dispatches and makes the "pallas" dispatch DROPLESS; > 0
    # overrides the derived value on every dispatch — the same cumsum drop
    # mask everywhere, so "pallas" capacity mode drops the bit-identical
    # token set the buffer paths drop (tests/test_moe.py).
    moe_capacity: int = 0
    # Collective payload dtype (tpukit/ops/quant_comm.py, round 12 —
    # EQuARX-style). "f32" (default): the exact pre-round-12 collectives,
    # byte-identical HLO. "bf16"/"int8": the strategies with hand-wired
    # quantized collectives (DataParallel grad psum, FSDP grad
    # reduce-scatter, ExpertParallel a2a dispatch payload) compress the
    # wire payload — int8 is block-scaled (per-256-element max-abs f32
    # scale sidecar packed into the payload) with f32 accumulation and
    # f32 master params/optimizer throughout. Strategies without wired
    # collectives reject non-f32 values at validate_config.
    comm_dtype: str = "f32"  # "f32" | "bf16" | "int8"
    # Stochastic rounding for the int8 quantizer (floor(x/scale + U[0,1)):
    # unbiased per element, the EQuARX option against long-horizon rounding
    # drift). Default OFF — round-to-nearest-even.
    quant_stochastic: bool = False
    # Overlap-scheduled gradient collectives (round 18, ROADMAP #5 —
    # tpukit/ops/quant_comm.py bucket scheduler). 0 (default): the serial
    # schedule — one flattened payload after backward completes,
    # byte-identical HLO to round 17. N >= 1: DataParallel/FSDP partition
    # the grad tree into N ~equal-byte buckets in layer-reversed
    # (backward-completion) order and issue each bucket's collective the
    # moment its grads exist, so the remaining backward compute hides the
    # wire (1 = the serial schedule expressed in the bucket machinery —
    # the bit-parity reference for the f32 tests). Composes with
    # --comm_dtype: the int8 wire cut and the overlap win stack. Under
    # ExpertParallel the a2a exchange is already per-layer, so any
    # N >= 1 declares the hlolint `overlap` gate without changing the
    # dataflow. Strategies without a hand-placed grad wire reject N > 0
    # at validate_config.
    grad_buckets: int = 0
    # Interleaved virtual pipeline stages (round 22, ROADMAP #5 —
    # tpukit/pipeline.py Pipeline1F1B + tpukit/pipeline_schedule.py).
    # 1 (default): each pipeline device owns ONE contiguous layer block —
    # the existing GPipe/1F1B schedules, byte-identical HLO. V > 1: device
    # d owns V non-contiguous chunks (global chunks d, d+S, d+2S, ... of
    # the layer stack), and the 1F1B tick machine runs a static interleaved
    # tick table (Megatron-LM's interleaved 1F1B) so the warm-up/cool-down
    # bubble shrinks toward (S-1)/(M*V) at equal micro count M. Only the
    # explicit-vjp 1f1b schedule interleaves; Pipeline (GPipe) rejects
    # V > 1 at validate_config with a named error.
    virtual_stages: int = 1
    # Fused paged decode (round 21, ROADMAP #3 — tpukit/ops/
    # paged_attention.py). False (default): the paged decode path keeps
    # its per-layer gather_view + _attend_over_cache trace byte-unchanged.
    # True: T==1 paged steps route attention through the fused Pallas
    # kernel — block tables dereferenced inside the kernel, int8 pages
    # dequantized tile-by-tile in VMEM, single-block flash softmax —
    # the gathered view's math op-for-op (~1-ULP dot reassociation only;
    # token streams exactly identical — tests/test_paged_attention.py).
    # Prefill chunks (T>1) and the pool write-back stay on the shared
    # unfused spellings either way.
    fused_decode: bool = False

    def __post_init__(self):
        if self.comm_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"comm_dtype={self.comm_dtype!r} must be 'f32', 'bf16' or "
                f"'int8'"
            )
        if self.grad_buckets < 0:
            raise ValueError(
                f"grad_buckets={self.grad_buckets} must be >= 0 (0 = the "
                f"serial schedule, N = bucket count)"
            )
        if self.num_experts > 0 and not (1 <= self.router_top_k <= self.num_experts):
            raise ValueError(
                f"router_top_k={self.router_top_k} must be in [1, "
                f"num_experts={self.num_experts}] — silently clamping would "
                f"train a different routing than the one requested"
            )
        if self.moe_dispatch not in ("xla", "a2a", "pallas"):
            raise ValueError(
                f"moe_dispatch={self.moe_dispatch!r} must be 'xla', 'a2a' "
                f"or 'pallas'"
            )
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages={self.virtual_stages} must be >= 1 (1 = "
                f"one contiguous layer block per pipeline stage, V > 1 = "
                f"interleaved chunks under the 1f1b schedule)"
            )

    @property
    def inner_dim(self) -> int:
        return self.head_dim * self.heads

    @property
    def padded_vocab_size(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab_size // m) * m

    def replace(self, **kw) -> "GPTConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Initialization.
#
# Distributions twin the torch defaults the reference inherits:
#   nn.Linear   -> kernel & bias ~ U(-1/sqrt(fan_in), 1/sqrt(fan_in))
#   nn.Embedding-> N(0, 1)
#   nn.LayerNorm-> scale 1, bias 0
# --------------------------------------------------------------------------


def _linear_params(rng, fan_in: int, fan_out: int, bias: bool, dtype) -> dict:
    bound = 1.0 / jnp.sqrt(fan_in)
    k_rng, b_rng = jax.random.split(rng)
    p = {"kernel": jax.random.uniform(k_rng, (fan_in, fan_out), dtype, -bound, bound)}
    if bias:
        p["bias"] = jax.random.uniform(b_rng, (fan_out,), dtype, -bound, bound)
    return p


def _layer_norm_params(dim: int, dtype) -> dict:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def _init_decoder_layer(rng, cfg: GPTConfig) -> dict:
    """One DecoderLayer (models/gpt.py:108-135): attn + ffn + two norms.
    With cfg.num_experts > 0 the ffn is a router + stacked expert bank
    (leading axis num_experts on every expert leaf)."""
    rngs = jax.random.split(rng, 7)
    dtype = cfg.param_dtype
    if cfg.num_experts > 0:
        up = partial(
            _linear_params, fan_in=cfg.dim, fan_out=cfg.dim * cfg.ffn_mult,
            bias=True, dtype=dtype,
        )
        down = partial(
            _linear_params, fan_in=cfg.dim * cfg.ffn_mult, fan_out=cfg.dim,
            bias=True, dtype=dtype,
        )
        ffn = {
            "router": _linear_params(rngs[6], cfg.dim, cfg.num_experts, bias=False, dtype=dtype),
            "experts": {
                "up": jax.vmap(up)(jax.random.split(rngs[4], cfg.num_experts)),
                "down": jax.vmap(down)(jax.random.split(rngs[5], cfg.num_experts)),
            },
        }
    else:
        ffn = {
            "up": _linear_params(rngs[4], cfg.dim, cfg.dim * cfg.ffn_mult, bias=True, dtype=dtype),
            "down": _linear_params(rngs[5], cfg.dim * cfg.ffn_mult, cfg.dim, bias=True, dtype=dtype),
        }
    return {
        "norm1": _layer_norm_params(cfg.dim, dtype),
        "attn": {
            "q": _linear_params(rngs[0], cfg.dim, cfg.inner_dim, bias=False, dtype=dtype),
            "k": _linear_params(rngs[1], cfg.dim, cfg.inner_dim, bias=False, dtype=dtype),
            "v": _linear_params(rngs[2], cfg.dim, cfg.inner_dim, bias=False, dtype=dtype),
            "out": _linear_params(rngs[3], cfg.inner_dim, cfg.dim, bias=True, dtype=dtype),
        },
        "norm2": _layer_norm_params(cfg.dim, dtype),
        "ffn": ffn,
    }


def init_params(rng: jax.Array, cfg: GPTConfig) -> Params:
    """Build the full parameter pytree. Layer params are stacked: every leaf
    under `params["layers"]` has a leading `num_layers` axis."""
    emb_rng, pos_rng, head_rng, layers_rng = jax.random.split(rng, 4)
    dtype = cfg.param_dtype
    layer_rngs = jax.random.split(layers_rng, cfg.num_layers)
    layers = jax.vmap(partial(_init_decoder_layer, cfg=cfg))(layer_rngs)
    # vocab dims are padded to the lane multiple (cfg.padded_vocab_size);
    # pad rows are never gathered and pad logits are masked in apply_head
    return {
        "embeddings": {
            "token": jax.random.normal(emb_rng, (cfg.padded_vocab_size, cfg.dim), dtype),
            "position": jax.random.normal(pos_rng, (cfg.max_position_embeddings, cfg.dim), dtype),
        },
        "layers": layers,
        "norm_out": _layer_norm_params(cfg.dim, dtype),
        "lm_head": _linear_params(head_rng, cfg.dim, cfg.padded_vocab_size, bias=False, dtype=dtype),
    }


def param_count(params: Params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------
# Forward pass, decomposed into embed / trunk / head so the pipeline recipe
# can place the pieces on stages (reference main-pipe.py:52-68 puts
# embeddings on the first stage and norm+lm_head on the last).
# --------------------------------------------------------------------------


# Device-side names (`jax.named_scope`): metadata on the ops, no instruction
# added. One spelling for the training and the cached/paged forwards: embed,
# attn, ffn, moe, ln, head here; kv_gather, kv_write, attend where the cache
# is read and written. `tpukit.obs.xla.instruction_scopes` maps a compiled
# module's instructions back to them.
layer_norm = jax.named_scope("ln")(_layer_norm)


@jax.named_scope("embed")
def apply_embeddings(params: Params, cfg: GPTConfig, input_ids, position_ids) -> jax.Array:
    """Token + position embedding sum (models/gpt.py:180-185), cast to the
    compute dtype."""
    emb = params["embeddings"]
    x = jnp.take(emb["token"], input_ids, axis=0) + jnp.take(emb["position"], position_ids, axis=0)
    return x.astype(cfg.compute_dtype)


@jax.named_scope("ffn")
def _apply_feed_forward(layer, cfg: GPTConfig, x, rng, deterministic):
    """FeedForward (models/gpt.py:33-41): up -> relu -> down -> relu -> drop.
    The post-down_proj activation is the reference's (unusual) behavior."""
    h = linear(x, layer["ffn"]["up"], cfg.compute_dtype)
    h = jax.nn.relu(h)
    h = linear(h, layer["ffn"]["down"], cfg.compute_dtype)
    h = jax.nn.relu(h)
    return dropout(h, cfg.dropout, rng, deterministic)


@jax.named_scope("moe")
def _apply_moe_ffn(layer, cfg: GPTConfig, x, rng, deterministic, pad_mask=None):
    """Routed mixture-of-experts FFN: Switch-style top-1 by default,
    GShard/Mixtral-style top-k via cfg.router_top_k. Returns (out, aux).

    `pad_mask` (optional `[B, S]` bool, True = padding — the attention
    convention) only affects the load-balance STATISTICS: with
    cfg.moe_aux_mask_pads (default) pad positions are excluded from
    frac_tokens/mean_prob and each row normalizes by its real-token count,
    so heavily padded batches no longer dilute the balance signal toward
    how pads route (ADVICE r5 #2). Dispatch itself still routes every
    position — masking dispatch would change the FFN outputs and break
    the width-invariance contract below.

    TPU-first design: STATIC shapes throughout — tokens dispatch into
    fixed capacity buffers, each expert runs the reference FFN (up -> relu
    -> down -> relu, the double-relu quirk, models/gpt.py:33-41) as one
    batched matmul pair on the MXU, and the gated combine returns results
    to their residual positions. Capacity is PER ROW (position within an
    expert = causal cumsum of its assignment mask along the sequence), so
    rows never compete for expert slots, and it derives from the STATIC
    max_position_embeddings — not the call's sequence width — so a row's
    dispatch is identical whatever buffer padding surrounds it: eval
    losses are batch-composition-independent and the batched decode stays
    token-for-token equal to the serial one even when their buffer widths
    differ. Tokens beyond an expert's row capacity get zero FFN output
    (they ride the residual stream). Router math is f32 (softmax stability
    under bf16 compute). `aux` is the Switch load-balance loss
    E * sum(frac_tokens_e * mean_router_prob_e), averaged over rows — 1.0
    at perfect balance. The KV-cached decode routes each chunk with its
    own capacity window, so a capacity-dropped token can differ from the
    full-reforward path there — use_cache=False is exact for the buffer
    dispatches. EXCEPTION (round 14): with moe_dispatch="pallas" and no
    moe_capacity override the dataflow is DROPLESS — every routed token
    computes regardless of chunk composition, per-token routing depends
    only on that token's activations, and the cached decode is therefore
    exactly the full-reforward decode (cached==uncached equivalence in
    tests/test_serve.py); sampling's use_cache auto-resolve treats that
    case as exact (tpukit/sampling._cached_decode_exact).

    The dispatch DATAFLOW is pluggable (cfg.moe_dispatch, implementations
    in tpukit/ops/moe_dispatch.py and tpukit/ops/moe_gemm.py): "xla"
    computes global one-hot dispatch/combine einsums and leaves
    partitioning to GSPMD; "a2a" (the ExpertParallel default) hand-places
    the token exchange as a lax.all_to_all pair over the `expert` mesh
    axis inside shard_map — identical math, and the backward is also an
    all_to_all pair instead of the GSPMD replicate-repartition fallback
    the einsum transpose provokes (MULTICHIP_r05.json); "pallas" sorts
    tokens by expert and runs the fused Pallas segment GEMM — no capacity
    buffer or padding FLOPs, dropless unless cfg.moe_capacity is set, and
    under ExpertParallel it rides the same a2a exchange. Dropout applies
    to the combined output, outside every dataflow, so all three stay
    loss/grad-parity-equal.
    """
    if cfg.moe_dispatch == "pallas":
        from tpukit.ops.moe_gemm import moe_ffn_pallas

        impl = moe_ffn_pallas
    else:
        impl = moe_ffn_a2a if cfg.moe_dispatch == "a2a" else moe_ffn_xla
    out, aux = impl(layer, cfg, x, pad_mask=pad_mask)
    return dropout(out, cfg.dropout, rng, deterministic), aux


@jax.named_scope("attn")
def _apply_attention(layer, cfg: GPTConfig, x, pad_mask, rng, deterministic):
    """SelfAttention (models/gpt.py:68-105).

    The q/k/v parameters stay separate (exact reference surface,
    models/gpt.py:60-62) but compute as ONE fused [dim, 3*inner] matmul:
    column blocks of a wider matmul are bitwise identical to the three
    narrow ones, and the 3x-wider N dimension tiles the MXU far better at
    the reference's small dim."""
    batch, seq_len = x.shape[0], x.shape[1]
    if cfg.fuse_qkv:
        qkv_kernel = jnp.concatenate(
            [layer["attn"][n]["kernel"] for n in ("q", "k", "v")], axis=1
        )
        qkv = linear(x, {"kernel": qkv_kernel}, cfg.compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
    else:
        q = linear(x, layer["attn"]["q"], cfg.compute_dtype)
        k = linear(x, layer["attn"]["k"], cfg.compute_dtype)
        v = linear(x, layer["attn"]["v"], cfg.compute_dtype)

    split = lambda t: t.reshape(batch, seq_len, cfg.heads, cfg.head_dim).transpose(0, 2, 1, 3)
    out = causal_attention(
        split(q),
        split(k),
        split(v),
        scale=1.0 / (cfg.head_dim**0.5),
        pad_mask=pad_mask,
        impl=cfg.attention_impl,
        ring_axis=cfg.ring_axis,
        ring_layout=cfg.ring_layout,
        shard=cfg.kernel_shard,
    )
    out = out.transpose(0, 2, 1, 3).reshape(batch, seq_len, cfg.inner_dim)
    out = linear(out, layer["attn"]["out"], cfg.compute_dtype)
    return dropout(out, cfg.dropout, rng, deterministic)


def apply_decoder_layer(layer: Params, cfg: GPTConfig, x, pad_mask, rng=None, deterministic=True):
    """Pre-LN block (models/gpt.py:124-135). With cfg.num_experts > 0 the
    FFN is the routed expert bank and the return is `(x, aux)` — the
    branch is on a STATIC config field, so the dense path's signature and
    compiled graph are untouched."""
    if rng is None:
        attn_rng = ffn_rng = None
    else:
        attn_rng, ffn_rng = jax.random.split(rng)
    h = layer_norm(x, layer["norm1"]).astype(cfg.compute_dtype)
    x = x + _apply_attention(layer, cfg, h, pad_mask, attn_rng, deterministic)
    h = layer_norm(x, layer["norm2"]).astype(cfg.compute_dtype)
    if cfg.num_experts > 0:
        ffn_out, aux = _apply_moe_ffn(
            layer, cfg, h, ffn_rng, deterministic, pad_mask=pad_mask
        )
        return x + ffn_out, aux
    x = x + _apply_feed_forward(layer, cfg, h, ffn_rng, deterministic)
    return x


def apply_decoder_layers(
    stacked_layers: Params, cfg: GPTConfig, x, pad_mask, rng=None, deterministic=True,
    active=None, aux_out: list | None = None,
) -> jax.Array:
    """Sequential layer stack (models/gpt.py:161-167) over the stacked layer
    parameters. Works for any leading stack size, so pipeline stages call it
    on their `[layers_per_stage, ...]` slice.

    `active` (optional bool [num]): per-slot gate for padding layers in
    uneven pipeline layouts — an inactive slot passes `x` through unchanged
    and its parameters receive zero gradient (the `where` selects the
    residual stream, so the layer branch is dead in the backward pass).

    `aux_out` (MoE only): a list the summed per-layer load-balance aux loss
    is appended to — a trace-time side channel, appended OUTSIDE any scan
    body so no tracer leaks. Ignored for dense configs.

    Execution is controlled by cfg.scan_layers (unrolled blocks vs one
    lax.scan body) and cfg.remat_layers (checkpoint each layer); see the
    GPTConfig field docs for the measured trade-offs. Both paths are
    numerically identical (tests/test_model.py::test_scan_matches_unrolled).
    """
    num = jax.tree_util.tree_leaves(stacked_layers)[0].shape[0]
    moe = cfg.num_experts > 0

    layer_fn = apply_decoder_layer
    if cfg.remat_layers:
        layer_fn = jax.checkpoint(
            apply_decoder_layer, static_argnums=(1, 5)
        )

    if rng is None:
        rngs = jnp.zeros((num, 2), dtype=jnp.uint32)
        use_rng = False
    else:
        rngs = jax.random.split(rng, num)
        use_rng = True

    if not cfg.scan_layers:
        aux_total = jnp.float32(0)
        for i in range(num):
            layer = jax.tree_util.tree_map(lambda t: t[i], stacked_layers)
            y = layer_fn(
                layer, cfg, x, pad_mask, rngs[i] if use_rng else None, deterministic
            )
            if moe:
                y, aux = y
                aux_total = aux_total + (
                    aux if active is None else jnp.where(active[i], aux, 0.0)
                )
            x = y if active is None else jnp.where(active[i], y, x)
        if moe and aux_out is not None:
            aux_out.append(aux_total)
        return x

    if active is None:
        active = jnp.ones((num,), dtype=bool)
        gate = False
    else:
        gate = True

    def body(carry, scanned):
        layer, layer_rng, act = scanned
        x, aux_total = carry
        out = layer_fn(
            layer, cfg, x, pad_mask, layer_rng if use_rng else None, deterministic
        )
        if moe:
            out, aux = out
            aux_total = aux_total + jnp.where(act, aux.astype(jnp.float32), 0.0)
        if gate:
            out = jnp.where(act, out, x)
        return (out, aux_total), None

    (x, aux_total), _ = jax.lax.scan(
        body, (x, jnp.float32(0)), (stacked_layers, rngs, active)
    )
    if moe and aux_out is not None:
        aux_out.append(aux_total)
    return x


# --------------------------------------------------------------------------
# KV-cached decode path (no reference counterpart: the reference re-forwards
# the whole growing sequence per generated token, utils.py:63-64 — a known
# wart SURVEY §3.5 flags. Used by tpukit/sampling.py).
# --------------------------------------------------------------------------


def init_kv_cache(cfg: GPTConfig, batch: int, max_len: int) -> dict:
    """Per-layer stacked K/V buffers: `[num_layers, B, heads, max_len, d]`."""
    shape = (cfg.num_layers, batch, cfg.heads, max_len, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.compute_dtype),
        "v": jnp.zeros(shape, cfg.compute_dtype),
    }


@jax.named_scope("attn")
def _apply_attention_cached(layer, cfg: GPTConfig, x, k_cache, v_cache, start):
    """Attention for decode: write this chunk's K/V into the cache at
    `start` and attend over all cached positions `<= query position`.
    x: [B, T, dim]; k_cache/v_cache: [B, heads, S_max, d]. Returns
    (out, k_cache, v_cache).

    `start` is a scalar (every row writes at the same offset — the
    single-sequence decode and the full-width batched prefill) or a
    `[B]` vector of PER-ROW offsets (the continuous-batching decode
    step, tpukit/serve: each slot sits at its own cursor). The scalar
    path keeps its original dynamic-update-slice trace byte-unchanged;
    the vector path vmaps the cache write over rows and offsets each
    row's query position independently — identical math per row."""
    batch, t = x.shape[0], x.shape[1]
    q = linear(x, layer["attn"]["q"], cfg.compute_dtype)
    k = linear(x, layer["attn"]["k"], cfg.compute_dtype)
    v = linear(x, layer["attn"]["v"], cfg.compute_dtype)
    split = lambda z: z.reshape(batch, t, cfg.heads, cfg.head_dim).transpose(0, 2, 1, 3)
    q, k, v = split(q), split(k), split(v)

    s_max = k_cache.shape[2]
    if jnp.ndim(start) == 1:
        upd = lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s, 0))
        with jax.named_scope("kv_write"):
            k_cache = jax.vmap(upd)(k_cache, k, start)
            v_cache = jax.vmap(upd)(v_cache, v, start)
        q_pos = (start[:, None] + jnp.arange(t))[:, None, :, None]
    else:
        with jax.named_scope("kv_write"):
            k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, 0, start, 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, 0, start, 0))
        q_pos = (start + jnp.arange(t))[None, None, :, None]

    out = _attend_over_cache(layer, cfg, q, k_cache, v_cache, q_pos)
    return out, k_cache, v_cache


@jax.named_scope("attend")
def _attend_over_cache(layer, cfg: GPTConfig, q, k_cache, v_cache, q_pos):
    """The cached-attention read: scores over every cache position, causal
    `key_pos <= q_pos` window, softmax, value mix, output projection. ONE
    spelling shared by the ring path above and the paged path below —
    masked positions softmax to exact zeros (exp underflows in f32) and
    exact zeros annihilate whatever garbage the masked cache slots hold,
    which is why the two storage layouts produce bit-identical outputs
    for the same logical K/V (the paged parity bar, tests/test_paged.py).
    """
    batch, t = q.shape[0], q.shape[2]
    s_max = k_cache.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache) * (1.0 / cfg.head_dim**0.5)
    key_pos = jnp.arange(s_max)[None, None, None, :]
    scores = jnp.where(key_pos <= q_pos, scores, jnp.asarray(-1e9, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v_cache)
    out = out.transpose(0, 2, 1, 3).reshape(batch, t, cfg.inner_dim)
    return linear(out, layer["attn"]["out"], cfg.compute_dtype)


@jax.named_scope("attn")
def _apply_attention_paged(layer, cfg: GPTConfig, x, pool_k, pool_v,
                           scale_k, scale_v, li, bt, start, write_mask,
                           mesh=None):
    """Attention for decode over the PAGED cache (round 15, ROADMAP #2):
    the per-row-cursor indirection of the vector path above with one extra
    hop — each row's K/V comes from fixed-size pages dereferenced through
    its block-table row `bt [B, MP]` instead of a contiguous ring slice.

    `pool_k` / `pool_v` (and the int8 `scale_k` / `scale_v` sidecars) are
    the STACKED pools `[L, NP, H, P, D]`, whole; `li` is this layer's
    index. Reads and writes index `(li, page)`, so no layer's pool is ever
    sliced out and the updated stacks are handed back for the next layer.

    The gather (`serve.paged.gather_view`) materializes exactly the
    `[B, H, MP*P, D]` per-row view the vector path writes and attends, the
    chunk's fresh K/V is written into the view with the SAME vmapped
    dynamic-update-slice, and the attend math is `_attend_over_cache`
    verbatim — so for page storage at the compute dtype the outputs are
    bit-identical to the ring path and the parity bar transfers. The only
    paged-specific math is the write-back: the fresh K/V also lands in the
    pool (single position for decode T==1, whole pages for a prefill
    chunk — `start` page-aligned and T a page multiple, the engine's
    chunking contract), with `write_mask`-False rows routed to the null
    page so inactive/prefilling slots never touch a page another slot may
    own. int8 pools dequantize after the gather and requantize written
    rows (lossy — gated by tolerance, never claimed exact).

    Under a serving mesh the pools shard heads-over-`model` and stay
    replicated across `data` (the engine enforces a model-only grid for
    paged serving): gather and scatter index only the unsharded layer and
    page axes with replicated indices, so the paged hop adds ZERO collectives — the
    `decode_step_comm` closed form is unchanged and the compiled HLO must
    still match it exactly (tests/test_paged.py)."""
    from tpukit.serve import paged as paged_lib  # lazy: tpukit.serve imports gpt

    batch, t = x.shape[0], x.shape[1]
    q = linear(x, layer["attn"]["q"], cfg.compute_dtype)
    k = linear(x, layer["attn"]["k"], cfg.compute_dtype)
    v = linear(x, layer["attn"]["v"], cfg.compute_dtype)
    split = lambda z: z.reshape(batch, t, cfg.heads, cfg.head_dim).transpose(0, 2, 1, 3)
    q, k, v = split(q), split(k), split(v)

    if cfg.fused_decode and t == 1:
        # round 21: the decode tick skips the materialized gather — the
        # fused kernel walks the block tables itself (same math op-for-op
        # as the gathered path; ~1-ULP dot reassociation, exact token
        # parity — tests/test_paged_attention.py). [B,H,D] out == the
        # reference transpose+reshape for T==1, so the projection line
        # is shared.
        from tpukit.ops import paged_attention as paged_kernel

        # the kernel takes ONE layer's pool: this slice is the fused path's
        # own cost (ROADMAP S1 (b))
        own = lambda z: None if z is None else z[li]
        attn = paged_kernel.fused_paged_attention(
            own(pool_k), own(pool_v), own(scale_k), own(scale_v), bt, start,
            q[:, :, 0, :], k[:, :, 0, :], v[:, :, 0, :], mesh=mesh,
        )
        out = linear(attn.reshape(batch, 1, cfg.inner_dim),
                     layer["attn"]["out"], cfg.compute_dtype)
    else:
        view_k = paged_lib.gather_view(pool_k, scale_k, li, bt, cfg.compute_dtype)
        view_v = paged_lib.gather_view(pool_v, scale_v, li, bt, cfg.compute_dtype)
        upd = lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s, 0))
        view_k = jax.vmap(upd)(view_k, k, start)
        view_v = jax.vmap(upd)(view_v, v, start)
        q_pos = (start[:, None] + jnp.arange(t))[:, None, :, None]
        out = _attend_over_cache(layer, cfg, q, view_k, view_v, q_pos)

    if t == 1:
        pool_k, scale_k = paged_lib.write_token(
            pool_k, scale_k, li, bt, start, k[:, :, 0, :], write_mask
        )
        pool_v, scale_v = paged_lib.write_token(
            pool_v, scale_v, li, bt, start, v[:, :, 0, :], write_mask
        )
    else:
        pool_k, scale_k = paged_lib.write_pages(pool_k, scale_k, li, bt, start, k, write_mask)
        pool_v, scale_v = paged_lib.write_pages(pool_v, scale_v, li, bt, start, v, write_mask)
    return out, pool_k, pool_v, scale_k, scale_v


def forward_cached(params: Params, cfg: GPTConfig, input_ids, position_ids,
                   cache, start, write_mask=None, mesh=None):
    """Forward a chunk of tokens with the KV cache: writes K/V for positions
    `[start, start+T)` and returns `(logits [B, T, padded_vocab], cache)`.
    Prefill with the prompt chunk, then decode with T=1 per step. `start`
    is a scalar offset shared by every row, or a `[B]` vector of per-row
    offsets (the continuous-batching decode step — see
    `_apply_attention_cached`).

    `cache` is either the contiguous ring (`init_kv_cache`) or the paged
    pytree (`serve.paged.init_paged_cache`, detected by its `"bt"` block
    tables — round 15): paged caches require a vector `start` and route
    each layer through `_apply_attention_paged`, with `write_mask [B]`
    (default all-True) gating which rows' K/V reach the pool — the paged
    engine passes the live-slot mask so an inactive lane's re-forward can
    never write a page it no longer owns. The ring path ignores
    `write_mask` and keeps its original trace byte-unchanged.

    The two branches hold their cache differently through the layer loop.
    The paged branch threads the stacked pools `[L, NP, H, P, D]` whole and
    every layer reads and writes its rows by index (one gather and one
    scatter on the stack per layer and pool), so a decode tick moves the
    values it writes and not the pool. The ring branch still slices each
    layer's `[B, H, S, D]` ring out of the stack and restacks all of them
    on the way out; no benchmark cell runs it (ROADMAP S1).

    `mesh` matters only for the paged path with `cfg.fused_decode`: the
    fused kernel must run inside shard_map when heads are sharded over a
    `model` axis (GSPMD cannot partition a pallas_call) — the serve
    decode step threads its mesh through here."""
    paged = isinstance(cache, dict) and "bt" in cache
    if paged:
        bt = cache["bt"]
        if jnp.ndim(start) != 1:
            raise ValueError(
                "paged forward_cached requires a [B] vector `start` (each "
                "row sits at its own cursor through its block table)"
            )
        if write_mask is None:
            write_mask = jnp.ones((bt.shape[0],), bool)
        # the stacked pools (and int8 scale sidecars) go through the layer
        # loop whole: each layer reads and writes its own rows by index
        pool_k, pool_v = cache["k"], cache["v"]
        scale_k, scale_v = cache.get("ks"), cache.get("vs")
    else:
        new_k, new_v = [], []
    x = apply_embeddings(params, cfg, input_ids, position_ids)
    for i in range(cfg.num_layers):
        layer = jax.tree_util.tree_map(lambda t: t[i], params["layers"])
        h = layer_norm(x, layer["norm1"]).astype(cfg.compute_dtype)
        if paged:
            attn, pool_k, pool_v, scale_k, scale_v = _apply_attention_paged(
                layer, cfg, h, pool_k, pool_v, scale_k, scale_v, i,
                bt, start, write_mask, mesh=mesh,
            )
        else:
            attn, k_c, v_c = _apply_attention_cached(
                layer, cfg, h, cache["k"][i], cache["v"][i], start
            )
            new_k.append(k_c)
            new_v.append(v_c)
        x = x + attn
        h = layer_norm(x, layer["norm2"]).astype(cfg.compute_dtype)
        if cfg.num_experts > 0:
            ffn_out, _ = _apply_moe_ffn(layer, cfg, h, None, True)
            x = x + ffn_out
        else:
            x = x + _apply_feed_forward(layer, cfg, h, None, True)
    if paged:
        cache = {"k": pool_k, "v": pool_v, "bt": bt}
        if scale_k is not None:
            cache.update(ks=scale_k, vs=scale_v)
    else:
        with jax.named_scope("kv_write"):  # the per-layer caches restacked
            cache = {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}
    return apply_head(params, cfg, x), cache


# --------------------------------------------------------------------------
# What a block family offers the serving stack (`tpukit.model.family(cfg)`;
# tpukit/model/latent.py offers the same names): the serve programs, the
# engine and the samplers reach the model through these and through
# `init_params` / `forward` / `forward_cached` / `init_kv_cache` above.
# --------------------------------------------------------------------------


def max_context(cfg: GPTConfig) -> int:
    """The longest context the model can be served at: its learned position
    table. Beyond it position lookups silently clamp instead of erroring."""
    return cfg.max_position_embeddings


def kv_heads(cfg: GPTConfig) -> int:
    """Heads the cache keeps K and V rows for: what a serving mesh's `model`
    axis has to divide to shard the cache over it."""
    return cfg.heads


def cached_decode_exact(cfg: GPTConfig) -> bool:
    """True when the KV-cached decode is token-for-token the full-reforward
    decode. Dense models always are (causality). MoE models route each
    cached chunk with its own capacity window, so the buffer dispatches
    ("xla"/"a2a") can drop different tokens cached vs uncached — EXCEPT
    dropless "pallas" (no capacity override): per-token routing there is
    chunk-composition-independent and nothing is ever dropped (round 14;
    equivalence tested in tests/test_serve.py, rationale at
    `_apply_moe_ffn`)."""
    return cfg.num_experts == 0 or (
        cfg.moe_dispatch == "pallas" and cfg.moe_capacity == 0
    )


def page_kinds(cfg: GPTConfig, page_size: int, kv_dtype: str):
    """One kind of page: K and V rows of every head, every layer behind the
    one block table `bt` (`serve.paged.gpt_page_kinds`)."""
    from tpukit.serve import paged as paged_lib  # lazy: tpukit.serve imports gpt

    paged_lib.validate_kv_layout(cfg, page_size, kv_dtype)
    return paged_lib.gpt_page_kinds(cfg, page_size, kv_dtype)


def init_paged_cache(cfg: GPTConfig, num_pages, page_size: int, pages_per_slot: int,
                     slots: int, kv_dtype: str = "f32") -> dict:
    """The paged-cache pytree of `serve.paged.init_paged_cache`."""
    from tpukit.serve import paged as paged_lib

    if isinstance(num_pages, dict):
        num_pages = num_pages["bt"]
    return paged_lib.init_paged_cache(cfg, num_pages, page_size, pages_per_slot, slots, kv_dtype)


def select_lanes(cache: dict, slots, prompt_lens) -> dict:
    """The admit batch's view of the paged cache for one prefill chunk: the
    pools whole, the block table cut to the batch's lanes."""
    return dict(cache, bt=cache["bt"][slots])


def merge_lanes(cache: dict, sub: dict) -> dict:
    """The whole cache again after the chunk: the pools carry the writes,
    the global block tables are kept."""
    return dict(sub, bt=cache["bt"])


def counters(cache: dict) -> tuple:
    """Device counters the cache carries for the engine to fetch with the
    cursors, as `(names, arrays)`: this block keeps none."""
    return (), ()


def forward_hidden(
    params: Params,
    cfg: GPTConfig,
    input_ids: jax.Array,
    position_ids: jax.Array,
    mask: jax.Array | None = None,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    aux_out: list | None = None,
) -> jax.Array:
    """Everything up to (and including) the final LayerNorm — the hidden
    states the LM head consumes. Split out so the fused head+CE kernel
    (tpukit/ops/fused_head_ce.py) can take over from here without the
    logits ever materializing; `forward` == `apply_head`-minus-norm of
    this."""
    x = apply_embeddings(params, cfg, input_ids, position_ids)
    x = apply_decoder_layers(
        params["layers"], cfg, x, mask, rng, deterministic, aux_out=aux_out
    )
    return layer_norm(x, params["norm_out"]).astype(cfg.compute_dtype)


@jax.named_scope("head")
def apply_head(params: Params, cfg: GPTConfig, x) -> jax.Array:
    """Final LayerNorm + untied lm_head (models/gpt.py:217-219,229-231).

    Returns `[B, S, padded_vocab_size]`; pad columns (if any) are -1e9, so
    every softmax/argmax consumer behaves as with the logical vocab and the
    pad columns get zero gradient."""
    x = layer_norm(x, params["norm_out"]).astype(cfg.compute_dtype)
    logits = linear(x, params["lm_head"], cfg.compute_dtype)
    if cfg.padded_vocab_size != cfg.vocab_size:
        col = jax.lax.broadcasted_iota(jnp.int32, (cfg.padded_vocab_size,), 0)
        logits = jnp.where(
            col < cfg.vocab_size, logits, jnp.asarray(-1e9, logits.dtype)
        )
    return logits


def forward(
    params: Params,
    cfg: GPTConfig,
    input_ids: jax.Array,
    position_ids: jax.Array,
    mask: jax.Array | None = None,
    rng: jax.Array | None = None,
    deterministic: bool = True,
    aux_out: list | None = None,
) -> jax.Array:
    """Full model: logits `[B, S, vocab]` in the compute dtype.

    Twin of `TransformerDecoderLM.forward` (models/gpt.py:221-231, with the
    undefined-`x` bug fixed to the intended `input_ids`). `mask` is `[B, S]`
    bool, True = padding (the inverted convention produced by
    `prepare_batch`, reference utils.py:36).
    """
    x = apply_embeddings(params, cfg, input_ids, position_ids)
    x = apply_decoder_layers(
        params["layers"], cfg, x, mask, rng, deterministic, aux_out=aux_out
    )
    return apply_head(params, cfg, x)


class TransformerDecoderLM:
    """Thin OO veneer over the functional model, mirroring the reference's
    constructor surface (models/gpt.py:187-208) for users arriving from it.

    `model = TransformerDecoderLM(dim=..., ...); params = model.init(rng);
    logits = model(params, input_ids, position_ids, mask)`.
    """

    def __init__(
        self,
        dim: int,
        head_dim: int,
        heads: int,
        num_layers: int,
        vocab_size: int,
        max_position_embeddings: int,
        dropout: float = 0.0,
        **kw,
    ):
        self.config = GPTConfig(
            dim=dim,
            head_dim=head_dim,
            heads=heads,
            num_layers=num_layers,
            vocab_size=vocab_size,
            max_position_embeddings=max_position_embeddings,
            dropout=dropout,
            **kw,
        )

    @property
    def vocab_size(self) -> int:
        return self.config.vocab_size

    def init(self, rng: jax.Array) -> Params:
        return init_params(rng, self.config)

    def __call__(self, params, input_ids, position_ids, mask=None, rng=None, deterministic=True):
        return forward(params, self.config, input_ids, position_ids, mask, rng, deterministic)
