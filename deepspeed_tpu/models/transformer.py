"""Decoder-only transformer (GPT-2 / Llama families), TPU-first.

This is the flagship model the engine trains and benches. Design choices that
matter on TPU (vs the reference's per-layer torch modules +
``csrc/transformer`` fused CUDA kernels):

  - layer params are *stacked* along a leading L dim and the decoder body is a
    single ``lax.scan`` — one compiled layer body regardless of depth (fast
    compile, and XLA pipelines the scan);
  - everything is static-shape, bf16-friendly, einsum-based so the MXU gets
    large batched GEMMs; elementwise chains (bias/residual/norm/activation)
    are left to XLA fusion — the CUDA fused-kernel inventory
    (softmax/gelu/layernorm/transform kernels, SURVEY §2.4 #5/#6) is the
    compiler's job here, with Pallas reserved for attention;
  - parameters carry logical axis names (embed/mlp/heads/vocab/layers) so the
    ZeRO/TP ShardingPolicy can place them (runtime/zero/sharding.py);
  - activation rematerialisation is a ``jax.checkpoint`` policy around the
    scanned layer body (reference: activation_checkpointing/checkpointing.py).

Functional API: ``init(rng, cfg) -> params``; ``apply(params, cfg, tokens)``;
``loss(params, cfg, batch)``. The TransformerModel class packages these for
the engine protocol.
"""

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.hlo_scopes import Scope


@dataclass(frozen=True)
class LayerKind:
    """One kind of decoder layer in a layer plan: its mixer (softmax
    attention of some shape, the gated delta rule, whose shape is the
    configuration's ``gdn_*``, a state-space scan (Mamba-2), whose shape is
    its ``ssm_*``, or latent attention, whose shape is its ``mla_*``) and the
    kind of its FFN; a gated short convolution, whose taps are its
    ``conv_taps``, is a mixer that trains and is not served yet. Layers of one
    kind share parameter shapes and are
    stacked together (``params["layers"][name]``); attention layers of the
    same reach (``window`` 0 or not) share a KV pool, delta-rule or
    state-space layers the state pool, latent layers the latent pool; a
    short-convolution layer has none. A layer may be ONE sublayer alone, with
    one norm and one residual add: a mixer without an FFN (``ffn="none"``), or
    an FFN without a mixer (``mixer="none"``), which keeps nothing in any pool."""
    name: str
    kv_heads: int = 1  # of an attention mixer
    window: int = 0  # 0 = full causal attention; W = the last W positions
    rope_theta: float = 10000.0
    sink: bool = False  # a learned per-head logit joins the softmax's denominator
    ffn: str = "dense"  # dense | moe (sigmoid top-k over the experts held) | none (the mixer alone)
    ffn_size: Optional[int] = None  # None => cfg.ffn_size
    # an expert FFN whose routed experts work in a latent of this width: one projection down
    # before them and one up after them, shared by the experts; the router and the shared
    # expert read the model's width (0: the experts work at the model's width)
    ffn_latent: int = 0
    # attention | gdn (Gated DeltaNet: recurrent state, no keys kept) | ssm (Mamba-2: a
    # state-space scan with a scalar decay a head, no keys kept) | mla (latent
    # attention: one latent and one rotated key a token, shared by every head) | conv (a
    # double-gated causal depthwise convolution of cfg.conv_taps taps: no keys, no scan) |
    # none (the FFN alone: no row in any pool)
    mixer: str = "attention"

    @property
    def pool(self) -> Optional[str]:
        """The cache pool this kind's layers live in; None for a kind that is
        not served (``kv_cache.refuse_unserved``) or keeps nothing (no mixer)."""
        if self.mixer in ("conv", "none"):
            return None
        if self.mixer in ("gdn", "ssm"):
            return "state"
        if self.mixer == "mla":
            return "latent"
        return "window" if self.window > 0 else "full"


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None => MHA
    ffn_hidden_size: Optional[int] = None  # None => 4*hidden (gpt) / derived (llama)
    max_seq_len: int = 1024
    pos_embedding: str = "learned"  # learned | rope | alibi | none
    norm_type: str = "layernorm"  # layernorm | rmsnorm
    activation: str = "gelu"  # gelu | relu | silu_glu (SwiGLU) | relu2 (a plan's: W_down relu(W_up x)^2)
    tie_embeddings: bool = True
    dtype: str = "float32"  # compute/storage dtype for params & activations
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dropout: float = 0.0
    remat: bool = False
    # a name of checkpointing.POLICIES (or "offload"): flash_saveable |
    # nothing_saveable | dots_saveable | dots_with_no_batch_dims | full.
    # The default keeps the flash kernel's output and log-sum-exp a layer
    # (one (B, S, D) array in the model's dtype + 3 %) so the backward pass
    # does not rerun the kernel; without the kernel (attn_impl="xla", ...) it
    # is nothing_saveable, which a job at its memory limit can also ask for
    remat_policy: str = "flash_saveable"
    attn_impl: str = "xla"  # xla | pallas (flash) | block_sparse (layout kernel)
    # block-sparse attention pattern (attn_impl="block_sparse"): mode is one
    # of dense|fixed|bigbird|bslongformer|variable plus that mode's kwargs
    # (ops/sparse_attention/sparsity_config.py; reference
    # ops/sparse_attention/sparse_self_attention.py + docs "~10x longer
    # sequences"). Tuple-of-pairs so the frozen config stays hashable.
    sparse_attention: Optional[tuple] = None  # e.g. (("mode","fixed"),("block",128))
    use_bias: bool = True  # linear/ln biases (gpt2 yes, llama no)
    scan_layers: bool = True
    # --- architecture variants for the HF injection-policy families
    # (module_inject/policies.py; reference replace_policy.py:20-26) ---
    rope_dim: Optional[int] = None  # partial rotary over first rope_dim dims (GPT-J/NeoX)
    rope_interleaved: bool = False  # GPT-J even/odd pairing (vs llama/neox half-split)
    parallel_residual: bool = False  # x + attn(h) + mlp(h') in one residual (GPT-J/NeoX)
    shared_ln: bool = False  # parallel residual feeds mlp from ln1 too (GPT-J)
    # pre | post (post: BERT / OPT-350m ordering) | sandwich (a layer plan's: a norm before
    # AND after each sublayer, the second on what the sublayer returns, before the residual add)
    norm_position: str = "pre"
    causal: bool = True  # False = bidirectional encoder attention (BERT)
    type_vocab_size: int = 0  # token-type-embedding vocab (BERT; 0 = off)
    embed_norm: bool = False  # LayerNorm over summed embeddings (BERT, BLOOM)
    lm_head_bias: bool = False  # untied lm head carries a bias (GPT-J)
    attn_scale: Optional[float] = None  # None => 1/sqrt(head_dim); GPT-Neo uses 1.0
    # per-layer local-attention windows (GPT-Neo global/local alternation:
    # 0 = global, W = attend only the last W positions). Tuple of
    # num_layers ints; None = all-global.
    local_attn_windows: Optional[tuple] = None
    # flash-attention tile size (PERF.md block sweep; None = kernel default
    # of 128). Larger tiles amortize the softmax running-max bookkeeping
    # against HBM re-reads of K/V; the bench self-tune probes this.
    flash_block: Optional[int] = None
    # KV-cache storage: "model" dtype or "int8" (per-token-per-head scales;
    # decode reads half the cache bytes, context capacity doubles — the
    # quantize/dequantize lives in ops/transformer/inference_ops)
    kv_cache_dtype: str = "model"
    # rolling (ring-buffer) KV cache for uniform-sliding-window models
    # (Mistral): the cache holds only the last `window` positions — decode
    # memory and cache-read bandwidth are O(window) instead of O(total
    # generated length). Set by the inference engine when the conditions
    # hold (uniform window, rope/no pos-emb, flash prefill available);
    # slot absolute positions are derived modulo the cache length, so the
    # math degenerates to the plain cache whenever nothing wraps.
    rolling_kv_cache: bool = False
    # --- MoE (reference: deepspeed/moe/; 0 experts = dense MLP) ---
    moe_num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_drop_tokens: bool = True
    moe_use_rts: bool = False  # random token selection needs an rng at loss()
    # PR-MoE residual mixing (reference moe/layer.py:28,45): dense MLP +
    # expert mix with a learned per-token 2-way softmax coefficient
    moe_use_residual: bool = False
    # --- sequence/context parallelism (parallel/sequence.py) ---
    seq_parallel: str = "none"  # none | ring | ulysses
    # --- QAT activation fake-quant bits, 0 = off (compression/ wiring) ---
    act_quant_bits: int = 0
    # --- data efficiency (engine-driven schedules) ---
    # random-LTD: layers run on a random token subset of this length
    # (engine re-jits per scheduled value; 0 = off). Applies to all scanned
    # layers; per-layer subsets need scan_layers=False.
    random_ltd: bool = False
    # progressive layer drop: stochastic depth with keep prob
    # p_l = 1 - (l/L) * (1 - theta); theta is a dynamic scalar from the
    # engine's PLD schedule (runtime/progressive_layer_drop.py)
    pld_enabled: bool = False
    # --- head widths free of hidden // heads (None => the derived width) ---
    head_size: Optional[int] = None  # query/key head width
    v_head_size: Optional[int] = None  # value head width (None => head_dim)
    attn_value_scale: Optional[float] = None  # v = scale * (h Wv)
    # --- layer plan (models/layer_plan.py): layers of several kinds in one
    # stack. ``layer_kinds`` lists the kinds, ``layer_plan`` gives each
    # layer's index into it; parameters are stacked per kind and the KV
    # cache is one pool per attention reach (full length / a ring of
    # ``window`` positions). None = one kind, the single scan above.
    layer_kinds: Optional[tuple] = None
    layer_plan: Optional[tuple] = None
    # expert layers of a plan: sigmoid scores over ``moe_num_experts``,
    # top ``moe_top_k`` by score + selection bias, weights normalised, no
    # capacity and no dropped token; this chip holds the contiguous experts
    # [first, first + count) and computes their part of the result
    moe_experts_held: Optional[tuple] = None  # (first, count); None => all
    moe_score: str = "sigmoid"  # sigmoid (+ selection bias) | softmax over all experts, no bias
    moe_shared_size: int = 0  # width of a shared SwiGLU expert; 0 = none
    moe_shared_gated: bool = True  # ... behind a sigmoid gate (False: added as it is)
    moe_routed_scale: float = 1.0  # the normalised top-k weights times this (routed_scaling_factor)
    moe_norm_eps: float = 0.0  # added to the chosen scores' sum before they are divided by it
    # a plan's attention layers: sigmoid(gate) on the attention output (the gate rides wq's
    # projection as a leaf of its own), RMSNorm over each query and key head
    attn_out_gate: bool = False
    qk_norm: bool = False
    norm_one_plus: bool = False  # RMSNorm scales by (1 + w), w stored zero-centred
    # Gated DeltaNet mixer (LayerKind.mixer == "gdn"): key heads x key width,
    # value heads x value width (value head j reads key head j // (value / key
    # heads)), the taps of its causal depthwise convolution
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_conv: int = 4
    # Mamba-2 mixer (LayerKind.mixer == "ssm"): heads x head width is its inner
    # width, every head keeps a (head width, state width) float32 state, ``B``
    # and ``C`` are shared by the heads of a group (as is the gated norm: over
    # each group's channels), and the causal depthwise convolution (with a
    # bias) runs over inner width + 2 x groups x state width
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    # Gated short convolution mixer (LayerKind.mixer == "conv"): [b | c | u] = h W_in, a causal
    # depthwise convolution of this many taps over b * u, gated by c, through W_out
    conv_taps: int = 3
    # a plan's scalar multipliers: the embedding's output, what a mixer or an
    # FFN adds to the residual stream, the logits (attn_scale is above)
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # Latent attention mixer (LayerKind.mixer == "mla", DeepSeek-V2's MLA): the
    # queries' low rank, the latent a token keeps (its keys' and values' low
    # rank), each head's unrotated and rotated query/key widths and its value
    # width; the cache is the latent and ONE rotated key a token
    mla_q_rank: int = 0
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0
    # a layer plan walked this many times over the SAME weights (a looped model), the final
    # norm after every pass and feeding the next; every pass keeps keys and values of its own,
    # so a keyed pool holds loop_steps x its kinds' layers; 1 = every layer once
    loop_steps: int = 1
    # leaves made in the model dtype at init (a model whose float32 leaves
    # would not fit beside their cast copy)
    init_in_model_dtype: bool = False

    def __post_init__(self):
        # accept a dict for sparse_attention (user-facing) but store a
        # tuple-of-pairs so the frozen config stays hashable
        if isinstance(self.sparse_attention, dict):
            object.__setattr__(
                self, "sparse_attention", tuple(sorted(self.sparse_attention.items()))
            )
        if self.layer_kinds is not None:
            from deepspeed_tpu.models.layer_plan import check_plan

            check_plan(self)
        elif self.loop_steps != 1 or self.norm_position == "sandwich":
            raise ValueError("loop_steps > 1 and norm_position 'sandwich' are a layer plan's "
                             "(layer_kinds, layer_plan): the one-kind body has neither")

    @property
    def uniform_window(self) -> Optional[int]:
        """The single static sliding-window size when every layer shares one
        positive window (Mistral); None for no windows or per-layer mixes
        (GPT-Neo alternation)."""
        w = self.local_attn_windows
        if w is None or len(set(w)) != 1 or int(w[0]) <= 0:
            return None
        return int(w[0])

    @property
    def varying_windows(self) -> bool:
        """True when windows differ per layer (GPT-Neo alternation) and must
        ride the layer scan as traced scalars; uniform/absent windows stay
        static python ints (flash band kernel + rolling cache rely on it)."""
        w = self.local_attn_windows
        return w is not None and len(set(w)) > 1

    @property
    def head_dim(self):
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def v_head_dim(self):
        return self.v_head_size or self.head_dim

    @property
    def plan(self) -> Optional[tuple]:
        """The layer plan as LayerKind objects, layer by layer; None for a
        model of one kind."""
        if self.layer_kinds is None:
            return None
        return tuple(self.layer_kinds[i] for i in self.layer_plan)

    @property
    def held_experts(self) -> tuple:
        return self.moe_experts_held or (0, self.moe_num_experts)

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def ffn_size(self):
        if self.ffn_hidden_size is not None:
            return self.ffn_hidden_size
        return 4 * self.hidden_size

    @property
    def jnp_dtype(self):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[self.dtype]

    def num_params(self) -> int:
        if self.layer_kinds is not None:
            from deepspeed_tpu.models.layer_plan import num_params

            return num_params(self)
        D, V, L, F = self.hidden_size, self.vocab_size, self.num_layers, self.ffn_size
        kvd = self.kv_heads * self.head_dim
        attn = D * D + 2 * D * kvd + D * D  # q,k,v,o
        mlp = (3 if self.activation == "silu_glu" else 2) * D * F
        if self.moe_num_experts > 0:
            dense_mlp = mlp
            mlp = mlp * self.moe_num_experts + D * self.moe_num_experts  # experts + router
            if self.moe_use_residual:
                mlp += dense_mlp + 2 * D + 2  # residual MLP + coefficient
        per_layer = attn + mlp + 2 * D  # + ln scales
        if self.use_bias:
            mlp_bias = F + D
            if self.moe_num_experts > 0:
                mlp_bias *= self.moe_num_experts  # per-expert bi/bo
                if self.moe_use_residual:
                    mlp_bias += F + D  # dense residual MLP biases
            per_layer += (D + 2 * kvd + D) + mlp_bias + 2 * D  # attn/mlp/ln biases
        emb = V * D + (self.max_seq_len * D if self.pos_embedding == "learned" else 0)
        emb += self.type_vocab_size * D
        if self.embed_norm:
            emb += D + (D if self.use_bias else 0)
        head = 0 if self.tie_embeddings else V * D + (V if self.lm_head_bias else 0)
        final = D + (D if self.use_bias else 0)
        return emb + L * per_layer + final + head

    def flops_per_token(self, seq_len: int) -> float:
        """Training FLOPs/token, Megatron-style accounting (fwd+bwd):
        6*N over matmul params + the logits projection (the V×D matmul runs
        every step whether or not embeddings are tied) + causal attention."""
        n = self.num_params() - self.vocab_size * self.hidden_size * (1 if self.tie_embeddings else 2)
        lm_head_flops = 6 * self.vocab_size * self.hidden_size
        # 2*2*3 per token pair, in the layers that attend (a plan may hold layers that do not)
        attending = (self.num_layers if self.layer_kinds is None else
                     sum(k.mixer in ("attention", "mla") for k in self.plan))
        attn_flops = 12 * attending * self.hidden_size * seq_len
        return 6.0 * n + lm_head_flops + attn_flops


# preset shapes for parity configs (BASELINE.md tracked configs)
PRESETS = {
    "gpt2-125m": dict(vocab_size=50257, hidden_size=768, num_layers=12, num_heads=12, max_seq_len=1024),
    "gpt2-350m": dict(vocab_size=50257, hidden_size=1024, num_layers=24, num_heads=16, max_seq_len=1024),
    "gpt2-760m": dict(vocab_size=50257, hidden_size=1280, num_layers=36, num_heads=20, max_seq_len=1024),
    "gpt2-1.5b": dict(vocab_size=50257, hidden_size=1600, num_layers=48, num_heads=25, max_seq_len=1024),
    "llama2-7b": dict(
        vocab_size=32000, hidden_size=4096, num_layers=32, num_heads=32, num_kv_heads=32,
        ffn_hidden_size=11008, max_seq_len=4096, pos_embedding="rope", norm_type="rmsnorm",
        activation="silu_glu", tie_embeddings=False, use_bias=False,
    ),
    "llama2-70b": dict(
        vocab_size=32000, hidden_size=8192, num_layers=80, num_heads=64, num_kv_heads=8,
        ffn_hidden_size=28672, max_seq_len=4096, pos_embedding="rope", norm_type="rmsnorm",
        activation="silu_glu", tie_embeddings=False, use_bias=False,
    ),
    # BASELINE.json tracked inference config (BLOOM-7B kernel injection)
    "bloom-7b": dict(
        vocab_size=250880, hidden_size=4096, num_layers=30, num_heads=32,
        max_seq_len=2048, pos_embedding="alibi", embed_norm=True, tie_embeddings=True,
    ),
    "gptj-6b": dict(
        vocab_size=50400, hidden_size=4096, num_layers=28, num_heads=16,
        max_seq_len=2048, pos_embedding="rope", rope_dim=64, rope_interleaved=True,
        parallel_residual=True, shared_ln=True, tie_embeddings=False, lm_head_bias=True,
    ),
    "gpt-neox-20b": dict(
        vocab_size=50432, hidden_size=6144, num_layers=44, num_heads=64,
        ffn_hidden_size=24576, max_seq_len=2048, pos_embedding="rope", rope_dim=24,
        parallel_residual=True, tie_embeddings=False,
    ),
    # Reference headline-bench family (docs/_posts/2020-05-28-fastest-bert-training.md:
    # BERT-large pretrain, 64 TFLOPS/V100 @ seq 128). Bidirectional post-LN
    # encoder: tok+pos+type embeddings -> LayerNorm, no final norm (post-LN
    # already normalizes the last residual), MLM via labels+loss_mask in
    # loss_fn. Deviation from HF BERT: the MLM head ties directly to the
    # token embedding (no extra transform dense); pooler/NSP head omitted.
    "bert-large": dict(
        vocab_size=30522, hidden_size=1024, num_layers=24, num_heads=16,
        max_seq_len=512, pos_embedding="learned", type_vocab_size=2,
        embed_norm=True, norm_position="post", causal=False,
    ),
    "bert-base": dict(
        vocab_size=30522, hidden_size=768, num_layers=12, num_heads=12,
        max_seq_len=512, pos_embedding="learned", type_vocab_size=2,
        embed_norm=True, norm_position="post", causal=False,
    ),
}


def get_config(preset: str, **overrides) -> TransformerConfig:
    base = dict(PRESETS[preset])
    base.update(overrides)
    return TransformerConfig(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_outer(rng, cfg: TransformerConfig):
    """Non-layer params: embeddings, final norm, lm head (all fp32)."""
    D, V, S = cfg.hidden_size, cfg.vocab_size, cfg.max_seq_len
    k_tok, k_pos, k_head = jax.random.split(rng, 3)
    params = {
        "embed": {"tok": jax.random.normal(k_tok, (V, D), jnp.float32) * 0.02},
        "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
    }
    if cfg.pos_embedding == "learned":
        params["embed"]["pos"] = jax.random.normal(k_pos, (S, D), jnp.float32) * 0.02
    if cfg.type_vocab_size > 0:
        params["embed"]["type"] = (
            jax.random.normal(jax.random.fold_in(k_pos, 1), (cfg.type_vocab_size, D), jnp.float32) * 0.02
        )
    if cfg.embed_norm:
        params["embed_norm"] = {"scale": jnp.ones((D,), jnp.float32)}
        if cfg.use_bias:
            params["embed_norm"]["bias"] = jnp.zeros((D,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": jax.random.normal(k_head, (D, V), jnp.float32) / math.sqrt(D)
        }
        if cfg.lm_head_bias:
            params["lm_head"]["b"] = jnp.zeros((V,), jnp.float32)
    if cfg.use_bias:
        params["final_norm"]["bias"] = jnp.zeros((D,), jnp.float32)
    return params


def _init_one_layer(key, cfg: TransformerConfig):
    """Unstacked params for a single decoder layer."""
    D, F, L = cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    hd, nh, nkv, E = cfg.head_dim, cfg.num_heads, cfg.kv_heads, cfg.moe_num_experts
    ks = iter(jax.random.split(key, 16))

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (1.0 / math.sqrt(fan_in))

    def experts(maker):
        return jnp.stack([maker(k) for k in jax.random.split(next(ks), E)])

    if E > 0:
        mlp = {
            "gate": jax.random.normal(next(ks), (D, E), jnp.float32) * 0.02,
            "wi": experts(lambda k: dense(k, (D, F), D)),
            "wo": experts(lambda k: dense(k, (F, D), F) / math.sqrt(2 * L)),
        }
        if cfg.activation == "silu_glu":
            mlp["wg"] = experts(lambda k: dense(k, (D, F), D))
        if cfg.moe_use_residual:
            # PR-MoE (reference moe/layer.py:28,45): dense residual MLP +
            # per-token 2-way mixing coefficient
            mlp["res_wi"] = dense(next(ks), (D, F), D)
            mlp["res_wo"] = dense(next(ks), (F, D), F) / math.sqrt(2 * L)
            if cfg.activation == "silu_glu":
                mlp["res_wg"] = dense(next(ks), (D, F), D)
            mlp["coef_w"] = jax.random.normal(next(ks), (D, 2), jnp.float32) * 0.02
            mlp["coef_b"] = jnp.zeros((2,), jnp.float32)
    else:
        mlp = {
            "wi": dense(next(ks), (D, F), D),
            "wo": dense(next(ks), (F, D), F) / math.sqrt(2 * L),
        }
        if cfg.activation == "silu_glu":
            mlp["wg"] = dense(next(ks), (D, F), D)

    layer = {
        "attn": {
            "wq": dense(next(ks), (D, nh * hd), D),
            "wk": dense(next(ks), (D, nkv * hd), D),
            "wv": dense(next(ks), (D, nkv * hd), D),
            "wo": dense(next(ks), (nh * hd, D), nh * hd) / math.sqrt(2 * L),
        },
        "mlp": mlp,
        "ln1": {"scale": jnp.ones((D,), jnp.float32)},
        "ln2": {"scale": jnp.ones((D,), jnp.float32)},
    }
    if cfg.use_bias:
        layer["attn"]["bq"] = jnp.zeros((nh * hd,), jnp.float32)
        layer["attn"]["bk"] = jnp.zeros((nkv * hd,), jnp.float32)
        layer["attn"]["bv"] = jnp.zeros((nkv * hd,), jnp.float32)
        layer["attn"]["bo"] = jnp.zeros((D,), jnp.float32)
        if E > 0:
            layer["mlp"]["bi"] = jnp.zeros((E, F), jnp.float32)
            layer["mlp"]["bo"] = jnp.zeros((E, D), jnp.float32)
            if cfg.moe_use_residual:
                layer["mlp"]["res_bi"] = jnp.zeros((F,), jnp.float32)
                layer["mlp"]["res_bo"] = jnp.zeros((D,), jnp.float32)
        else:
            layer["mlp"]["bi"] = jnp.zeros((F,), jnp.float32)
            layer["mlp"]["bo"] = jnp.zeros((D,), jnp.float32)
        layer["ln1"]["bias"] = jnp.zeros((D,), jnp.float32)
        layer["ln2"]["bias"] = jnp.zeros((D,), jnp.float32)
    return layer


def init_layer_slice(rng, cfg: TransformerConfig, lo: int, hi: int):
    """Stacked params for layers [lo, hi) — per-layer keys are ``fold_in``
    of the absolute layer index, so any slicing yields identical leaves.
    This is the ZeRO-Infinity streaming-init hook (reference analogue:
    zero.Init partitioned construction, partition_parameters.py:601):
    the param-offload tier materialises one sub-group at a time."""
    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(lo, hi))
    return jax.vmap(lambda k: _init_one_layer(k, cfg))(keys)


def init(rng, cfg: TransformerConfig):
    """Build the parameter pytree (all leaves fp32; engine casts as needed)."""
    r_outer, r_layers = jax.random.split(rng)
    params = init_outer(r_outer, cfg)
    if cfg.layer_kinds is not None:
        from deepspeed_tpu.models.layer_plan import init_exit_gate, init_layers

        params["layers"] = init_layers(r_layers, cfg)
        if cfg.loop_steps > 1:
            params["exit_gate"] = init_exit_gate(jax.random.fold_in(r_outer, 11), cfg)
        if cfg.norm_one_plus:  # (1 + w): w is stored zero-centred
            params["final_norm"]["scale"] = 0.1 * jax.random.normal(
                jax.random.fold_in(r_outer, 7), (cfg.hidden_size,), jnp.float32)
        if cfg.init_in_model_dtype:
            params = jax.tree.map(lambda p: p.astype(cfg.jnp_dtype), params)
        return params
    params["layers"] = init_layer_slice(r_layers, cfg, 0, cfg.num_layers)
    return params


def logical_specs(params, cfg: TransformerConfig):
    """Per-dimension logical axis names, mirroring the params pytree.

    The ShardingPolicy maps these through rules onto mesh axes; the 'layers'
    leading scan dim is never sharded.
    """

    def annotate(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        last = names[-1]
        stacked = "layers" in names
        pre = ("layers",) if stacked else ()
        if "attn" in names:
            table = {
                "wq": ("embed", "heads"), "wk": ("embed", "kv"), "wv": ("embed", "kv"),
                "wo": ("heads", "embed"), "bq": ("heads",), "bk": ("kv",), "bv": ("kv",), "bo": ("embed",),
                "sink": ("heads",), "wq_gate": ("embed", "heads"), "q_norm": (None,),
                "k_norm": (None,),
            }
            return pre + table[last]
        if "mlp" in names:
            # a layer plan has dense layers beside its expert layers: the rank tells them apart
            if (cfg.moe_num_experts > 0 and last in ("wi", "wg", "wo", "bi", "bo")
                    and leaf.ndim == len(pre) + (3 if last[0] == "w" else 2)):
                table = {"wi": ("expert", "embed", "mlp"), "wg": ("expert", "embed", "mlp"),
                         "wo": ("expert", "mlp", "embed"), "bi": ("expert", "mlp"), "bo": ("expert", "embed")}
                return pre + table[last]
            table = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"), "wo": ("mlp", "embed"),
                     "bi": ("mlp",), "bo": ("embed",), "gate": ("embed", None),
                     "gate_bias": (None,),
                     "shared_wi": ("embed", "mlp"), "shared_wg": ("embed", "mlp"),
                     "shared_wo": ("mlp", "embed"), "shared_gate": ("embed", None),
                     "latent_down": ("embed", None), "latent_up": (None, "embed"),
                     # PR-MoE residual MLP + mixing coefficient (dense)
                     "res_wi": ("embed", "mlp"), "res_wg": ("embed", "mlp"),
                     "res_wo": ("mlp", "embed"), "res_bi": ("mlp",), "res_bo": ("embed",),
                     "coef_w": ("embed", None), "coef_b": (None,)}
            return pre + table[last]
        if any(n in names for n in ("ln1", "ln2", "ln1_post", "ln2_post")):
            return pre + ("norm",)
        if "final_norm" in names or "embed_norm" in names:
            return ("norm",)
        if "embed" in names:
            if last == "tok":
                return ("vocab", "embed")
            # pos table shards over seq; the tiny type table stays unsharded
            return ("seq", "embed") if last == "pos" else (None, "embed")
        if "lm_head" in names:
            return ("embed", "vocab") if last == "w" else ("vocab",)
        if "mlm_head" in names:
            table = {"w": ("embed", None), "b": (None,), "ln_scale": ("norm",),
                     "ln_bias": ("norm",), "proj_bias": ("vocab",)}
            return table[last]
        return tuple(None for _ in leaf.shape)

    return jax.tree_util.tree_map_with_path(annotate, params)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

@jax.named_scope(Scope.NORM)
def _norm(x, scale, bias, cfg: TransformerConfig):
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.norm_eps)
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        x32 = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    if cfg.norm_one_plus:
        scale = 1.0 + scale.astype(jnp.float32)
    out = x32 * scale
    if bias is not None:
        out = out + bias
    return out.astype(x.dtype)


# rotary embedding: the op-registry surface IS the implementation
# (ops/transformer/inference_ops.apply_rotary_pos_emb; reference analogue
# csrc/transformer/inference apply_rotary_pos_emb.cu)
from deepspeed_tpu.ops.transformer.fused_ops import fused_softmax  # noqa: E402
from deepspeed_tpu.ops.transformer import kv_cache  # noqa: E402
from deepspeed_tpu.ops.transformer.inference_ops import (  # noqa: E402
    apply_rotary_pos_emb as _rope,
    softmax_context,
)
from deepspeed_tpu.ops.transformer.kv_cache import update_kv_cache  # noqa: E402


def _alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi per-head slopes (press et al.; reference: BLOOM container's
    alibi path in module_inject/containers/bloom.py lineage)."""

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        slopes = pow2_slopes(n_heads)
    else:
        closest = 2 ** math.floor(math.log2(n_heads))
        slopes = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)[0::2][: n_heads - closest]
        slopes = slopes + extra
    return jnp.asarray(slopes, jnp.float32)


import functools


@functools.lru_cache(maxsize=32)
def _sparse_layout(sparse_attention: tuple, num_heads: int, seq_len: int):
    """Static block-sparse layout for (pattern, heads, seq) — numpy, built
    once per shape and embedded as a jit constant. Returns (layout, block)."""
    from deepspeed_tpu.ops.sparse_attention import sparsity_config as sc

    opts = dict(sparse_attention)
    mode = opts.pop("mode", "fixed")
    cls = {
        "dense": sc.DenseSparsityConfig,
        "fixed": sc.FixedSparsityConfig,
        "bigbird": sc.BigBirdSparsityConfig,
        "bslongformer": sc.BSLongformerSparsityConfig,
        "variable": sc.VariableSparsityConfig,
    }[mode]
    config = cls(num_heads=num_heads, **opts)
    return config.make_layout(seq_len), config.block


@jax.named_scope(Scope.ATTN_CORE)
def _attention(q, k, v, cfg: TransformerConfig, segment_positions, window=None):
    """Causal multi-head / grouped-query attention.

    xla impl: einsum softmax einsum (fp32 logits). pallas impl: flash kernel
    (ops/pallas/flash_attention.py) once available. ``window`` restricts each
    query to the last ``window`` positions (0 = unlimited) — GPT-Neo local
    layers, Mistral sliding windows. A STATIC python-int window rides the
    flash kernel's tile-pruned sliding-window path (O(S*window) compute and
    HBM — the layer stack passes static ints whenever the config allows);
    a traced i32 scalar (per-layer windows inside the layer scan) takes the
    masked einsum path.
    """
    B, S, nh, hd = q.shape
    had_window = window is not None
    if isinstance(window, int) and (window <= 0 or window >= S):
        # static 0 = a global layer; a window covering the whole sequence
        # is a numerical no-op — elide it so e.g. Mistral (sliding_window
        # 4096) at seq <= 4096 keeps the unwindowed fast paths, including
        # sequence parallelism below. had_window still gates the
        # block-sparse branch: elision must not reroute a windowed config
        # onto an APPROXIMATE kernel it never used before.
        window = None
    static_window = window if isinstance(window, int) else None
    nkv = k.shape[2]
    if cfg.seq_parallel in ("ring", "ulysses"):
        from deepspeed_tpu import comm
        from deepspeed_tpu.parallel.sequence import sequence_parallel_attention

        mesh = comm.get_mesh()
        if mesh.shape.get("sequence", 1) > 1:
            if cfg.pos_embedding == "alibi":
                raise NotImplementedError("ALiBi bias is not supported under sequence parallelism")
            if window is not None:
                raise NotImplementedError(
                    "local attention windows are not supported under sequence parallelism"
                )
            return sequence_parallel_attention(
                q, k, v, impl=cfg.seq_parallel, causal=cfg.causal, mesh=mesh,
                attn_impl=cfg.attn_impl, sm_scale=cfg.attn_scale,
            )
    if not had_window and window is None and cfg.attn_impl == "block_sparse":
        # layout-aware Pallas kernel: long-sequence training/prefill path
        # (reference SparseSelfAttention; decode stays dense — the KV-cache
        # loop attends a single query row)
        if cfg.pos_embedding == "alibi":
            raise NotImplementedError("ALiBi bias is not supported with block-sparse attention")
        from deepspeed_tpu.ops.pallas.block_sparse_attention import block_sparse_attention

        if nkv != nh:
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
        layout, block = _sparse_layout(cfg.sparse_attention or (("mode", "fixed"),), nh, S)
        # kernel convention matches the model: (B, S, H, hd)
        info = _kernel_shard(B, nh, nh)
        if info is not None:
            # same GSPMD-unpartitionable story as flash (_kernel_shard):
            # the per-head layout rows shard the way the heads do
            from jax.sharding import PartitionSpec

            mesh, spec = info
            lspec = PartitionSpec(spec[2], None, None)
            fn = _head_shard_map(
                mesh,
                lambda q_, k_, v_, l_: block_sparse_attention(
                    q_, k_, v_, l_, causal=cfg.causal, block=block,
                    sm_scale=cfg.attn_scale),
                (spec, spec, spec, lspec), spec)
            return fn(q, k, v, jnp.asarray(layout))
        return block_sparse_attention(q, k, v, layout, causal=cfg.causal, block=block,
                                      sm_scale=cfg.attn_scale)
    if ((window is None or (static_window is not None and cfg.causal))
            and cfg.attn_impl == "pallas" and cfg.pos_embedding != "alibi"):
        return _flash_sharded(q, k, v, cfg, causal=cfg.causal, window=static_window)
    if nkv != nh:
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
    scale = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if cfg.pos_embedding == "alibi":
        pos = jnp.arange(S, dtype=jnp.float32)
        rel = pos[None, :] - pos[:, None]  # (q, k): negative into the past
        logits = logits + _alibi_slopes(nh)[None, :, None, None] * rel[None, None]
    mask = None
    if cfg.causal:
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if window is not None:
        qp = jnp.arange(S, dtype=jnp.int32)[:, None]
        kp = jnp.arange(S, dtype=jnp.int32)[None, :]
        local_ok = (qp - kp < window) | (window <= 0)
        mask = local_ok if mask is None else mask & local_ok
    if mask is not None:
        logits = jnp.where(mask[None, None, :, :], logits, jnp.float32(-1e30))
    probs = fused_softmax(logits).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _kernel_shard(B, nh, nkv):
    """(mesh, qkv_spec) for running a Pallas attention kernel per-shard
    under shard_map, or None when no mesh of more than one device is live.

    GSPMD cannot partition a Mosaic kernel: the TPU lowering refuses one
    left inside a multi-device program outright ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map") —
    whatever the mesh axes are, pure ZeRO/data-parallel meshes included —
    and the interpreter, which GSPMD does partition, all-gathers the
    operands and computes every head on every chip. So on ANY multi-device
    mesh the kernel runs under shard_map. The spec shards (B, S, H, hd):
    batch over its data-parallel axes when it divides them, heads over
    'tensor' when the head counts divide it (the qkv projections' output
    sharding, so the common case reshards nothing), replicated over
    whatever is left."""
    from jax.sharding import PartitionSpec

    from deepspeed_tpu import comm

    if not comm.is_initialized():
        return None
    mesh = comm.get_mesh()
    if mesh.size == 1:
        return None
    tp = mesh.shape.get("tensor", 1)
    head_axis = "tensor" if tp > 1 and nh % tp == 0 and nkv % tp == 0 else None
    batch_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    if batch_axes and B % math.prod(mesh.shape[a] for a in batch_axes):
        batch_axes = ()
    return mesh, PartitionSpec(batch_axes or None, None, head_axis, None)


def _head_shard_map(mesh, fn, in_specs, out_spec):
    """shard_map wrapper for Pallas attention kernels (see _kernel_shard).
    Semantics are preserved for every caller — shard_map reshards inputs
    to the stated specs and back, so a mismatched sharding pays a
    reshard, never a wrong answer."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
                         check_vma=False)


def _flash_sharded(q, k, v, cfg: TransformerConfig, causal: bool, window=None):
    """Flash attention, run per-shard when a multi-device mesh is live
    (see _kernel_shard)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    blk = {"block_q": cfg.flash_block, "block_k": cfg.flash_block} if cfg.flash_block else {}
    kwargs = dict(causal=causal, sm_scale=cfg.attn_scale, window=window, **blk)

    info = _kernel_shard(q.shape[0], q.shape[2], k.shape[2])
    if info is None:
        return flash_attention(q, k, v, **kwargs)
    mesh, spec = info
    fn = _head_shard_map(
        mesh, lambda q_, k_, v_: flash_attention(q_, k_, v_, **kwargs),
        (spec, spec, spec), spec)
    return fn(q, k, v)


def _quick_gelu(x):
    # CLIP's approximation: x * sigmoid(1.702 x)
    return x * jax.nn.sigmoid(1.702 * x)


def _dense_act(cfg: TransformerConfig):
    return {"relu": jax.nn.relu, "quick_gelu": _quick_gelu}.get(cfg.activation, jax.nn.gelu)


@jax.named_scope(Scope.MLP)
def _mlp_block(h, mlp_p, cfg: TransformerConfig, dropout_rng=None, decode=False):
    """Shared MLP/MoE block: h (B,S,D) -> (out (B,S,D), moe aux loss)."""
    if cfg.moe_num_experts > 0:
        from deepspeed_tpu.moe.sharded_moe import moe_forward

        def expert_fn(ep, t):
            if cfg.activation == "silu_glu":
                a = jax.nn.silu(_linear(t, ep["wg"])) * _linear(t, ep["wi"])
            else:
                a = _linear(t, ep["wi"])
                if cfg.use_bias:
                    a = a + ep["bi"]
                a = _dense_act(cfg)(a)
            out = _linear(a, ep["wo"])
            if cfg.use_bias:
                out = out + ep["bo"]
            return out

        _residual_keys = ("res_wi", "res_wg", "res_wo", "res_bi", "res_bo",
                          "coef_w", "coef_b")
        expert_params = {k: v for k, v in mlp_p.items()
                         if k != "gate" and k not in _residual_keys}
        mlp_out, aux, _ = moe_forward(
            h,
            mlp_p["gate"],
            expert_fn,
            expert_params,
            k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor * (2 if decode else 1),
            min_capacity=cfg.moe_min_capacity,
            rng=dropout_rng if (cfg.moe_use_rts and not decode) else None,
            use_rts=cfg.moe_use_rts and not decode,
            drop_tokens=cfg.moe_drop_tokens,
        )
        if cfg.moe_use_residual:
            # PR-MoE (reference moe/layer.py:28,45): every token also runs
            # the dense residual MLP; a learned 2-way softmax mixes the two
            res_p = {k[len("res_"):]: v for k, v in mlp_p.items()
                     if k.startswith("res_")}
            dense_out = expert_fn(res_p, h)
            coef = jax.nn.softmax(h @ mlp_p["coef_w"] + mlp_p["coef_b"], axis=-1)
            # channel 0 scales the expert branch, channel 1 the dense MLP
            # (reference moe/layer.py:123 coefficient order)
            mlp_out = mlp_out * coef[..., 0:1] + dense_out * coef[..., 1:2]
        return mlp_out, aux
    aux = jnp.float32(0.0)
    if cfg.activation == "silu_glu":
        up = _linear(h, mlp_p["wi"])
        gate = _linear(h, mlp_p["wg"])
        act = jax.nn.silu(gate) * up
    else:
        act = _linear(h, mlp_p["wi"])
        if cfg.use_bias:
            act = act + mlp_p["bi"]
        act = _dense_act(cfg)(act)
    mlp_out = _linear(act, mlp_p["wo"])
    if cfg.use_bias:
        mlp_out = mlp_out + mlp_p["bo"]
    return mlp_out, aux


def _cast_layers(tree, dtype):
    """fp32->model-dtype cast for layer params that leaves int8-quantized
    weights' fp32 per-channel scales ("s" siblings of "q8") untouched —
    downcasting scales to bf16 would add dequant error comparable to the
    int8 quantization error itself."""
    def cast(path, p):
        if getattr(path[-1], "key", None) == "s":
            return p
        return p.astype(dtype) if p.dtype == jnp.float32 else p

    return jax.tree_util.tree_map_with_path(cast, tree)


def _linear(x, w):
    """Last-dim contraction ``x @ w`` that also accepts a REAL-int8 weight
    ({"q8": int8 (K,N), "s": per-channel scales} — built by the inference
    engine's weight quantizer). Raw arrays take the plain matmul path, so
    training is untouched; quantized leaves run the W8A8 int8-MXU kernel
    (ops/quantizer.int8_linear)."""
    if isinstance(w, dict):
        from deepspeed_tpu.ops.quantizer import int8_linear

        return int8_linear(x, w["q8"], w["s"])
    return x @ w


@jax.named_scope(Scope.ATTN_QKV)
def _qkv(h, attn_p, cfg: TransformerConfig, positions, product=_linear):
    """Project h -> (q, k, v) heads with positional transform applied (``product``: see _heads_product)."""
    B, S, _ = h.shape
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    q = product(h, attn_p["wq"])
    k = product(h, attn_p["wk"])
    v = product(h, attn_p["wv"])
    if cfg.use_bias:
        q, k, v = q + attn_p["bq"], k + attn_p["bk"], v + attn_p["bv"]
    q = q.reshape(B, S, nh, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    if cfg.pos_embedding == "rope":
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_interleaved)
    return q, k, v


@jax.named_scope(Scope.ATTN_OUT)
def _attn_out_proj(attn_out, attn_p, cfg: TransformerConfig):
    attn_out = _linear(attn_out, attn_p["wo"])
    if cfg.use_bias:
        attn_out = attn_out + attn_p["bo"]
    return attn_out


def _layer_body(x, layer_params, cfg: TransformerConfig, positions, dropout_rng,
                window=None):
    """One decoder layer; shapes: x (B,S,D), layer_params leaves unstacked.

    Residual topologies: pre-LN (GPT-2/llama), post-LN (BERT / OPT-350m
    ``do_layer_norm_before=False``), and parallel residual (GPT-J / NeoX:
    x + attn(ln1 x) + mlp(ln1|ln2 x))."""
    attn_p, mlp_p = layer_params["attn"], layer_params["mlp"]
    ln1, ln2 = layer_params["ln1"], layer_params["ln2"]
    B, S, D = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim

    def maybe_quant(h):
        if cfg.act_quant_bits > 0:
            from deepspeed_tpu.compression.ops import quantize_activation_ste

            return quantize_activation_ste(h, bits=cfg.act_quant_bits)
        return h

    pre_ln = cfg.norm_position == "pre"
    h = _norm(x, ln1["scale"], ln1.get("bias"), cfg) if pre_ln else x
    h = maybe_quant(h)
    q, k, v = _qkv(h, attn_p, cfg, positions)
    attn_out = _attention(q, k, v, cfg, positions, window=window).reshape(B, S, nh * hd)
    attn_out = _attn_out_proj(attn_out, attn_p, cfg)
    if cfg.dropout > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - cfg.dropout, attn_out.shape)
        attn_out = jnp.where(keep, attn_out / (1.0 - cfg.dropout), 0.0).astype(attn_out.dtype)

    if cfg.parallel_residual:
        h2 = h if cfg.shared_ln else maybe_quant(_norm(x, ln2["scale"], ln2.get("bias"), cfg))
        mlp_out, aux = _mlp_block(h2, mlp_p, cfg, dropout_rng)
        return x + attn_out + mlp_out, aux

    if pre_ln:
        x = x + attn_out
        h = maybe_quant(_norm(x, ln2["scale"], ln2.get("bias"), cfg))
        mlp_out, aux = _mlp_block(h, mlp_p, cfg, dropout_rng)
        return x + mlp_out, aux

    # post-LN: norm is applied over residual sums (BERT ordering)
    x = _norm(x + attn_out, ln1["scale"], ln1.get("bias"), cfg)
    mlp_out, aux = _mlp_block(maybe_quant(x), mlp_p, cfg, dropout_rng)
    return _norm(x + mlp_out, ln2["scale"], ln2.get("bias"), cfg), aux


# policy registry lives in runtime/activation_checkpointing (shared with the
# engine's configure() surface; adds host-offload as policy name "offload")
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as _ckpt  # noqa: E402
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import resolve_policy as _resolve_remat_policy  # noqa: E402


def _constrain_tp(p, logical_names):
    """Pin a parameter to its tensor-parallel compute sharding (the logical
    spec WITHOUT the ZeRO fsdp dim) at its use site.

    For the embedding tables this is what makes the gradient scatter-add
    partition well: the constraint's transpose pins the table cotangent to
    the same spec, so GSPMD scatters batch-sharded updates locally and
    psums over the batch axes, instead of resharding the full (B, S, D)
    hidden-state gradient from batch sharding to the fsdp grad-accumulator
    spec — its only plan for that is a replicate-then-repartition of the
    whole tensor ("[SPMD] Involuntary full rematerialization")."""
    from deepspeed_tpu import comm
    from deepspeed_tpu.runtime.zero.sharding import drop_indivisible_axes, logical_to_mesh_spec

    # is_initialized guard: get_mesh() would auto-create a default all-data
    # mesh, silently initializing global comm state from a bare forward()
    if not comm.is_initialized():
        return p
    mesh = comm.get_mesh()
    # same degradation as the stored sharding (ShardingPolicy._tp_spec)
    spec = drop_indivisible_axes(logical_to_mesh_spec(logical_names), p.shape, mesh)
    return jax.lax.with_sharding_constraint(p, jax.sharding.NamedSharding(mesh, spec))


def _constrain_batch_sharding(x):
    """Pin (B, S, ...) activations to batch sharding: dim0 over (data, fsdp),
    dim1 over sequence, trailing dims unconstrained.

    The constraint's transpose applies the same spec to the cotangent, so the
    hidden-state gradient leaving the backward layer scan stays batch-sharded.
    Without it, GSPMD propagates the (fsdp-sharded) embedding-grad-accumulator
    spec backwards onto the full (B, S, D) gradient, and its only way from
    batch-sharding to hidden-sharding there is a replicate-then-repartition of
    the whole tensor — the "[SPMD] Involuntary full rematerialization" warning
    (a full-tensor all-gather per step on the ZeRO-3 offload path)."""
    from deepspeed_tpu import comm

    if not comm.is_initialized() or x.ndim < 2:
        return x
    mesh = comm.get_mesh()
    batch_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    if not batch_axes:
        return x
    dp = 1
    for a in batch_axes:
        dp *= mesh.shape[a]
    if x.shape[0] % dp != 0:
        return x  # unshardable batch (e.g. odd eval shapes): leave it alone
    U = jax.sharding.PartitionSpec.UNCONSTRAINED
    sub = mesh.shape.get("sequence", 1)
    seq = "sequence" if sub > 1 and x.shape[1] % sub == 0 else U
    spec = jax.sharding.PartitionSpec(
        batch_axes if len(batch_axes) > 1 else batch_axes[0], seq, *([U] * (x.ndim - 2))
    )
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def forward(params, cfg: TransformerConfig, tokens, dropout_rng=None,
            ltd_keep_len=None, pld_theta=None, token_types=None, return_hidden=False,
            return_stats=False):
    """tokens (B, S) int32 -> (logits (B, S, V), moe_aux_loss scalar); with
    ``return_stats`` (a layer plan's) a third result, its expert layers'
    routing counters (``layer_plan.forward_plan``).

    ``ltd_keep_len`` (static int) — random-LTD: each participating layer runs
    on that many randomly kept tokens, outputs scattered back (reference
    data_routing/basic_layer.py:113; engine advances the schedule and re-jits
    per value). ``pld_theta`` (dynamic scalar) — progressive layer drop:
    stochastic depth with keep prob 1 - (l/L)(1-theta) (reference
    progressive_layer_drop.py, consumed at engine.py:1512).
    """
    if cfg.layer_kinds is not None:
        from deepspeed_tpu.models.layer_plan import forward_plan

        return forward_plan(params, cfg, tokens, return_hidden=return_hidden,
                            return_stats=return_stats)
    if return_stats:
        raise ValueError("routing counters are a layer plan's: the one-kind body keeps none")
    dtype = cfg.jnp_dtype
    B, S = tokens.shape
    with jax.named_scope(Scope.EMBED):
        x = jnp.take(_constrain_tp(params["embed"]["tok"], ("vocab", "embed")),
                     tokens, axis=0).astype(dtype)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        if cfg.pos_embedding == "learned":
            pos_t = _constrain_tp(params["embed"]["pos"], ("seq", "embed"))
            # explicit broadcast: the implicit (1, S, D) rank-promotion leaves a
            # keepdims reduce in the transpose whose unit dim drags the batch
            # sharding along, and GSPMD can only reshard that to the fsdp grad
            # spec by replicating ("[SPMD] Involuntary full rematerialization")
            x = x + jnp.broadcast_to(pos_t[:S].astype(dtype), x.shape)
        if cfg.type_vocab_size > 0:
            tt = token_types if token_types is not None else jnp.zeros_like(tokens)
            # same scatter-grad constraint as tok/pos (logical (None, "embed"),
            # matching logical_specs for the type table)
            type_t = _constrain_tp(params["embed"]["type"], (None, "embed"))
            x = x + jnp.take(type_t, tt, axis=0).astype(dtype)
        if cfg.embed_norm:
            en = params["embed_norm"]
            x = _norm(x, en["scale"], en.get("bias"), cfg)
    x = _constrain_batch_sharding(x)

    ltd_on = (
        cfg.random_ltd and ltd_keep_len is not None and 0 < int(ltd_keep_len) < S
        and dropout_rng is not None
    )
    pld_on = cfg.pld_enabled and pld_theta is not None and dropout_rng is not None

    # Window staticness: uniform windows (Mistral-style sliding window, or
    # no windows at all) are baked into the layer body as a python int via
    # this closure — surviving jax.checkpoint and lax.scan untraced, so
    # _attention can take the tile-pruned flash path. Only per-layer-varying
    # windows (GPT-Neo local/global alternation under scan_layers) flow
    # through as traced scalars.
    _varying_windows = cfg.varying_windows
    _static_win = (int(cfg.local_attn_windows[0])
                   if (cfg.local_attn_windows is not None and not _varying_windows)
                   else None)

    def layer_with_routing(x_in, layer_p, rng, layer_frac, window=None):
        """One layer + data-efficiency wrappers (LTD token subset, PLD skip)."""
        if not _varying_windows:
            window = _static_win  # closure keeps it a static python int
        r_drop = r_ltd = r_pld = None
        if rng is not None:
            r_drop, r_ltd, r_pld = jax.random.split(rng, 3)
        if ltd_on:
            from deepspeed_tpu.runtime.data_pipeline.data_routing.basic_layer import (
                gather_tokens,
                random_keep_indices,
                scatter_tokens,
            )

            idx = random_keep_indices(r_ltd, B, S, int(ltd_keep_len))
            x_k = gather_tokens(x_in, idx)
            pos_k = jnp.take_along_axis(positions, idx, axis=1)
            new_k, aux = _layer_body(x_k, layer_p, cfg=cfg, positions=pos_k,
                                     dropout_rng=r_drop, window=window)
            new_x = scatter_tokens(x_in, new_k, idx)
        else:
            new_x, aux = _layer_body(x_in, layer_p, cfg=cfg, positions=positions,
                                     dropout_rng=r_drop, window=window)
        if pld_on:
            p_keep = 1.0 - layer_frac * (1.0 - jnp.float32(pld_theta))
            keep = jax.random.bernoulli(r_pld, p_keep)
            new_x = jnp.where(keep, new_x, x_in)
            aux = jnp.where(keep, aux, jnp.zeros_like(aux))
        return new_x, aux

    layer_fn = layer_with_routing
    if cfg.remat:
        # unrolled layers receive window as a static python int (per-layer
        # flash tile pruning); it must stay static THROUGH the checkpoint
        # wrapper or the tracer defeats the isinstance(int) gate in
        # _attention. The scan path passes traced windows, where
        # static_argnums would be an error.
        static_args = (4,) if (not cfg.scan_layers and _varying_windows) else ()
        layer_fn = jax.checkpoint(layer_fn, policy=_resolve_remat_policy(cfg.remat_policy),
                                  static_argnums=static_args)
    if _ckpt.partition_activations_enabled():
        # partition_activations (reference checkpointing.py:366): shard the
        # layer-boundary residual over tensor(+sequence) so the saved stash
        # is 1/TP and GSPMD swaps the layer allreduce for AG+RS
        _inner_fn = layer_fn

        def layer_fn(x_in, *rest):  # noqa: F811
            return _inner_fn(_ckpt.partition_saved_activation(x_in), *rest)
    if _ckpt.profile_enabled():
        _profiled_fn = layer_fn

        def layer_fn(x_in, *rest):  # noqa: F811
            with jax.named_scope("checkpoint_layer"):
                return _profiled_fn(x_in, *rest)

    layers = _cast_layers(params["layers"], dtype)
    needs_rng = (
        cfg.dropout > 0.0 or cfg.moe_use_rts or ltd_on or pld_on
    ) and dropout_rng is not None
    L = cfg.num_layers
    layer_fracs = jnp.arange(1, L + 1, dtype=jnp.float32) / L
    if cfg.scan_layers:
        if needs_rng:
            layer_rngs = jax.random.split(dropout_rng, L)
        else:
            layer_rngs = jnp.zeros((L, 2), jnp.uint32)

        # uniform/absent windows are baked into the layer body as a static
        # int (see layer_with_routing); the stacked array only carries
        # per-layer-VARYING windows
        windows = (jnp.asarray(cfg.local_attn_windows, jnp.int32)
                   if _varying_windows else jnp.zeros((L,), jnp.int32))

        def scan_step(carry, inp):
            layer_p, rng, frac, win = inp
            rng = rng if needs_rng else None
            win = win if _varying_windows else None
            new_x, aux = layer_fn(carry, layer_p, rng, frac, win)
            return new_x, aux

        x, auxs = jax.lax.scan(scan_step, x, (layers, layer_rngs, layer_fracs, windows))
        aux_total = jnp.sum(auxs)
    else:
        aux_total = jnp.float32(0.0)
        for i in range(L):
            layer_p = jax.tree.map(lambda p: p[i], layers)
            rng = jax.random.fold_in(dropout_rng, i) if needs_rng else None
            # unrolled layers: every window is a static python int, so
            # each local layer gets the tile-pruned flash path (uniform
            # windows are redundantly re-set by the layer-body closure)
            win = (int(cfg.local_attn_windows[i])
                   if cfg.local_attn_windows is not None else None)
            x, aux = layer_fn(x, layer_p, rng, layer_fracs[i], win)
            aux_total = aux_total + aux

    if cfg.norm_position == "pre":  # post-LN stacks end normalized already
        x = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"), cfg)
    if return_hidden:
        return x, aux_total
    return _vocab_head(x, params, cfg, dtype), aux_total


@jax.named_scope(Scope.LM_HEAD)
def _vocab_head(x, params, cfg: TransformerConfig, dtype):
    """Hidden states -> vocab logits.

    An optional ``mlm_head`` in params (BERT ``cls.predictions.transform``
    / DistilBERT ``vocab_transform``+``vocab_layer_norm``: dense + act +
    LayerNorm, then a decoder bias) runs before the tied or untied
    projection — MLM checkpoints deviate from HF numerics without it."""
    mh = params.get("mlm_head")
    if mh is not None:
        x = _dense_act(cfg)(x @ mh["w"].astype(dtype) + mh["b"].astype(dtype))
        x = _norm(x, mh["ln_scale"], mh.get("ln_bias"), cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...sd,vd->...sv", x, params["embed"]["tok"].astype(dtype))
    else:
        w = params["lm_head"]["w"]
        logits = _linear(x, w if isinstance(w, dict) else w.astype(dtype))
        if "b" in params.get("lm_head", {}):
            logits = logits + params["lm_head"]["b"].astype(dtype)
    if mh is not None and "proj_bias" in mh:
        logits = logits + mh["proj_bias"].astype(dtype)
    return logits


def apply(params, cfg: TransformerConfig, tokens, dropout_rng=None, token_types=None):
    """tokens (B, S) int32 -> logits (B, S, V)."""
    return forward(params, cfg, tokens, dropout_rng=dropout_rng, token_types=token_types)[0]


def encode(params, cfg: TransformerConfig, tokens, token_types=None):
    """tokens (B, S) int32 -> final hidden states (B, S, D) (encoder use:
    the BERT-family injection policies; reference policy ABC policy.py)."""
    return forward(params, cfg, tokens, token_types=token_types, return_hidden=True)[0]


# ---------------------------------------------------------------------------
# streaming (sub-group) execution pieces — ZeRO-Infinity parameter offload
# (runtime/zero/param_offload.py). The decoder is cut at layer-group
# boundaries so host-resident weights stream through HBM one group at a
# time; the activation at each boundary is the only checkpoint kept.
# Reference analogue: stage3.py sub_group_size streaming +
# partitioned_param_swapper.py.
# ---------------------------------------------------------------------------

@jax.named_scope(Scope.EMBED)
def embed_fwd(params, cfg: TransformerConfig, tokens):
    """tokens (..., S) -> embedded activations (..., S, D) in model dtype
    (leading dims beyond batch — e.g. a microbatch dim — broadcast through)."""
    dtype = cfg.jnp_dtype
    S = tokens.shape[-1]
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(dtype)
    if cfg.pos_embedding == "learned":
        x = x + params["embed"]["pos"][:S].astype(dtype)
    if cfg.type_vocab_size > 0:
        x = x + params["embed"]["type"][0].astype(dtype)
    if cfg.embed_norm:
        en = params["embed_norm"]
        x = _norm(x, en["scale"], en.get("bias"), cfg)
    return x


def layer_slice_fwd(layers_slice, cfg: TransformerConfig, x, windows=None):
    """Run a contiguous group of decoder layers (stacked leaves, leading dim
    = group size). Returns (x', moe_aux_sum). No dropout in the streaming
    path (offload training runs at scales where dropout is off).

    ``windows`` — (group_size,) i32 per-layer local-attention windows for
    models with cfg.local_attn_windows (GPT-Neo); the caller slices the
    global tuple to this group's [lo:hi) rows. None = all-global."""
    B, S, D = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    layer_fn = partial(_layer_body, cfg=cfg, positions=positions, dropout_rng=None)
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=_resolve_remat_policy(cfg.remat_policy))
    dtype = cfg.jnp_dtype
    layers = _cast_layers(layers_slice, dtype)

    n = jax.tree.leaves(layers_slice)[0].shape[0]
    if windows is None and cfg.local_attn_windows is not None:
        raise ValueError(
            "cfg.local_attn_windows is set: layer_slice_fwd needs this "
            "group's per-layer windows (pass windows=cfg.local_attn_windows[lo:hi])"
        )
    wins = windows if windows is not None else jnp.zeros((n,), jnp.int32)

    def scan_step(carry, inp):
        layer_p, win = inp
        win = win if windows is not None else None
        new_x, aux = layer_fn(carry, layer_p, window=win)
        return new_x, aux

    x, auxs = jax.lax.scan(scan_step, x, (layers, wins))
    return x, jnp.sum(auxs)


@jax.named_scope(Scope.LOSS)
def _ce_from_logits(logits, batch, tokens, denom=None):
    """Shift + masked token cross-entropy shared by loss_fn / head_loss_fwd.

    ``denom`` overrides the masked normalizer — callers that sum partial CE
    terms across microbatches (the 1F1B pipeline head) pass the GLOBAL mask
    token count so per-microbatch sums add up to the whole-batch mean.
    """
    from deepspeed_tpu.ops.cross_entropy import softmax_cross_entropy

    if "labels" in batch:
        labels = batch["labels"]
        logits_for_loss = logits
    else:
        labels = tokens[..., 1:]
        logits_for_loss = logits[..., :-1, :]
    nll = softmax_cross_entropy(logits_for_loss, labels)
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[..., : nll.shape[-1]].astype(jnp.float32)
        if denom is None:
            denom = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(nll * mask) / denom
    if denom is not None:
        return jnp.sum(nll) / denom
    return jnp.mean(nll)


def head_loss_fwd(params, cfg: TransformerConfig, x, batch, denom=None):
    """Final norm + logits + cross-entropy (MoE aux is added by the caller
    from the per-group aux sums)."""
    dtype = cfg.jnp_dtype
    if cfg.norm_position == "pre":
        x = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"), cfg)
    logits = _vocab_head(x, params, cfg, dtype)
    return _ce_from_logits(logits, batch, batch["input_ids"], denom=denom)


# ---------------------------------------------------------------------------
# KV-cache decode path (reference: csrc/transformer/inference softmax_context
# kernels + InferenceEngine token loop, inference/engine.py:560)
# ---------------------------------------------------------------------------

def init_cache(cfg: TransformerConfig, batch_size: int, max_len: Optional[int] = None):
    # kept for benchmark/rehearse_aot.py and runtime/hybrid_engine.py: everyone else calls kv_cache.init
    return kv_cache.init(cfg, batch_size, max_len or cfg.max_seq_len)


def _layer_body_cached(x, layer_params, pool_k, pool_v, layer, cfg: TransformerConfig, positions,
                       pos, window=None, read_len=None):
    """One decoder layer over a segment of S new tokens with KV cache.

    x: (B, S, D); pool_k/pool_v: the WHOLE stacked (L, B, T, nkv, hd) pool
    (or its int8 {"q8","s"} form), of which this layer touches ``[layer]``
    only: it writes the S new tokens in place and reads its own window
    back, so no layer-sized copy of the pool is ever made. pos: the
    count of tokens already cached — a scalar (all rows aligned: plain
    prefill/decode) or an (B,) vector (rows at different depths: the
    speculative-decode verify/draft path writes each row's segment at its
    own offset). ``read_len`` (static int) tight-reads the cache: attention
    streams only slots [0, read_len) — the caller guarantees it covers
    every attended position. Returns (x, pool_k, pool_v).
    """
    attn_p, mlp_p = layer_params["attn"], layer_params["mlp"]
    ln1, ln2 = layer_params["ln1"], layer_params["ln2"]
    B, S, D = x.shape
    nh, hd = cfg.num_heads, cfg.head_dim

    pre_ln = cfg.norm_position == "pre"
    h = _norm(x, ln1["scale"], ln1.get("bias"), cfg) if pre_ln else x
    q, k, v = _qkv(h, attn_p, cfg, positions, product=_heads_product)

    # PREFILL fast path: pos is the literal int 0 only in the prefill
    # program (compile_decode_fns traces with a Python 0), where attention
    # over the segment is exactly causal self-attention — the Pallas flash
    # kernel computes it without materializing the (B, H, S, T) logits
    # (reference: the inference softmax_context kernel family). Static
    # windows ride the kernel's tile-pruned band path; the rolling cache
    # RELIES on this (segment attention must not read the ring, whose
    # slots a long segment partially evicts).
    from deepspeed_tpu.ops.pallas.flash_attention import supports_seq_len

    use_flash_prefill = (
        isinstance(pos, int) and pos == 0 and S > 1
        and (window is None or isinstance(window, int))
        and cfg.attn_impl == "pallas" and cfg.causal
        and cfg.pos_embedding != "alibi"
        # seq lens the auto-tiler can't cover stay on the einsum path
        # rather than erroring at trace time
        and supports_seq_len(S)
    )
    ring = cfg.rolling_kv_cache
    if ring:
        read_len = None  # the ring is already O(window): no tight reads

    with jax.named_scope(Scope.ATTN_KV_WRITE):
        pool_k, pool_v = update_kv_cache(pool_k, pool_v, k, v, pos, positions, ring=ring,
                                         layer=layer, write_len=read_len)

    if use_flash_prefill:
        w = window if isinstance(window, int) and window > 0 and window < S else None
        with jax.named_scope(Scope.ATTN_CORE):
            attn_out = _flash_sharded(q, k, v, cfg, causal=True,
                                      window=w).reshape(B, S, nh * hd)
        attn_out = _attn_out_proj(attn_out, attn_p, cfg)
        return _finish_layer_cached(x, h, attn_out, layer_params, cfg), pool_k, pool_v

    cache_T = kv_cache.alloc_len(cfg, pool_k)
    assert not (ring and S > 1 and cache_T < S), (
        "rolling KV cache: a multi-token segment longer than the ring must "
        f"take the flash prefill path (S={S}, cache={cache_T}) — a segment "
        "read through the ring would see its own evictions; the engine "
        "gates cache sizing on this")
    slopes = _alibi_slopes(nh) if cfg.pos_embedding == "alibi" else None
    attn_out = softmax_context(
        q, pool_k, pool_v, pos, scale=cfg.attn_scale, positions=positions,
        alibi_slopes=slopes, local_window=window, ring=ring, read_len=read_len, layer=layer,
    ).reshape(B, S, nh * hd)
    attn_out = _attn_out_proj(attn_out, attn_p, cfg)
    return _finish_layer_cached(x, h, attn_out, layer_params, cfg), pool_k, pool_v


def _finish_layer_cached(x, h, attn_out, layer_params, cfg: TransformerConfig):
    """Residual topology + MLP tail of a cached layer (shared by the einsum
    and flash-prefill attention paths)."""
    mlp_p = layer_params["mlp"]
    ln1, ln2 = layer_params["ln1"], layer_params["ln2"]

    if cfg.parallel_residual:
        h2 = h if cfg.shared_ln else _norm(x, ln2["scale"], ln2.get("bias"), cfg)
        mlp_out, _ = _mlp_block(h2, mlp_p, cfg, decode=True)
        return x + attn_out + mlp_out

    if cfg.norm_position == "pre":
        x = x + attn_out
        h = _norm(x, ln2["scale"], ln2.get("bias"), cfg)
        mlp_out, _ = _mlp_block(h, mlp_p, cfg, decode=True)
        return x + mlp_out

    x = _norm(x + attn_out, ln1["scale"], ln1.get("bias"), cfg)
    mlp_out, _ = _mlp_block(x, mlp_p, cfg, decode=True)
    return _norm(x + mlp_out, ln2["scale"], ln2.get("bias"), cfg)


def forward_with_cache(params, cfg: TransformerConfig, tokens, cache, pos, positions=None,
                       read_len=None):
    """Segment forward with KV cache (prefill: S = prompt len, pos = 0;
    decode: S = 1). ``pos`` may be a scalar (all rows aligned) or an (B,)
    vector of per-row depths (speculative decoding — rows advance by their
    own accepted counts). ``positions`` (B, S) overrides the derived token
    positions for RAGGED/padded prompts: pad slots carry position >= cache
    length, so their KV writes drop out of bounds and real tokens pack
    densely per row (requires vector ``pos``). ``read_len`` (static int)
    tight-reads the cache time axis — attention streams slots
    [0, read_len) only, and the write touches no slot beyond them; the
    caller guarantees the active extent (the new tokens included) fits.

    The pool travels in the layer scan's CARRY, never as its xs / ys: each
    layer updates ``[layer]`` in place (kv_cache.update_kv_cache) and
    reads its window straight back (softmax_context), so with the cache
    donated a call moves the windows it reads and no layer-sized copy.
    Returns (logits (B,S,V), updated cache)."""
    if cfg.layer_kinds is not None:
        from deepspeed_tpu.models.layer_plan import forward_plan_cached

        if tokens.shape[1] != 1 or jnp.ndim(pos) != 1 or positions is not None:
            raise NotImplementedError(
                "a layer-plan model takes single-token rows at per-row depths here; its "
                "prompts are prefilled chunk by chunk in the serving tick "
                "(layer_plan.forward_tick)")
        logits, cache, _ = forward_plan_cached(params, cfg, tokens[:, 0], pos, cache,
                                               read_len=read_len)
        return logits[:, None], cache
    B, S = tokens.shape
    if read_len is not None and read_len >= kv_cache.alloc_len(cfg, cache):
        read_len = None  # degenerate slice: the allocation is already tight
    if positions is not None:
        assert jnp.ndim(pos) == 1, "explicit positions require vector pos"
    elif jnp.ndim(pos) == 1:
        positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # (B, S)
    else:
        positions = pos + jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    x = _embed_cached(params, cfg, tokens, positions, aligned=jnp.ndim(pos) == 0)

    def layer_fn(h, layer_p, pool_k, pool_v, layer, win):
        return _layer_body_cached(h, layer_p, pool_k, pool_v, layer, cfg, positions, pos,
                                  window=win, read_len=read_len)

    x, cache = _scan_layers_cached(params, cfg, x, cache, layer_fn)
    return _head_cached(x, params, cfg), cache


def _embed_cached(params, cfg: TransformerConfig, tokens, positions, aligned: bool):
    """Token (+ learned position, type) embedding of a cached segment:
    tokens / positions (B, S) -> (B, S, D). ``aligned``: every row sits at
    the same positions (scalar ``pos``), so one row's are looked up."""
    dtype = cfg.jnp_dtype
    with jax.named_scope(Scope.EMBED):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(dtype)
        if cfg.pos_embedding == "learned":
            pos_table = params["embed"]["pos"].astype(dtype)
            clamped = jnp.minimum(positions, pos_table.shape[0] - 1)
            x = x + jnp.take(pos_table, clamped[0] if aligned else clamped, axis=0)
        if cfg.type_vocab_size > 0:
            # decode has no token-type stream; type 0 matches forward()'s default
            x = x + params["embed"]["type"][0].astype(dtype)
        if cfg.embed_norm:
            en = params["embed_norm"]
            x = _norm(x, en["scale"], en.get("bias"), cfg)
    return x


def _scan_layers_cached(params, cfg: TransformerConfig, x, cache, layer_fn):
    """The layer scan of a cached forward: ``layer_fn(x, layer_params,
    pool_k, pool_v, layer, window) -> (x, pool_k, pool_v)`` over every
    layer. Returns (x, cache)."""
    layers = _cast_layers(params["layers"], cfg.jnp_dtype)

    # mirror forward(): a uniform window stays a STATIC int through the
    # scan (flash band prefill + the rolling cache depend on it); only
    # per-layer-varying windows ride the scan as traced scalars
    uniform_w = cfg.uniform_window
    varying = cfg.varying_windows
    windows = (
        jnp.asarray(cfg.local_attn_windows, jnp.int32)
        if varying else jnp.zeros((cfg.num_layers,), jnp.int32)
    )

    # the pool rides the CARRY: a carried buffer is updated in place, layer
    # after layer. A scanned input (xs) is read-only and a stacked output
    # (ys) a fresh buffer, so as xs / ys XLA has to copy each layer's whole
    # (B, T, H, hd) K and V out of the pool and back, donated or not
    def body(carry, inp):
        h, pool_k, pool_v = carry
        layer_p, layer, win = inp
        return layer_fn(h, layer_p, pool_k, pool_v, layer, win if varying else uniform_w), None

    layer_ids = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, pool_k, pool_v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]), (layers, layer_ids, windows))
    return x, {"k": pool_k, "v": pool_v}


def _head_cached(x, params, cfg: TransformerConfig):
    if cfg.norm_position == "pre":
        x = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"), cfg)
    return _vocab_head(x, params, cfg, cfg.jnp_dtype)


def forward_tick_cached(params, cfg: TransformerConfig, tokens, pos, cache, chunk,
                        read_len=None):
    """One serving tick that carries a prefill chunk, as a FLAT list of
    B + W tokens: every slot's next token (``tokens`` / ``pos`` (B,): a
    parked row carries the pool's length, writes nothing and its output
    means nothing) followed by ONE row's next W prompt tokens (``chunk``, a
    ``layer_plan.Chunk``; the row it belongs to is parked among the rows,
    pads carry the pool's length). Embedding, norms, projections and MLP run
    over the B + W tokens once; rows write one token at their own depth and
    attend their windows as a plain tick does, the chunk writes its tokens
    into row ``chunk.slot`` alone and attends that row's ``[0, read_len)``
    after the write, causal by ``chunk.pos``. The pool rides the layer
    scan's carry as in :func:`forward_with_cache`. Returns (logits
    (B + 1, V): the rows', then the chunk's column ``chunk.emit``; cache)."""
    assert not cfg.rolling_kv_cache, "slot pools run plain caches (rows sit at their own depths)"
    B = tokens.shape[0]
    if read_len is not None and read_len >= kv_cache.alloc_len(cfg, cache):
        read_len = None
    positions = jnp.concatenate([pos, chunk.pos])[None]                  # (1, B + W)
    x = _embed_cached(params, cfg, jnp.concatenate([tokens, chunk.toks])[None], positions,
                      aligned=False)
    slopes = _alibi_slopes(cfg.num_heads) if cfg.pos_embedding == "alibi" else None
    row_pos, chunk_pos = pos[:, None], chunk.pos[None]                   # (B, 1), (1, W)
    chunk_depth = chunk.pos[:1]  # a (1,) vector ``pos``: the per-row form of write and read

    def layer_fn(x, layer_p, pool_k, pool_v, layer, window):
        attn_p, ln1 = layer_p["attn"], layer_p["ln1"]
        h = _norm(x, ln1["scale"], ln1.get("bias"), cfg) if cfg.norm_position == "pre" else x
        q, k, v = _qkv(h, attn_p, cfg, positions, product=_heads_product)  # (1, B + W, heads, hd)
        rows = lambda a: a[0, :B, None]                                  # (B, 1, heads, hd)
        with jax.named_scope(Scope.ATTN_KV_WRITE):
            pool_k, pool_v = update_kv_cache(pool_k, pool_v, rows(k), rows(v), pos, row_pos,
                                             layer=layer, write_len=read_len)
            pool_k, pool_v = update_kv_cache(pool_k, pool_v, k[:, B:], v[:, B:], chunk_depth,
                                             chunk_pos, layer=layer, write_len=read_len,
                                             slot=chunk.slot)
        attend = partial(softmax_context, scale=cfg.attn_scale, alibi_slopes=slopes,
                         local_window=window, read_len=read_len, layer=layer)
        att_rows = attend(rows(q), pool_k, pool_v, pos, positions=row_pos)
        att_chunk = attend(q[:, B:], pool_k, pool_v, chunk_depth, positions=chunk_pos,
                           slot=chunk.slot)
        attn_out = jnp.concatenate([att_rows.reshape(B, -1),
                                    att_chunk.reshape(chunk.toks.shape[0], -1)])[None]
        attn_out = _attn_out_proj(attn_out, attn_p, cfg)
        return _finish_layer_cached(x, h, attn_out, layer_p, cfg), pool_k, pool_v

    x, cache = _scan_layers_cached(params, cfg, x, cache, layer_fn)
    # the head over B + 1 positions: the rows, and the chunk's sampled column
    x = jnp.concatenate([x[0, :B], jax.lax.dynamic_slice_in_dim(x[0], B + chunk.emit, 1)])
    return _head_cached(x, params, cfg), cache


def loss_fn(params, cfg: TransformerConfig, batch, rng=None, ltd_keep_len=None, pld_theta=None,
            with_counters=False):
    """Next-token cross entropy. batch: {'input_ids': (B,S) int32} and
    optional 'labels' (shifted internally if absent), 'loss_mask', and
    'token_type_ids' (BERT-family segment ids). ``with_counters`` (a layer
    plan's): (loss, the forward's routing counters)."""
    tokens = batch["input_ids"]
    logits, moe_aux, *stats = forward(
        params, cfg, tokens, dropout_rng=rng,
        ltd_keep_len=ltd_keep_len, pld_theta=pld_theta,
        token_types=batch.get("token_type_ids"), return_stats=with_counters,
    )
    ce = _ce_from_logits(logits, batch, tokens)
    if cfg.moe_num_experts > 0:
        ce = ce + cfg.moe_aux_loss_coef * moe_aux
    return (ce, stats[0]) if with_counters else ce


class TransformerModel:
    """Engine-protocol wrapper (see runtime/engine.py): init/loss/logical_specs."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    @classmethod
    def from_preset(cls, name: str, **overrides):
        return cls(get_config(name, **overrides))

    def init(self, rng):
        return init(rng, self.cfg)

    def loss(self, params, batch, rng=None, ltd_keep_len=None, pld_theta=None,
             with_counters=False):
        return loss_fn(
            params, self.cfg, batch, rng=rng,
            ltd_keep_len=ltd_keep_len, pld_theta=pld_theta, with_counters=with_counters,
        )

    # what a layer plan's expert layers count in one forward, summed over the layers (the most
    # one held expert got in a layer: its largest), in ``loss_with_counters``'s order
    counter_names = ("moe_assignments", "moe_held_assignments", "moe_expert_tokens_most",
                     "moe_expert_layers", "moe_experts_hit")

    @property
    def loss_with_counters(self):
        """The engine's optional protocol (looked up as ``ltd_keep_len`` is):
        ``loss`` returning ``(loss, counters (len(counter_names),) int32)``,
        which the engine sums over micro-steps (``engine.moe_stats()``). None
        for a model without expert layers in a plan: the engine then compiles
        the micro-step of a model that never had the attribute."""
        cfg = self.cfg
        if cfg.layer_kinds is None or not any(k.ffn == "moe" for k in cfg.plan):
            return None
        return partial(self.loss, with_counters=True)

    def apply(self, params, tokens, rng=None):
        return apply(params, self.cfg, tokens, dropout_rng=rng)

    def logical_specs(self, params):
        return logical_specs(params, self.cfg)

    def flops_per_token(self, seq_len: int) -> float:
        return self.cfg.flops_per_token(seq_len)

    def num_params(self) -> int:
        return self.cfg.num_params()


def _heads_product(x, w):
    """:func:`_linear` for a q / k / v product of a program that holds a KV
    pool, whose consumers are heads-first (the pool's leaves, the grouped
    attention): the ``(..., heads * width)`` result passes a row-major layout
    constraint before it is split into heads, so the chip's compiler lays
    the product out for itself and not for them. Left free, it laid the
    RESULT out heads-outermost and so wanted the weight transposed: a copy
    of the whole stacked leaf every tick, or of a layer's slice every layer
    (PERF.md section 6, PR 46). Constrained, the product takes the stacked
    weight as the engine holds it, by the layer's index, and what is re-laid
    out for the heads is the result (rows x heads * width: 64 KB at 16 rows).
    Changes no value. Two cases constrain nothing: a quantized leaf, which
    goes where it went (no cell measures one), and a program whose pools
    span several chips, where the partitioner knows no rule for the
    constraint and gathers a split result to apply it (three all-gathers a
    layer at tensor width 2)."""
    from jax.experimental.layout import Layout, with_layout_constraint  # here: no line above moves

    if isinstance(w, dict) or kv_cache.traced_over_chips():
        return _linear(x, w)
    out = _linear(x, w)
    return with_layout_constraint(out, Layout(major_to_minor=tuple(range(out.ndim))))
