"""Configuration file -> the program's model object, for Qwen3-Next's family
(``"builder": "benchmark.models_qwen3_next"``; its reference is
``reference/qwen3_next.py``): Gated DeltaNet layers, every
``full_attention_interval``-th layer gated full attention, every layer a
softmax top-k expert layer with a shared expert, of whose routed experts this
chip holds a contiguous share. The keys are the published ``config.json``'s
own; ``num_experts`` counts the experts HELD and ``deployment`` says which
and of how many."""

import dataclasses
import math

REQUIRED_SIZES = ("hidden_size", "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
                  "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel_dim", "full_attention_interval",
                  "num_experts", "num_experts_per_tok", "max_position_embeddings", "vocab_size")

KINDS = ("gdn", "full")


def rotary_dim(m):
    """``partial_rotary_factor`` of the head width, rounded down to a whole pair."""
    return int(m["partial_rotary_factor"] * m["head_dim"]) // 2 * 2


def layer_plan(m):
    """Each layer's index into :data:`KINDS`: full attention where ``(i + 1) %
    full_attention_interval == 0``."""
    return tuple(int((i + 1) % m["full_attention_interval"] == 0)
                 for i in range(m["num_hidden_layers"]))


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    if "mixer" not in {f.name for f in dataclasses.fields(LayerKind)}:
        # the parent of the PR that brought the family: the harness reports it and exits
        raise ImportError("this program's layer plan has no gated-delta-rule mixer")
    m, dep = config["model"], config["deployment"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if (m["tie_word_embeddings"] or m["mlp_only_layers"] or m["decoder_sparse_step"] != 1
            or not m["norm_topk_prob"] or m["use_sliding_window"] or m["rope_scaling"]
            or m["hidden_act"] != "silu"):
        raise ValueError("this family's reference has an untied head, an expert layer in every "
                         "layer, normalised top-k weights, no sliding window, plain rotary, SiLU")
    if dep["held_experts"]["count"] != m["num_experts"]:
        raise ValueError("num_experts counts the experts this chip holds")
    plan = layer_plan(m)
    if 1 not in plan:
        raise ValueError("the depth holds no full-attention layer")
    kinds = (LayerKind(name="gdn", mixer="gdn", ffn="moe", ffn_size=m["moe_intermediate_size"]),
             LayerKind(name="full", kv_heads=m["num_key_value_heads"],
                       rope_theta=float(m["rope_theta"]), ffn="moe",
                       ffn_size=m["moe_intermediate_size"]))
    held = dep["held_experts"]
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        head_size=m["head_dim"], rope_dim=rotary_dim(m), rope_interleaved=False,
        pos_embedding="rope", norm_type="rmsnorm", norm_eps=m["rms_norm_eps"], norm_one_plus=True,
        activation="silu_glu", tie_embeddings=False, use_bias=False, attn_out_gate=True,
        qk_norm=True, layer_kinds=kinds, layer_plan=plan,
        gdn_key_heads=m["linear_num_key_heads"], gdn_value_heads=m["linear_num_value_heads"],
        gdn_key_dim=m["linear_key_head_dim"], gdn_value_dim=m["linear_value_head_dim"],
        gdn_conv=m["linear_conv_kernel_dim"],
        moe_num_experts=dep["experts_published"], moe_top_k=m["num_experts_per_tok"],
        moe_experts_held=(held["first"], held["count"]), moe_score="softmax",
        moe_shared_size=m["shared_expert_intermediate_size"],
        init_in_model_dtype=bool(config.get("init_in_model_dtype", False)),
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


# the log of a head's decay rate A, placed at DECAY_AT + DECAY_SPREAD x its unit draw: a
# token's decay is exp(-A x softplus(.)), softplus near 1, so a head at the centre forgets in
# ~30 tokens, one two spreads below it would keep a third of a token 1,000 positions back, and
# one two spreads above forgets in two: every head's decay is far from both 0 and 1 somewhere.
# (The slow heads' memory is bounded by the rule's own overwrite, not the decay: beta ~ 0.5
# over 128 unit-key dimensions renews a state every ~300 tokens, and b has no bias to place.)
DECAY_AT, DECAY_SPREAD = -3.5, 1.5
KEY_SCALE = 0.5


def sharpen(params, config, query_scale):
    """Seed weights rescaled so that the context decides the next token and
    each of the family's terms carries weight in the logits (a term drawn at
    unit scale can be invisible to the comparison: PERF.md section 6, PR
    27b). Both mixers: init's 1/sqrt(2L) on the output projection undone.
    Full attention: the queries sharpened through the QUERY NORM's scale (the
    norm undoes any scale put on wq), ``1 + w -> query_scale x (1 + w)``, and
    wk scaled by :data:`KEY_SCALE`, which the key norm undoes and a program
    without the norm would not. Gated DeltaNet: ``A_log`` placed as
    :data:`DECAY_AT` says, where init's unit draw (decay rates near 1) would
    leave no head that remembers a token a hundred positions back."""
    L = config["model"]["num_hidden_layers"]
    layers = params["layers"]
    attn, gdn = layers["full"]["attn"], layers["gdn"]["gdn"]
    like = lambda new, old: new.astype(old.dtype)
    attn["q_norm"] = like(query_scale * (1.0 + attn["q_norm"].astype("float32")) - 1.0,
                          attn["q_norm"])
    attn["wk"] = attn["wk"] * KEY_SCALE
    attn["wo"] = attn["wo"] * math.sqrt(2 * L)
    gdn["wo"] = gdn["wo"] * math.sqrt(2 * L)
    gdn["a_log"] = like(DECAY_AT + DECAY_SPREAD * gdn["a_log"].astype("float32"), gdn["a_log"])
    return params
