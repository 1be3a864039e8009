"""Configuration file -> the program's model object, for GLM-4.7-Flash's
family (``model_type`` ``glm4_moe_lite``; ``"builder":
"benchmark.models_glm4_moe_lite"``; its reference is
``reference/glm4_moe_lite.py``): latent attention (MLA) in every layer, the
first ``first_k_dense_replace`` layers a dense SwiGLU and every later one
sigmoid top-k routing with a selection bias and a routed scaling factor over
``n_routed_experts`` experts plus a shared expert added as it is. The keys
are the published ``config.json``'s own; ``deployment`` says which experts
this chip holds (all of them, in the committed configuration)."""

import dataclasses
import math

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
                  "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "n_shared_experts",
                  "num_experts_per_tok", "first_k_dense_replace", "max_position_embeddings",
                  "vocab_size")

KINDS = ("dense", "moe")


def layer_plan(m):
    """Each layer's index into :data:`KINDS`: dense below ``first_k_dense_replace``."""
    return tuple(int(i >= m["first_k_dense_replace"]) for i in range(m["num_hidden_layers"]))


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    if "mla_kv_rank" not in {f.name for f in dataclasses.fields(TransformerConfig)}:
        # the parent of the PR that brought the family: the harness reports it and exits
        raise ImportError("this program's layer plan has no latent-attention mixer")
    m, dep = config["model"], config["deployment"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if (m["tie_word_embeddings"] or m["attention_bias"] or not m["norm_topk_prob"]
            or m["rope_scaling"] or m["hidden_act"] != "silu" or m["topk_method"] != "noaux_tc"
            or m["n_group"] != 1 or m["topk_group"] != 1 or m["partial_rotary_factor"] != 1
            or m["num_key_value_heads"] != m["num_attention_heads"]
            or m["num_nextn_predict_layers"]):
        raise ValueError("this family's reference has an untied head, no biases, normalised "
                         "top-k weights chosen by score + bias in one group, plain rotary over "
                         "the whole rotary width, SiLU, a key-value head a query head and no "
                         "multi-token-prediction module")
    held = dep["held_experts"]
    if held["count"] != m["n_routed_experts"] or dep["experts_published"] < held["first"] + held["count"]:
        raise ValueError("n_routed_experts counts the experts this chip holds, of experts_published")
    mixer = dict(mixer="mla", rope_theta=float(m["rope_theta"]))
    kinds = (LayerKind(name="dense", ffn="dense", ffn_size=m["intermediate_size"], **mixer),
             LayerKind(name="moe", ffn="moe", ffn_size=m["moe_intermediate_size"], **mixer))
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        head_size=m["qk_nope_head_dim"] + m["qk_rope_head_dim"], v_head_size=m["v_head_dim"],
        ffn_hidden_size=m["intermediate_size"], rope_interleaved=False,
        pos_embedding="rope", norm_type="rmsnorm", norm_eps=m["rms_norm_eps"],
        activation="silu_glu", tie_embeddings=False, use_bias=False,
        layer_kinds=kinds, layer_plan=layer_plan(m),
        mla_q_rank=m["q_lora_rank"], mla_kv_rank=m["kv_lora_rank"],
        mla_nope_dim=m["qk_nope_head_dim"], mla_rope_dim=m["qk_rope_head_dim"],
        mla_v_dim=m["v_head_dim"],
        moe_num_experts=dep["experts_published"], moe_top_k=m["num_experts_per_tok"],
        moe_experts_held=(held["first"], held["count"]), moe_score="sigmoid",
        moe_routed_scale=float(m["routed_scaling_factor"]),
        moe_shared_size=m["n_shared_experts"] * m["moe_intermediate_size"], moe_shared_gated=False,
        init_in_model_dtype=bool(config.get("init_in_model_dtype", False)),
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


def sharpen(params, config, query_scale):
    """Seed weights rescaled so that the context decides the next token.
    Init leaves every score a unit draw (a normed query latent through
    ``wuq`` at 1/sqrt(rank), a normed latent through ``wukv`` likewise, the
    shared key ``h wdkv`` at 1/sqrt(hidden): q . k over 256 dimensions /
    sqrt(256) has unit spread, of which the rotated 64 carry a quarter). The
    queries are sharpened through the QUERY NORM's scale (the norm undoes
    any scale put on ``wdq``): ``w -> query_scale x w``, which scales the
    unrotated and the rotated part alike; init's 1/sqrt(2L) on the output
    projection is undone."""
    L = config["model"]["num_hidden_layers"]
    for kind in params["layers"].values():
        mla = kind["mla"]
        mla["q_norm"] = (mla["q_norm"].astype("float32") * query_scale).astype(mla["q_norm"].dtype)
        mla["wo"] = mla["wo"] * math.sqrt(2 * L)
    return params
