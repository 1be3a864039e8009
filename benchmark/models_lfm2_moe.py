"""Configuration file -> the program's model object, for LFM2-24B-A2B's
family (``model_type`` ``lfm2_moe``; ``"builder":
"benchmark.models_lfm2_moe"``; its reference is ``reference/lfm2_moe.py``):
``layer_types`` says which layers mix by a gated short convolution and which
by rotary grouped-query attention with a norm on each head's queries and
keys; the first ``num_dense_layers`` layers have a dense SwiGLU and every
later one sigmoid top-k routing with a selection bias over ``num_experts``
experts, no shared expert; the head is tied. The keys are the published
``config.json``'s own; ``deployment`` says which experts this chip holds."""

import dataclasses

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads", "num_experts",
                  "num_experts_per_tok", "num_dense_layers", "conv_L_cache",
                  "max_position_embeddings", "vocab_size")

MIXERS = {"conv": "conv", "full_attention": "attn"}   # layer_types -> the kind's name, as the reference reads it


def kind_names(m):
    """Each layer's kind, by name: its mixer and whether its FFN is dense."""
    return [MIXERS[t] + ("_dense" if i < m["num_dense_layers"] else "_moe")
            for i, t in enumerate(m["layer_types"])]


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    if "conv_taps" not in {f.name for f in dataclasses.fields(TransformerConfig)}:
        # the parent of the PR that brought the family: the harness reports it and exits
        raise ImportError("this program's layer plan has no short-convolution mixer")
    m, dep = config["model"], config["deployment"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if (m["conv_bias"] or not m["norm_topk_prob"] or not m["use_expert_bias"]
            or m["rope_parameters"]["rope_type"] != "default"
            or len(m["layer_types"]) != m["num_hidden_layers"]):
        raise ValueError("this family's reference has a convolution without a bias, normalised "
                         "top-k weights chosen by score + bias, plain rotary positions and a "
                         "layer type for every layer")
    held = dep["held_experts"]
    if held["count"] != m["num_experts"] or dep["experts_published"] < held["first"] + held["count"]:
        raise ValueError("num_experts counts the experts this chip holds, of experts_published")
    names = kind_names(m)
    kinds = tuple(
        LayerKind(name=name, mixer="conv" if name.startswith("conv") else "attention",
                  kv_heads=m["num_key_value_heads"],
                  rope_theta=float(m["rope_parameters"]["rope_theta"]),
                  ffn="dense" if name.endswith("dense") else "moe",
                  ffn_size=m["intermediate_size" if name.endswith("dense") else "moe_intermediate_size"])
        for name in dict.fromkeys(names))
    index = {k.name: i for i, k in enumerate(kinds)}
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        ffn_hidden_size=m["intermediate_size"], pos_embedding="rope", rope_interleaved=False,
        norm_type="rmsnorm", norm_eps=m["norm_eps"], activation="silu_glu", tie_embeddings=True,
        use_bias=False, qk_norm=True, conv_taps=m["conv_L_cache"],
        layer_kinds=kinds, layer_plan=tuple(index[n] for n in names),
        moe_num_experts=dep["experts_published"], moe_top_k=m["num_experts_per_tok"],
        moe_experts_held=(held["first"], held["count"]), moe_score="sigmoid",
        moe_routed_scale=float(m["routed_scaling_factor"]), moe_norm_eps=1e-6,
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))
