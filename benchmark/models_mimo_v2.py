"""Configuration file -> the program's model object, for MiMo-V2's family
(``"builder": "benchmark.models_mimo_v2"``; its reference is
``reference/mimo_v2.py``): window and full attention layers of different
shapes in one stack, a dense first layer, sigmoid top-k expert layers of
which this chip holds a contiguous share. The keys are the published
``config.json``'s own; ``n_routed_experts`` counts the experts HELD and
``deployment`` says which and of how many."""

from benchmark.models import sharpen_attention

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads",
                  "head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
                  "sliding_window", "max_position_embeddings", "vocab_size")

KINDS = ("dense_full", "dense_window", "moe_full", "moe_window")  # ffn x attention


def rotary_dim(m):
    """``partial_rotary_factor`` of the head width, rounded down to a whole pair."""
    return int(m["partial_rotary_factor"] * m["head_dim"]) // 2 * 2


def layer_kinds(m):
    """(names in use, plan): each layer's kind from ``hybrid_layer_pattern``
    (0 full, 1 window) and ``moe_layer_freq`` (0 dense, 1 experts)."""
    names = [("moe" if moe else "dense") + ("_window" if swa else "_full")
             for swa, moe in zip(m["hybrid_layer_pattern"], m["moe_layer_freq"])]
    used = [k for k in KINDS if k in names]
    return used, tuple(used.index(n) for n in names)


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    m, dep = config["model"], config["deployment"]
    L = m["num_hidden_layers"]
    if len(m["hybrid_layer_pattern"]) != L or len(m["moe_layer_freq"]) != L:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq name every layer")
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if m["tie_word_embeddings"] or m["attention_bias"] or m.get("n_shared_experts"):
        raise ValueError("this family's reference has an untied head, no biases, no shared expert")
    if (m["scoring_func"], m["topk_method"], m["norm_topk_prob"], m["n_group"]) != (
            "sigmoid", "noaux_tc", True, 1) or m.get("routed_scaling_factor") not in (None, 1.0):
        raise ValueError("this family's router: sigmoid scores, top-k by score + bias over one "
                         "group, normalised weights, no scaling factor")
    if (m["swa_head_dim"], m["swa_v_head_dim"], m["swa_num_attention_heads"]) != (
            m["head_dim"], m["v_head_dim"], m["num_attention_heads"]):
        raise ValueError("window and full layers share their query heads and head widths here")
    if dep["held_experts"]["count"] != m["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts this chip holds")
    used, plan = layer_kinds(m)

    def kind(name):
        window = name.endswith("_window")
        moe = name.startswith("moe")
        return LayerKind(
            name=name, kv_heads=m["swa_num_key_value_heads" if window else "num_key_value_heads"],
            window=m["sliding_window"] if window else 0,
            rope_theta=float(m["swa_rope_theta" if window else "rope_theta"]),
            sink=bool(m["add_swa_attention_sink_bias" if window
                        else "add_full_attention_sink_bias"]),
            ffn="moe" if moe else "dense",
            ffn_size=m["moe_intermediate_size" if moe else "intermediate_size"])

    held = dep["held_experts"]
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"], num_layers=L,
        num_heads=m["num_attention_heads"], head_size=m["head_dim"], v_head_size=m["v_head_dim"],
        ffn_hidden_size=m["intermediate_size"], rope_dim=rotary_dim(m), rope_interleaved=False,
        attn_value_scale=m["attention_value_scale"], pos_embedding="rope", norm_type="rmsnorm",
        norm_eps=m["layernorm_epsilon"], activation="silu_glu", tie_embeddings=False,
        use_bias=False, layer_kinds=tuple(kind(n) for n in used), layer_plan=plan,
        moe_num_experts=dep["experts_published"], moe_top_k=m["num_experts_per_tok"],
        moe_experts_held=(held["first"], held["count"]),
        init_in_model_dtype=bool(config.get("init_in_model_dtype", False)),
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


SINK_AT, SINK_SPREAD = 2.5, 0.5


def sharpen(params, config, query_scale):
    """Seed weights rescaled so that the context decides the next token, in
    every kind of layer: ``models.sharpen_attention`` on each kind's stack.
    A sink logit drawn at unit scale is nothing beside a window's 128 logits
    (they spread by ``sigma = hidden x std(wq) x std(wk)``, ~5 at query scale
    3, so their largest stands near 2.6 sigma), and a program that dropped
    the sink could not be told from a sound one (PERF.md section 6, PR 27b):
    a head's sink is placed at ``sigma x (SINK_AT + SINK_SPREAD x its unit
    draw)``, where it takes ~0.4 of the softmax on average, head by head
    between a few hundredths and nearly all."""
    import jax.numpy as jnp

    for kind in params["layers"].values():
        sharpen_attention({"layers": kind}, config["model"]["num_hidden_layers"], query_scale)
        attn = kind["attn"]
        if "sink" in attn:
            std = lambda w: jnp.std(w.astype(jnp.float32), axis=(1, 2))        # a layer
            sigma = config["model"]["hidden_size"] * std(attn["wq"]) * std(attn["wk"])
            unit = attn["sink"].astype(jnp.float32)
            attn["sink"] = (sigma[:, None] * (SINK_AT + SINK_SPREAD * unit)).astype(attn["sink"].dtype)
    return params
