"""Configuration file -> the program's model object, for Nemotron 3 Super's
family (``model_type`` ``nemotron_h``; ``"builder":
"benchmark.models_nemotron_h"``; its reference is ``reference/nemotron_h.py``):
a layer is ONE sublayer, by ``hybrid_override_pattern`` a Mamba-2 mixer with
``n_groups`` groups of ``B`` and ``C`` (``M``), grouped-query attention with no
positions at all (``*``) or an expert layer (``E``) whose routed experts work
in a latent of ``moe_latent_size`` (sigmoid scores, selection bias, top-k of
ALL the published experts, the normalised weights times
``routed_scaling_factor``; un-gated squared-ReLU experts and shared expert), of
whose routed experts this chip holds a contiguous share; an untied head. The
keys are the published ``config.json``'s own; ``n_routed_experts`` counts the
experts HELD and ``deployment`` says which and of how many.

The three kinds of the plan are named as the reference's ``KINDS`` names them
(``mamba`` / ``attention`` / ``experts``): the parameter tree is the model's
own and both sides read it."""

import dataclasses
import math

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "moe_intermediate_size", "moe_latent_size",
                  "moe_shared_expert_intermediate_size", "num_hidden_layers", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim",
                  "ssm_state_size", "n_groups", "conv_kernel", "expand", "n_routed_experts",
                  "num_experts_per_tok", "max_position_embeddings", "vocab_size")

KINDS = "M*E"      # hybrid_override_pattern's own letters; a layer's kind is its letter's index


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    if "ffn_latent" not in {f.name for f in dataclasses.fields(LayerKind)}:
        # the parent of the PR that brought the family: the harness reports it and exits
        raise ImportError("this program's layer plan has no layer of one sublayer and no "
                          "expert layer in a latent")
    m, dep = config["model"], config["deployment"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    pattern = m["hybrid_override_pattern"]
    if (m["tie_word_embeddings"] or m["attention_bias"] or m["mamba_proj_bias"] or m["mlp_bias"]
            or m["use_bias"] or not m["use_conv_bias"] or m["mlp_hidden_act"] != "relu2"
            or m["mamba_hidden_act"] != "silu" or m["n_group"] != 1 or m["topk_group"] != 1
            or not m["norm_topk_prob"] or m["n_shared_experts"] != 1 or m["sliding_window"]
            or m["mamba_num_heads"] * m["mamba_head_dim"] != m["expand"] * m["hidden_size"]
            or m["intermediate_size"] != m["moe_intermediate_size"]
            or len(pattern) != m["num_hidden_layers"] or set(pattern) - set(KINDS)
            or m["num_nextn_predict_layers"]):
        raise ValueError("this family's reference has an untied head, no biases but the "
                         "convolution's, squared-ReLU experts, SiLU in the mixer, one routing "
                         "group, normalised top-k weights, one shared expert, no window, an inner "
                         "width of heads x head width = expand x hidden, a letter (M | * | E) a "
                         "layer and no multi-token-prediction module")
    held = dep["held_experts"]
    if held["count"] != m["n_routed_experts"] or dep["experts_published"] < held["first"] + held["count"]:
        raise ValueError("n_routed_experts counts the experts this chip holds, of experts_published")
    if "*" not in pattern:
        raise ValueError("the depth holds no attention layer")
    kinds = (LayerKind(name="mamba", mixer="ssm", ffn="none"),
             LayerKind(name="attention", kv_heads=m["num_key_value_heads"], ffn="none"),
             LayerKind(name="experts", mixer="none", ffn="moe", ffn_size=m["moe_intermediate_size"],
                       ffn_latent=m["moe_latent_size"]))
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        head_size=m["head_dim"], pos_embedding="none", norm_type="rmsnorm",
        norm_eps=m["layer_norm_epsilon"], activation="relu2", tie_embeddings=False, use_bias=False,
        layer_kinds=kinds, layer_plan=tuple(KINDS.index(c) for c in pattern),
        ssm_heads=m["mamba_num_heads"], ssm_head_dim=m["mamba_head_dim"],
        ssm_state=m["ssm_state_size"], ssm_groups=m["n_groups"], ssm_conv=m["conv_kernel"],
        moe_num_experts=dep["experts_published"], moe_top_k=m["num_experts_per_tok"],
        moe_experts_held=(held["first"], held["count"]), moe_score="sigmoid",
        moe_routed_scale=float(m["routed_scaling_factor"]),
        moe_norm_eps=float(config["assumed_values"]["moe_norm_eps"]),
        moe_shared_size=m["moe_shared_expert_intermediate_size"], moe_shared_gated=False,
        init_in_model_dtype=bool(config.get("init_in_model_dtype", False)),
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


# Where random weights are placed instead of the published initial values, as
# ``models_granitemoehybrid.py`` places them and for its reasons (PR 42: with dt_bias at -5.0
# +- 1.5 and A in 1-16 a slot reused WITHOUT its reset went through the full-size comparison).
# The step: dt_bias at DT_AT + DT_SPREAD x its unit draw, so softplus(.) has its centre at 0.05
# (published: log-uniform in [time_step_min 0.001, time_step_max 0.1], floor 0.0001). The decay
# rate A = exp(A_log): A_log at A_AT + A_SPREAD x its unit draw, A between 0.004 and 1.6 at two
# spreads (published: uniform in [1, 16]). A head at the centre forgets in ~250 tokens, one in
# eight keeps a third of a token 2,048 positions back (this cell's LONGEST prompt), the fastest
# forget in seven: the state, its reset and its hand-over from chunk to rows can be seen.
DT_AT, DT_SPREAD = -3.0, 1.0
A_AT, A_SPREAD = -2.5, 1.5


def sharpen(params, config, query_scale):
    """Seed weights rescaled so that the context decides the next token and
    each of the family's terms carries weight in the logits. Attention: the
    queries x ``query_scale`` on ``wq`` (no norm undoes it). Every sublayer's
    output projection (both mixers', each routed expert's and the shared
    expert's second matrix): init's 1/sqrt(2L) undone, so that a sublayer adds
    about a unit to the residual stream and the routed experts' part, 5 x
    5.5 held of 22 chosen, stands at about half of its layer's (left at init's
    scale the expert layers were a tenth of the stream, and a fault in them a
    tenth of that). Mamba-2: ``dt_bias`` and ``A_log`` placed as the constants
    above say. The head is untied and the embedding has no multiplier: both
    stay as drawn."""
    L = config["model"]["num_hidden_layers"]
    layers = params["layers"]
    attn, ssm, mlp = layers["attention"]["attn"], layers["mamba"]["ssm"], layers["experts"]["mlp"]
    place = lambda at, spread, old: (at + spread * old.astype("float32")).astype(old.dtype)
    attn["wq"] = attn["wq"] * query_scale
    attn["wo"] = attn["wo"] * math.sqrt(2 * L)
    ssm["wo"] = ssm["wo"] * math.sqrt(2 * L)
    mlp["wo"] = mlp["wo"] * math.sqrt(2 * L)
    mlp["shared_wo"] = mlp["shared_wo"] * math.sqrt(2 * L)
    ssm["dt_bias"] = place(DT_AT, DT_SPREAD, ssm["dt_bias"])
    ssm["a_log"] = place(A_AT, A_SPREAD, ssm["a_log"])
    return params
