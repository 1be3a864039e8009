"""Configuration file -> the program's model object, for Granite 4.0-H's
family (``model_type`` ``granitemoehybrid``; ``"builder":
"benchmark.models_granitemoehybrid"``; its reference is
``reference/granitemoehybrid.py``): Mamba-2 layers and, where
``layer_types`` says ``attention``, grouped-query attention with no positions
at all; every layer a top-k-then-softmax expert layer with an ungated shared
expert, of whose routed experts this chip holds a contiguous share; four
scalar multipliers. The keys are the published ``config.json``'s own;
``num_local_experts`` counts the experts HELD and ``deployment`` says which
and of how many."""

import dataclasses
import math

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "shared_intermediate_size",
                  "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                  "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
                  "mamba_n_groups", "mamba_expand", "num_local_experts", "num_experts_per_tok",
                  "max_position_embeddings", "vocab_size")

KINDS = ("mamba", "attention")      # layer_types' own words; a layer's kind is its entry's index


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    if "ssm_heads" not in {f.name for f in dataclasses.fields(TransformerConfig)}:
        # the parent of the PR that brought the family: the harness reports it and exits
        raise ImportError("this program's layer plan has no state-space mixer")
    m, dep = config["model"], config["deployment"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if (not m["tie_word_embeddings"] or m["attention_bias"] or m["mamba_proj_bias"]
            or not m["mamba_conv_bias"] or m["position_embedding_type"] != "nope"
            or m["rope_scaling"] or m["hidden_act"] != "silu" or m["mamba_n_groups"] != 1
            or m["normalization_function"] != "rmsnorm"
            or m["mamba_n_heads"] * m["mamba_d_head"] != m["mamba_expand"] * m["hidden_size"]
            or len(m["layer_types"]) != m["num_hidden_layers"]
            or set(m["layer_types"]) - set(KINDS)):
        raise ValueError("this family's reference has a tied head, no biases but the "
                         "convolution's, no positions, SiLU, RMSNorm, one group of B and C, an "
                         "inner width of heads x head width = expand x hidden, and a layer type "
                         "(mamba | attention) a layer")
    held = dep["held_experts"]
    if held["count"] != m["num_local_experts"] or dep["experts_published"] < held["first"] + held["count"]:
        raise ValueError("num_local_experts counts the experts this chip holds, of experts_published")
    if "attention" not in m["layer_types"]:
        raise ValueError("the depth holds no attention layer")
    ffn = dict(ffn="moe", ffn_size=m["intermediate_size"])
    kinds = (LayerKind(name="mamba", mixer="ssm", **ffn),
             LayerKind(name="attention", kv_heads=m["num_key_value_heads"], **ffn))
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        pos_embedding="none", norm_type="rmsnorm", norm_eps=m["rms_norm_eps"],
        activation="silu_glu", tie_embeddings=True, use_bias=False,
        layer_kinds=kinds, layer_plan=tuple(KINDS.index(t) for t in m["layer_types"]),
        ssm_heads=m["mamba_n_heads"], ssm_head_dim=m["mamba_d_head"], ssm_state=m["mamba_d_state"],
        ssm_groups=m["mamba_n_groups"], ssm_conv=m["mamba_d_conv"],
        embed_scale=float(m["embedding_multiplier"]), residual_scale=float(m["residual_multiplier"]),
        attn_scale=float(m["attention_multiplier"]), logit_scale=1.0 / float(m["logits_scaling"]),
        moe_num_experts=dep["experts_published"], moe_top_k=m["num_experts_per_tok"],
        moe_experts_held=(held["first"], held["count"]), moe_score="softmax",
        moe_shared_size=m["shared_intermediate_size"], moe_shared_gated=False,
        init_in_model_dtype=bool(config.get("init_in_model_dtype", False)),
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


# Where random weights are placed instead of the published initial values. The step: dt_bias
# at DT_AT + DT_SPREAD x its unit draw, so softplus(.) ~ exp(.) has its centre at 0.05 and
# lies between 0.007 and 0.37 at two spreads (published: log-uniform in [0.001, 0.1]). The
# decay rate A = exp(A_log): A_log at A_AT + A_SPREAD x its unit draw, A between 0.004 and 1.6
# at two spreads (published: uniform in [1, 16], where only a SMALL step makes a slow head, and
# a small step is also a faint input: with dt_bias at -5.0 +- 1.5 and A in 1-16 a slot reused
# WITHOUT its reset went through the full-size comparison, share within the margin 0.994 and
# 0.996, my chip runs, PR 42, call 6). A token's decay is exp(-dt A), the two draws together
# spread 1.8 in its log: a head at the centre forgets in ~250 tokens, one in eight keeps a
# third of a token 2,048 positions back (the shortest prompt), one in forty 9,000 (the mean
# prompt), and one two spreads above forgets in seven: some heads forget within tens of
# tokens and some remember across thousands, at steps large enough that the state, its reset
# and its hand-over from chunk to rows can all be SEEN by the comparison.
DT_AT, DT_SPREAD = -3.0, 1.0
A_AT, A_SPREAD = -2.5, 1.5
# The embedding times this. The head is TIED and x0 = 12 E[token]: at init's spread of 0.02 the
# last token's own logit (12 |E[t]|^2 = 12 D s^2) stands 12 sqrt(D) s = 15 spreads of the other
# logits (s sqrt(D)) above them at D = 4096, and the model repeats its last token whatever the
# context (my chip run, PR 42: 6 distinct tokens in 6 streams, the permuted prompt scored like
# the prompt). At s / 32 it stands half a spread above: a bias, not a verdict. A power of two:
# the bfloat16 mantissas stay as drawn.
EMBED_SCALE = 1.0 / 32


def sharpen(params, config, query_scale):
    """Seed weights rescaled so that the context decides the next token and
    each of the family's terms carries weight in the logits. Attention: the
    queries x ``query_scale`` on ``wq`` (no norm undoes it; with
    ``attention_multiplier`` 1/128 in 1/sqrt(128)'s place a unit-scale score
    has a spread of 0.09, so the scale is some 11 x the other families').
    Both mixers: init's 1/sqrt(2L) on the output projection undone. Mamba-2:
    ``dt_bias`` and ``A_log`` placed as the constants above say. The tied
    embedding x :data:`EMBED_SCALE`."""
    L = config["model"]["num_hidden_layers"]
    layers = params["layers"]
    attn, ssm = layers["attention"]["attn"], layers["mamba"]["ssm"]
    place = lambda at, spread, old: (at + spread * old.astype("float32")).astype(old.dtype)
    params["embed"]["tok"] = params["embed"]["tok"] * EMBED_SCALE
    attn["wq"] = attn["wq"] * query_scale
    attn["wo"] = attn["wo"] * math.sqrt(2 * L)
    ssm["wo"] = ssm["wo"] * math.sqrt(2 * L)
    ssm["dt_bias"] = place(DT_AT, DT_SPREAD, ssm["dt_bias"])
    ssm["a_log"] = place(A_AT, A_SPREAD, ssm["a_log"])
    return params
