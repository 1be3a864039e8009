"""Configuration file -> the program's model object, for Ouro's looped
language models (``model_type`` ``ouro``; ``"builder":
"benchmark.models_ouro"``; its reference is ``reference/ouro.py``): ONE kind
of layer (rotary multi-head attention, dense SwiGLU, a norm before and after
each sublayer) served as a layer plan of one run, walked ``total_ut_steps``
times over the same weights with the final norm after every pass. The keys
are the published ``config.json``'s own."""

import dataclasses
import math

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "total_ut_steps", "max_position_embeddings",
                  "vocab_size")

KIND = "block"   # the one kind's name: params["layers"]["block"]


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel

    if "loop_steps" not in {f.name for f in dataclasses.fields(TransformerConfig)}:
        # the parent of the PR that brought the family: the harness reports it and exits
        raise ImportError("this program's layer plan walks its layers once: no loop_steps")
    m = config["model"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if m["early_exit_threshold"] < 1:
        raise ValueError(
            f"early_exit_threshold {m['early_exit_threshold']}: under 1.0 a row leaves the loop at "
            "the first pass whose cumulative exit probability reaches it, and the passes it skips "
            "write no keys and values for the tokens that follow to attend. What stands in their "
            "place (the exit pass's, repeated? nothing?) is a rule the configuration does not "
            "give and this program does not invent: it runs every pass of every token, which is "
            "what the published 1.0 asks for")
    if (m["tie_word_embeddings"] or m["rope_scaling"] or m["hidden_act"] != "silu"
            or m["use_sliding_window"] or m["sliding_window"]
            or set(m["layer_types"]) != {"full_attention"}
            or len(m["layer_types"]) != m["num_hidden_layers"]):
        raise ValueError("this family's reference has a head of its own, plain rotary positions, "
                         "SiLU, no window, and full attention in every layer")
    kind = LayerKind(name=KIND, kv_heads=m["num_key_value_heads"], rope_theta=float(m["rope_theta"]))
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        head_size=m["head_dim"], ffn_hidden_size=m["intermediate_size"],
        pos_embedding="rope", rope_theta=float(m["rope_theta"]), norm_type="rmsnorm",
        norm_eps=m["rms_norm_eps"], norm_position="sandwich", activation="silu_glu",
        tie_embeddings=False, use_bias=False, layer_kinds=(kind,),
        layer_plan=(0,) * m["num_hidden_layers"], loop_steps=m["total_ut_steps"],
        init_in_model_dtype=bool(config.get("init_in_model_dtype", False)),
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


# Where random weights are placed so that the float32 reference and the bfloat16 program can be
# told apart from a fault; the two scales are the configuration file's ``seed_weights``. As
# drawn (every post-norm weight 1) each sublayer adds a unit vector to a unit residual, 384
# times at the published depth: a perturbation of one part in 2^9 (bfloat16's rounding) grows
# until the program's greedy token is the reference's top one at 5 % of positions, and the
# reference with bfloat16 OPERANDS alone (its residual float32) at 15 % (my chip run, PR 44,
# call 2): no tolerance tells rounding from a fault there. A trained model's sublayers move the
# residual a little at a time. ``post_scale`` on the two output norms (0.1: a pass, 96
# sublayers, moves the state by about its own length); the weights vary channel by channel (a
# fixed pattern in [0.5, 1.5)), so that the norm is not a multiple of what it norms, and W_o
# takes back init's 1/sqrt(2L), so that what the norm divides by is far from ``post_scale``.
# ``embed_scale`` on the embedding (50: 0.02 -> 1.0): pass 1 starts from a state as long as
# the one every later pass starts from (the final norm's output), and the token is not swamped
# by the first sublayer. The toy (2 layers x 3 passes) keeps both at 1: six layer-steps of
# unit vectors are what makes a dropped pass or float8 visible there.


def _pattern(shape):
    """A fixed pattern in [0.5, 1.5) over an array of ``shape``: the fractional
    part of the flat index times the golden ratio."""
    import jax.numpy as jnp

    index = jnp.arange(math.prod(shape), dtype=jnp.float32).reshape(shape)
    return 0.5 + jnp.mod(index * 0.6180339887, 1.0)


def sharpen(params, config, query_scale):
    """Seed weights rescaled so that the context decides the next token and
    bfloat16 rounding stays a rounding: the queries x ``query_scale`` (no norm
    on q or k undoes it), init's 1/sqrt(2L) on the attention output undone,
    the two output norms and the embedding placed as the comment above and
    the file's ``seed_weights`` say. The gate's bias stays 0."""
    seed = config["seed_weights"]
    block = params["layers"][KIND]
    attn = block["attn"]
    attn["wq"] = attn["wq"] * query_scale
    attn["wo"] = attn["wo"] * math.sqrt(2 * config["model"]["num_hidden_layers"])
    for name in ("ln1_post", "ln2_post"):
        scale = block[name]["scale"]
        block[name]["scale"] = (scale.astype("float32") * seed["post_scale"]
                                * _pattern(scale.shape)).astype(scale.dtype)
    params["embed"]["tok"] = params["embed"]["tok"] * seed["embed_scale"]
    return params
