"""Configuration file -> the program's model object, for decoders of the
RoPE / RMSNorm / SwiGLU / grouped-query kind (``"builder":
"benchmark.models_rope_glu"``; their reference is ``reference/rope_glu.py``).
The keys are the published ``config.json``'s own."""

from benchmark.models import sharpen_attention

REQUIRED_SIZES = ("hidden_size", "intermediate_size", "num_hidden_layers", "num_attention_heads",
                  "num_key_value_heads", "max_position_embeddings", "vocab_size")


def build_model(config, *, max_seq_len, remat, attn_impl):
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

    m = config["model"]
    if max_seq_len > m["max_position_embeddings"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with "
                         f"{m['max_position_embeddings']}")
    if m.get("tie_word_embeddings"):
        raise ValueError("this family's reference has an output head of its own")
    return TransformerModel(TransformerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], ffn_hidden_size=m["intermediate_size"],
        pos_embedding="rope", rope_theta=m["rope_theta"], norm_type="rmsnorm",
        norm_eps=m["rms_norm_eps"], activation="silu_glu", tie_embeddings=False, use_bias=False,
        dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len))


def sharpen(params, config, query_scale):
    return sharpen_attention(params, config["model"]["num_hidden_layers"], query_scale)
