"""Configuration file -> the program's model object: the builder of GPT-2's
family, and the default for a configuration file that names no ``builder``.
What a builder module gives is written down in ``benchmark/README.md``."""

import math

REQUIRED_SIZES = ("n_embd", "n_layer", "n_head", "n_positions", "vocab_size")


def sharpen_attention(params, n_layers, query_scale):
    """Seed weights of the program's ``TransformerModel`` rescaled so that
    the context decides the next token: queries x ``query_scale``, and init's
    1/sqrt(2L) on the attention output undone (PERF.md section 6)."""
    attn = params["layers"]["attn"]
    attn["wq"] = attn["wq"] * query_scale
    attn["wo"] = attn["wo"] * math.sqrt(2 * n_layers)
    return params


def sharpen(params, config, query_scale):
    return sharpen_attention(params, config["model"]["n_layer"], query_scale)


def build_model(config, *, max_seq_len, remat, attn_impl):
    """``TransformerModel`` for the configuration file ``config``: the repo's
    preset it names, checked against every size the file states."""
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

    m = config["model"]
    kw = dict(dtype=config["dtype"], remat=remat, attn_impl=attn_impl, max_seq_len=max_seq_len)
    if config.get("preset"):
        model = TransformerModel.from_preset(config["preset"], **kw)
    else:  # the tests' toy sizes: no preset of the program's has them
        model = TransformerModel(TransformerConfig(
            vocab_size=m["vocab_size"], hidden_size=m["n_embd"], num_layers=m["n_layer"],
            num_heads=m["n_head"], **kw))
    c = model.cfg
    stated = dict(hidden_size=m["n_embd"], num_layers=m["n_layer"], num_heads=m["n_head"],
                  vocab_size=m["vocab_size"])
    for key, value in stated.items():
        if getattr(c, key) != value:
            raise ValueError(f"preset {config['preset']} has {key}={getattr(c, key)}, "
                             f"the configuration file says {value}")
    if max_seq_len > m["n_positions"]:
        raise ValueError(f"{max_seq_len} positions asked of a model with {m['n_positions']}")
    return model
