"""Configuration file -> the program's model object."""


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
