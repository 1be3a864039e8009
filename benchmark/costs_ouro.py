"""Operations and bytes Ouro's looped serving tick NEEDS, from shapes and from
the runner's own counts of what was live and prefilled (``cost_module`` of the
``*.looped`` metric files). Conventions as ``costs.py`` and its siblings: a
multiply-add is 2 operations; the rows read the LIVE rows' keys and values,
not the read bucket's. The loop multiplies both terms: every pass reads every
layer's weights again (pass t + 1 needs the whole of pass t, and 4.93 GB of
layers stay on no chip between passes), so the weights count ``total_ut_steps``
times, which is the least any program of this model moves; and a token keeps
keys and values for passes x layers layer-steps.

Every cost function takes ``(config, cell, obs)`` and returns ``{"flops",
"bytes"}`` for ONE unit of what its metric is per (a tick, a chunk).
"""

BF16 = 2


def shapes(config):
    m = config["model"]
    return dict(D=m["hidden_size"], H=m["num_attention_heads"], hd=m["head_dim"],
                kv=m["num_key_value_heads"], F=m["intermediate_size"], V=m["vocab_size"],
                L=m["num_hidden_layers"], T=m["total_ut_steps"])


def layer_params(s):
    """wq, wo; wk, wv; gate, up, down; the four norms."""
    return 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["kv"] * s["hd"] + 3 * s["D"] * s["F"] + 4 * s["D"]


def layer_steps(s):
    """Layers a token goes through, and layer-caches it keeps: passes x layers."""
    return s["T"] * s["L"]


def tick_read_params(s):
    """What every tick reads: every layer's weights once a PASS, the final norm
    a pass, and the head (an embedding row a token is a gather, not a read of
    the table; the exit gate is not evaluated at threshold 1.0)."""
    return layer_steps(s) * layer_params(s) + s["T"] * s["D"] + s["D"] * s["V"]


def kv_bytes_per_position(s):
    """One cached position of one row, over every pass's layers."""
    return layer_steps(s) * s["kv"] * 2 * s["hd"] * BF16


def kv_bytes_tick(config, obs):
    """Keys and values the live rows' attention has to read in one tick."""
    return obs["mean_live_kv_tokens"] * kv_bytes_per_position(shapes(config))


def decode_tick(config, cell, obs):
    """One tick's decode rows: the weights a pass reads x the passes, the head,
    the live rows' keys and values over every layer-step; 2 operations per
    weight a row uses, and QK^T and PV over the live positions."""
    s = shapes(config)
    rows, live = obs["mean_live_rows"], obs["mean_live_kv_tokens"]
    attention = 2 * 2 * layer_steps(s) * s["H"] * s["hd"] * live
    return {"flops": 2 * tick_read_params(s) * rows + attention,
            "bytes": tick_read_params(s) * BF16 + kv_bytes_tick(config, obs)}


def flash_chunk(config, cell, obs):
    """The flash calls of one prefill chunk (one a layer-step: passes x
    layers): QK^T and PV over the pairs the causal mask leaves (the program's
    count, real tokens only); q in and out once, the keys and values a
    layer-step must read once a key-value head."""
    s = shapes(config)
    tokens, width = obs["chunk_tokens"], 2 * s["hd"]
    return {"flops": 2 * layer_steps(s) * obs["chunk_pairs_full"] * s["H"] * width,
            "bytes": layer_steps(s) * (tokens * s["H"] + obs["chunk_keys_full"] * s["kv"]) * width * BF16}
