"""Operations and bytes MiMo-V2's serving tick NEEDS, from shapes and from
the program's own counts of what was routed and prefilled (``cost_module`` of
the ``*.longdoc`` metric files). Conventions as ``costs.py``: a multiply-add
is 2 operations; an expert layer counts the assignments that reached the
experts HELD and reads the held experts that were HIT, not all of them; a
window layer reads and attends its window, not the row; a prefill chunk
attends the (query, key) pairs its mask leaves.

Every cost function takes ``(config, cell, obs)`` and returns ``{"flops",
"bytes"}`` for ONE unit of what its metric is per (a tick, a chunk).
"""

BF16 = 2


def shapes(config):
    m = config["model"]
    layers = list(zip(m["hybrid_layer_pattern"], m["moe_layer_freq"]))
    return dict(
        D=m["hidden_size"], H=m["num_attention_heads"], dk=m["head_dim"], dv=m["v_head_dim"],
        kv=(m["num_key_value_heads"], m["swa_num_key_value_heads"]), window=m["sliding_window"],
        F_dense=m["intermediate_size"], F_expert=m["moe_intermediate_size"],
        E=config["deployment"]["experts_published"], held=m["n_routed_experts"],
        k=m["num_experts_per_tok"], V=m["vocab_size"],
        n_full=sum(not w for w, _ in layers), n_window=sum(bool(w) for w, _ in layers),
        n_dense=sum(not e for _, e in layers), n_expert=sum(bool(e) for _, e in layers))


def attention_params(s, window):
    """wq, wk, wv, wo of one layer."""
    return s["D"] * (s["H"] * s["dk"] + s["kv"][window] * (s["dk"] + s["dv"])) + s["H"] * s["dv"] * s["D"]


def expert_params(s):
    return 3 * s["D"] * s["F_expert"]


def always_read_params(s):
    """What every tick reads whatever was routed: attention of every layer,
    the dense layers' MLPs, the routers, the output head (an embedding row a
    token is a gather, not a read of the table)."""
    return (s["n_full"] * attention_params(s, 0) + s["n_window"] * attention_params(s, 1)
            + s["n_dense"] * 3 * s["D"] * s["F_dense"] + s["n_expert"] * s["D"] * s["E"]
            + s["D"] * s["V"])


def kv_bytes_per_position(s, window):
    """One cached position of one row, over the layers of that reach."""
    return (s["n_window"] if window else s["n_full"]) * s["kv"][window] * (s["dk"] + s["dv"]) * BF16


def decode_tick(config, cell, obs):
    """One tick's decode rows: the weights every tick reads, the held experts
    HIT (the program's count), the live rows' full-layer keys and values and
    each live row's window; 2 operations per weight a row USES (its share of
    the held experts: the assignments that reached them)."""
    s = shapes(config)
    rows = obs["mean_live_rows"]
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    kv = (obs["mean_live_kv_tokens"] * kv_bytes_per_position(s, 0)
          + rows * s["window"] * kv_bytes_per_position(s, 1))
    return {"flops": 2 * (always_read_params(s) * rows + assigned * expert_params(s)),
            "bytes": (always_read_params(s) + hit * expert_params(s)) * BF16 + kv}


def grouped_matmul_tick(config, cell, obs):
    """The grouped matmuls of one tick (three an expert layer): 2 operations a
    weight an assignment to a held expert uses; the experts hit read once, the
    assigned rows in and out (D in, F and F out; F in, D out)."""
    s = shapes(config)
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    rows = assigned * (2 * s["D"] + 3 * s["F_expert"]) * BF16
    return {"flops": 2 * assigned * expert_params(s),
            "bytes": hit * expert_params(s) * BF16 + rows}


def flash_chunk(config, cell, obs):
    """The flash calls of one prefill chunk (one a layer): QK^T and PV over the
    pairs the mask leaves (the program's count, real tokens only); q in and
    out once, the keys and values a layer must read once a key-value head."""
    s = shapes(config)
    tokens = obs["chunk_tokens"]
    pairs = s["n_full"] * obs["chunk_pairs_full"] + s["n_window"] * obs["chunk_pairs_window"]
    keys = (s["n_full"] * obs["chunk_keys_full"] * s["kv"][0]
            + s["n_window"] * (tokens + s["window"]) * s["kv"][1])
    return {"flops": 2 * pairs * s["H"] * (s["dk"] + s["dv"]),
            "bytes": ((s["n_full"] + s["n_window"]) * tokens * s["H"] * (s["dk"] + s["dv"])
                      + keys * (s["dk"] + s["dv"])) * BF16}
