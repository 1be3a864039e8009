"""Operations and bytes GLM-4.7-Flash's serving tick NEEDS, from shapes and
from the program's own counts of what was routed, prefilled, read and expanded
(``cost_module`` of the ``*.latent`` metric files). Conventions as
``costs.py``, ``costs_mimo_v2.py`` and ``costs_qwen3_next.py``: a multiply-add
is 2 operations; an expert layer counts the assignments that reached the
experts HELD and reads the held experts that were HIT; a latent-attention
layer reads each live row's cached entries, one vector a token that is the
keys and the values of all heads, at the width the chip STORES it (the 576
numbers padded to 640: ``ops/transformer/kv_cache.latent_width``; the padding
is read because it is there, and counting it as needed work makes a share of
the roofline say what the kernel does with the bytes it cannot avoid).

Every cost function takes ``(config, cell, obs)`` and returns ``{"flops",
"bytes"}`` for ONE unit of what its metric is per (a tick, a chunk).
"""

BF16 = 2
LANES = 128


def shapes(config):
    m = config["model"]
    L, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    return dict(
        D=m["hidden_size"], H=m["num_attention_heads"], qr=m["q_lora_rank"], kr=m["kv_lora_rank"],
        dn=m["qk_nope_head_dim"], dr=m["qk_rope_head_dim"], dv=m["v_head_dim"],
        F=m["moe_intermediate_size"], Fd=m["intermediate_size"],
        Fs=m["n_shared_experts"] * m["moe_intermediate_size"],
        E=config["deployment"]["experts_published"], held=m["n_routed_experts"],
        k=m["num_experts_per_tok"], V=m["vocab_size"], L=L, n_dense=dense, n_moe=L - dense)


def stored_width(s):
    """Columns a cached token takes on the chip: latent + rotated key, to whole lane tiles."""
    return -(-(s["kr"] + s["dr"]) // LANES) * LANES


def mla_params(s):
    """W_DQ and its norm, W_UQ, W_DKV and the latent's norm, W_UKV, W_O."""
    return (s["D"] * s["qr"] + s["qr"] + s["qr"] * s["H"] * (s["dn"] + s["dr"])
            + s["D"] * (s["kr"] + s["dr"]) + s["kr"] + s["kr"] * s["H"] * (s["dn"] + s["dv"])
            + s["H"] * s["dv"] * s["D"])


def expert_params(s):
    return 3 * s["D"] * s["F"]


def always_read_params(s):
    """What every tick reads whatever was routed: every layer's latent
    attention and two norms, the dense layers' MLPs, an expert layer's
    router, selection bias and shared expert, and the output head (an
    embedding row a token is a gather, not a read of the table)."""
    return (s["L"] * (mla_params(s) + 2 * s["D"]) + s["n_dense"] * 3 * s["D"] * s["Fd"]
            + s["n_moe"] * (s["D"] * s["E"] + s["E"] + 3 * s["D"] * s["Fs"]) + s["D"] * s["V"])


def latent_bytes_per_key(s):
    """One cached entry of one row, over the layers, as stored."""
    return s["L"] * stored_width(s) * BF16


def decode_flops_per_key(s):
    """The rows' absorbed attention, a cached entry and layer: every head's
    score (latent + rotated key wide) and its share of the average (latent
    wide); TRUE operations at the model's heads, no padding counted."""
    return s["H"] * 2 * ((s["kr"] + s["dr"]) + s["kr"])


def expand_flops_per_token(s):
    """One cached entry through W_UKV into every head's key and value, a layer."""
    return 2 * s["kr"] * s["H"] * (s["dn"] + s["dv"])


def latent_bytes_tick(config, obs):
    """Latent bytes a tick's rows read: each live row to its own length."""
    return obs["mla_row_keys_per_tick"] * latent_bytes_per_key(shapes(config))


def decode_tick(config, cell, obs):
    """One tick's decode rows: the weights every tick reads, the held experts
    HIT (the program's count), the live rows' cached entries; 2 operations
    per weight a row USES (its share of the held experts: the assignments
    that reached them) and the rows' attention over the entries they read."""
    s = shapes(config)
    rows = obs["mean_live_rows"]
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    keys = obs["mla_row_keys_per_tick"]
    return {"flops": (2 * (always_read_params(s) * rows + assigned * expert_params(s))
                      + s["L"] * keys * decode_flops_per_key(s)),
            "bytes": ((always_read_params(s) + hit * expert_params(s)) * BF16
                      + latent_bytes_tick(config, obs))}


def mla_decode_tick(config, cell, obs):
    """The ``mla_decode`` calls of one tick (one a layer): each live row's
    cached entries read once for all heads, scores and averages at the
    model's 20 heads; the queries in and the averages out are a few KB a row."""
    s = shapes(config)
    keys = s["L"] * obs["mla_row_keys_per_tick"]
    return {"flops": keys * decode_flops_per_key(s), "bytes": keys * stored_width(s) * BF16}


def grouped_matmul_tick(config, cell, obs):
    """The grouped matmuls of one tick (three an expert layer): 2 operations a
    weight an assignment to a held expert uses; the experts hit read once, the
    assigned rows in and out (D in, F and F out; F in, D out)."""
    s = shapes(config)
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    rows = assigned * (2 * s["D"] + 3 * s["F"]) * BF16
    return {"flops": 2 * assigned * expert_params(s),
            "bytes": hit * expert_params(s) * BF16 + rows}


def flash_chunk(config, cell, obs):
    """The flash calls of one prefill chunk (one a layer, the expanded form):
    QK^T over dn + dr and PV over dv for the pairs the causal mask leaves
    (the program's count, real tokens only); q in and out once, every head's
    expanded keys and values once. The expansion that made them is NOT in
    here (``mla_expand_share_pct.latent`` has it)."""
    s = shapes(config)
    width = s["dn"] + s["dr"] + s["dv"]
    return {"flops": 2 * s["L"] * obs["chunk_pairs_full"] * s["H"] * width,
            "bytes": s["L"] * (obs["chunk_tokens"] + obs["chunk_keys_full"]) * s["H"] * width * BF16}


def expand_share_pct(config, obs):
    """Operations of the chunks' expansion (the entries the program expanded)
    over those of the chunk ticks' attention, expansion included."""
    s = shapes(config)
    expand = obs["mla_expand_tokens"] * expand_flops_per_token(s)
    attend = 2 * obs["chunk_pairs_full"] * s["H"] * (s["dn"] + s["dr"] + s["dv"])
    return 100.0 * expand / (expand + attend) if expand + attend else None
