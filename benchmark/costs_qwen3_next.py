"""Operations and bytes Qwen3-Next's serving tick NEEDS, from shapes and from
the program's own counts of what was routed, prefilled and stepped
(``cost_module`` of the ``*.hybrid`` metric files). Conventions as
``costs.py`` and ``costs_mimo_v2.py``: a multiply-add is 2 operations; an
expert layer counts the assignments that reached the experts HELD and reads
the held experts that were HIT; a full-attention layer reads the live rows'
keys and values; a Gated DeltaNet layer reads each stepped row's recurrent
state (float32) and convolution tail and writes them back, whatever the
row's length.

Every cost function takes ``(config, cell, obs)`` and returns ``{"flops",
"bytes"}`` for ONE unit of what its metric is per (a tick, a chunk).
"""

BF16, F32 = 2, 4
# tokens of one sub-chunk of the chunked delta rule, as the kernel walks them
# (``ops/pallas/gated_delta.SUB``; the published implementations' chunk too)
SUB = 64


def shapes(config):
    m = config["model"]
    L = m["num_hidden_layers"]
    n_full = sum((i + 1) % m["full_attention_interval"] == 0 for i in range(L))
    return dict(
        D=m["hidden_size"], H=m["num_attention_heads"], hd=m["head_dim"],
        kv=m["num_key_value_heads"], Hk=m["linear_num_key_heads"],
        Hv=m["linear_num_value_heads"], dk=m["linear_key_head_dim"],
        dv=m["linear_value_head_dim"], K=m["linear_conv_kernel_dim"],
        F=m["moe_intermediate_size"], Fs=m["shared_expert_intermediate_size"],
        E=config["deployment"]["experts_published"], held=m["num_experts"],
        k=m["num_experts_per_tok"], V=m["vocab_size"], L=L, n_full=n_full, n_gdn=L - n_full)


def conv_channels(s):
    return 2 * s["Hk"] * s["dk"] + s["Hv"] * s["dv"]


def gdn_params(s):
    """in_proj (q, k, v, z and b, a), the convolution, A_log, dt_bias, the inner norm, out_proj."""
    C, inner = conv_channels(s), s["Hv"] * s["dv"]
    return s["D"] * (C + inner + 2 * s["Hv"]) + C * s["K"] + 2 * s["Hv"] + s["dv"] + inner * s["D"]


def attention_params(s):
    """wq and its gate, wk, wv, wo, the two head norms."""
    return (s["D"] * 2 * s["H"] * s["hd"] + 2 * s["D"] * s["kv"] * s["hd"]
            + s["H"] * s["hd"] * s["D"] + 2 * s["hd"])


def expert_params(s):
    return 3 * s["D"] * s["F"]


def always_read_params(s):
    """What every tick reads whatever was routed: every layer's mixer, router,
    shared expert with its gate and two norms, and the output head (an
    embedding row a token is a gather, not a read of the table)."""
    layer = s["D"] * s["E"] + 3 * s["D"] * s["Fs"] + s["D"] + 2 * s["D"]
    return (s["n_gdn"] * gdn_params(s) + s["n_full"] * attention_params(s) + s["L"] * layer
            + s["D"] * s["V"])


def kv_bytes_per_position(s):
    """One cached position of one row, over the full-attention layers."""
    return s["n_full"] * s["kv"] * 2 * s["hd"] * BF16


def state_bytes_per_row(s):
    """One row's recurrent state and convolution tail, over the Gated DeltaNet layers."""
    return s["n_gdn"] * (s["Hv"] * s["dk"] * s["dv"] * F32 + (s["K"] - 1) * conv_channels(s) * BF16)


def state_bytes_tick(config, obs):
    """State bytes a tick reads and writes back: the rows the program stepped."""
    return 2 * obs["gdn_step_rows_per_tick"] * state_bytes_per_row(shapes(config))


def decode_tick(config, cell, obs):
    """One tick's decode rows: the weights every tick reads, the held experts
    HIT (the program's count), the live rows' keys and values in the
    full-attention layers, the stepped rows' state read and written; 2
    operations per weight a row USES (its share of the held experts: the
    assignments that reached them) and 6 a state element a stepped row
    (S'^T k, S'^T q and the update)."""
    s = shapes(config)
    rows = obs["mean_live_rows"]
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    kv = obs["mean_live_kv_tokens"] * kv_bytes_per_position(s)
    state_flops = 6 * obs["gdn_step_rows_per_tick"] * s["n_gdn"] * s["Hv"] * s["dk"] * s["dv"]
    return {"flops": 2 * (always_read_params(s) * rows + assigned * expert_params(s)) + state_flops,
            "bytes": ((always_read_params(s) + hit * expert_params(s)) * BF16 + kv
                      + state_bytes_tick(config, obs))}


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
    """The flash calls of one prefill chunk (one a full-attention layer): QK^T
    and PV over the pairs the causal mask leaves (the program's count, real
    tokens only); q in and out once, the keys and values a layer must read
    once a key-value head."""
    s = shapes(config)
    tokens, width = obs["chunk_tokens"], 2 * s["hd"]
    return {"flops": 2 * s["n_full"] * obs["chunk_pairs_full"] * s["H"] * width,
            "bytes": s["n_full"] * (tokens * s["H"] + obs["chunk_keys_full"] * s["kv"]) * width * BF16}


def gdn_chunk(config, cell, obs):
    """The ``gdn_chunk_fwd`` calls of one prefill chunk (one a Gated DeltaNet
    layer), for the real tokens the scan took (the program's count): a token
    and value head, three products against the state (Wm S, (Q e^c) S, (K
    e^(c_last - c))^T V': 2 dk dv each) and one inside the sub-chunk (P V': 2
    SUB dv); its float32 operands in (Wm, Q e^c, K e^.. of dk, U of dv, a row
    of P), its output out, and the head's state in and out once a layer."""
    s = shapes(config)
    heads = s["n_gdn"] * s["Hv"]
    tokens = obs["gdn_chunk_tokens"]
    per_token = 3 * s["dk"] + 2 * s["dv"] + SUB
    return {"flops": heads * tokens * (6 * s["dk"] * s["dv"] + 2 * SUB * s["dv"]),
            "bytes": heads * (tokens * per_token + 2 * s["dk"] * s["dv"]) * F32}


def gdn_step_tick(config, cell, obs):
    """The ``gdn_step`` calls of one tick (one a Gated DeltaNet layer): each
    stepped row's recurrent state read once and written once (the kernel
    walks every row, and a parked row's state goes through it unchanged: the
    rows the program counted are the ones that had to move); 6 operations a
    state element (S^T k and S^T q in one product, the outer-product update)."""
    s = shapes(config)
    states = obs["gdn_step_rows_per_tick"] * s["n_gdn"] * s["Hv"] * s["dk"] * s["dv"]
    return {"flops": 6 * states, "bytes": 2 * states * F32}
