"""Operations and bytes Nemotron 3 Super's serving tick NEEDS, from shapes and
from the program's own counts of what was routed, prefilled and stepped
(``cost_module`` of the ``*.latent_moe`` metric files). Conventions as
``costs.py`` and ``costs_granitemoehybrid.py``: a multiply-add is 2
operations; an expert layer counts the assignments that reached the experts
HELD and reads the held experts that were HIT (not the experts held: a program
that skips the experts no token chose must not read over 100 %); the attention
layer reads the live rows' keys and values; a Mamba-2 layer reads each stepped
row's state (float32) and convolution tail and writes them back, whatever the
row's length. A layer is ONE sublayer: the counts go by kind, ``M`` / ``*`` /
``E`` of ``hybrid_override_pattern``.

Every cost function takes ``(config, cell, obs)`` and returns ``{"flops",
"bytes"}`` for ONE unit of what its metric is per (a tick, a chunk).
"""

BF16, F32 = 2, 4
SUB_CHUNK = 256     # the program's scan works in sub-chunks of this (the published chunk_size is a schedule)


def shapes(config):
    m = config["model"]
    pattern = m["hybrid_override_pattern"]
    return dict(
        D=m["hidden_size"], H=m["num_attention_heads"], hd=m["head_dim"], kv=m["num_key_value_heads"],
        Hs=m["mamba_num_heads"], P=m["mamba_head_dim"], N=m["ssm_state_size"], G=m["n_groups"],
        K=m["conv_kernel"], F=m["moe_intermediate_size"], Fs=m["moe_shared_expert_intermediate_size"],
        Lt=m["moe_latent_size"], E=config["deployment"]["experts_published"],
        held=m["n_routed_experts"], k=m["num_experts_per_tok"], V=m["vocab_size"],
        L=len(pattern), n_ssm=pattern.count("M"), n_attn=pattern.count("*"), n_moe=pattern.count("E"))


def inner(s):
    return s["Hs"] * s["P"]


def conv_channels(s):
    return inner(s) + 2 * s["G"] * s["N"]


def ssm_params(s):
    """in_proj (z, x B C, dt), the convolution and its bias, A_log, D, dt_bias, the inner norm,
    out_proj, the layer's one norm."""
    C = conv_channels(s)
    return (s["D"] * (inner(s) + C + s["Hs"]) + C * s["K"] + C + 3 * s["Hs"] + inner(s)
            + inner(s) * s["D"] + s["D"])


def attention_params(s):
    """wq, wk, wv, wo, the layer's one norm."""
    return 2 * s["D"] * s["H"] * s["hd"] + 2 * s["D"] * s["kv"] * s["hd"] + s["D"]


def expert_params(s):
    """One routed expert: two matrices in the latent."""
    return 2 * s["Lt"] * s["F"]


def expert_layer_params(s):
    """What an expert layer holds beside its routed experts: the router and its selection bias,
    the latent's two projections, the shared expert's two matrices, the layer's one norm."""
    return s["D"] * s["E"] + s["E"] + 2 * s["D"] * s["Lt"] + 2 * s["D"] * s["Fs"] + s["D"]


def num_params(config, experts=None, vocab=None, pattern=None):
    """The model's parameters at ``experts`` routed experts a layer, ``vocab`` rows and the
    layers of ``pattern`` (None: the configuration's own)."""
    s = shapes(config if pattern is None else
               dict(config, model=dict(config["model"], hybrid_override_pattern=pattern)))
    experts = s["held"] if experts is None else experts
    vocab = s["V"] if vocab is None else vocab
    return (s["n_ssm"] * ssm_params(s) + s["n_attn"] * attention_params(s)
            + s["n_moe"] * (expert_layer_params(s) + experts * expert_params(s))
            + 2 * vocab * s["D"] + s["D"])


def always_read_params(s):
    """What every tick reads whatever was routed: every mixer, every expert layer's router,
    latent projections and shared expert, the norms, and the untied output head (an embedding
    row a token is a gather, not a read of the table)."""
    return (s["n_ssm"] * ssm_params(s) + s["n_attn"] * attention_params(s)
            + s["n_moe"] * expert_layer_params(s) + s["D"] * s["V"] + s["D"])


def kv_bytes_per_position(s):
    """One cached position of one row, over the attention layers."""
    return s["n_attn"] * s["kv"] * 2 * s["hd"] * BF16


def state_elements(s):
    """One row's state, one Mamba-2 layer."""
    return s["Hs"] * s["P"] * s["N"]


def state_bytes_per_row(s):
    """One row's state and convolution tail, over the Mamba-2 layers."""
    return s["n_ssm"] * (state_elements(s) * F32 + (s["K"] - 1) * conv_channels(s) * BF16)


def state_bytes_tick(config, obs):
    """State bytes a tick reads and writes back: the rows the program stepped."""
    return 2 * obs["ssm_step_rows_per_tick"] * state_bytes_per_row(shapes(config))


def decode_tick(config, cell, obs):
    """One tick's decode rows: the weights every tick reads, the held experts
    HIT (the program's count), the live rows' keys and values in the
    attention layer, the stepped rows' state read and written; 2 operations
    per weight a row USES (its share of the held experts: the assignments
    that reached them) and 5 a state element a stepped row (the decay, the
    outer product's multiply-add, the output's multiply-add)."""
    s = shapes(config)
    rows = obs["mean_live_rows"]
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    kv = obs["mean_live_kv_tokens"] * kv_bytes_per_position(s)
    state_flops = 5 * obs["ssm_step_rows_per_tick"] * s["n_ssm"] * state_elements(s)
    return {"flops": 2 * (always_read_params(s) * rows + assigned * expert_params(s)) + state_flops,
            "bytes": ((always_read_params(s) + hit * expert_params(s)) * BF16 + kv
                      + state_bytes_tick(config, obs))}


def grouped_matmul_tick(config, cell, obs):
    """The grouped matmuls of one tick (two an expert layer, 1,024 x 2,688 and
    back): 2 operations a weight an assignment to a held expert uses; the
    experts HIT read once, the assigned rows in and out (the latent in, F out;
    F in, the latent out)."""
    s = shapes(config)
    hit, assigned = obs["moe_experts_hit_per_tick"], obs["moe_held_assignments_per_tick"]
    rows = assigned * (2 * s["Lt"] + 2 * s["F"]) * BF16
    return {"flops": 2 * assigned * expert_params(s),
            "bytes": hit * expert_params(s) * BF16 + rows}


def flash_chunk(config, cell, obs):
    """The flash calls of one prefill chunk (one an attention layer): QK^T and
    PV over the pairs the causal mask leaves (the program's count, real
    tokens only); q in and out once, the keys and values a layer must read
    once a key-value head."""
    s = shapes(config)
    tokens, width = obs["chunk_tokens"], 2 * s["hd"]
    return {"flops": 2 * s["n_attn"] * obs["chunk_pairs_full"] * s["H"] * width,
            "bytes": s["n_attn"] * (tokens * s["H"] + obs["chunk_keys_full"] * s["kv"]) * width * BF16}


def ssd_chunk(config, cell, obs):
    """The ``ssd_chunk_fwd`` calls of one prefill chunk (one a Mamba-2 layer),
    for the real tokens the scan took (the program's count) in sub-chunks of
    Q = 256: a token and head, the product inside the sub-chunk ((C B^T . L)
    X: 2 Q P), the state's output (C S: 2 N P) and its update (B^T (w X): 2 N
    P); its float32 operands in (X a head; C, B^T and a row of C B^T once a
    GROUP, the cumulative decay a head), its output out, and the row's state
    in and out once a layer. The 0/1 product that picks a head's decay is the
    kernel's own device, not the scan's: not counted."""
    s = shapes(config)
    tokens, Q = obs["ssm_chunk_tokens"], SUB_CHUNK
    per_token = 2 * inner(s) + s["G"] * (2 * s["N"] + Q) + 2 * s["Hs"]
    return {"flops": s["n_ssm"] * s["Hs"] * tokens * (2 * Q * s["P"] + 4 * s["N"] * s["P"]),
            "bytes": s["n_ssm"] * (tokens * per_token + 2 * state_elements(s)) * F32}


def ssd_step_tick(config, cell, obs):
    """The ``ssd_step`` calls of one tick (one a Mamba-2 layer): each stepped
    row's state read once and written once (the kernel walks every row, and a
    parked row's state goes through it unchanged: the rows the program
    counted are the ones that had to move); 5 operations a state element."""
    s = shapes(config)
    states = obs["ssm_step_rows_per_tick"] * s["n_ssm"] * state_elements(s)
    return {"flops": 5 * states, "bytes": 2 * states * F32}
