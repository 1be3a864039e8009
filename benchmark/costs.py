"""Operations and bytes the algorithms NEED, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change how its
gain is counted. Conventions: a multiply-add is 2 operations; a causal model
needs only the unmasked half of its S x S attention; recomputation (remat)
is not required work and is not counted in ``train_flops_per_token``; an MoE
counts the experts a token is routed to, not all of them (no MoE
configuration exists yet: ``dense_params`` refuses one).

Every cost function takes ``(config, cell, obs)`` - the configuration file,
the cell file, the run's observations - and returns ``{"flops", "bytes"}``
for ONE unit of the thing its metric is per (a micro-step, a tick).
"""

BF16 = 2


def _dims(config):
    m = config["model"]
    if m.get("moe_num_experts"):
        raise NotImplementedError("MoE costs (active experts only) are not written yet")
    return m["n_layer"], m["n_embd"], m["n_head"], m["vocab_size"], m["n_positions"]


def dense_params(config):
    """(per-layer matmul weights, embedding table rows x width)."""
    L, D, H, V, P = _dims(config)
    return L * (4 * D * D + 2 * D * 4 * D), V * D


def total_params(config):
    L, D, H, V, P = _dims(config)
    per_layer = 4 * D * D + 4 * D + 8 * D * D + 5 * D + 4 * D  # attn w+b, mlp w+b, two norms
    return L * per_layer + V * D + P * D + 2 * D


def train_flops_per_token(config, seq):
    """Forward + backward operations one trained token requires at sequence
    length ``seq``: 6 x (matmul weights + tied output head) for the dense
    part, and for causal attention the QK^T and PV products over the unmasked
    half, forward (2 matmuls) and backward (4): 6 * 2 * seq/2 * D per layer."""
    L, D, H, V, P = _dims(config)
    layer_w, table = dense_params(config)
    return 6 * (layer_w + table) + L * 6 * 2 * (seq / 2) * D


def kv_bytes_per_position(config, kv_bytes=BF16):
    L, D, H, V, P = _dims(config)
    return 2 * L * D * kv_bytes


def weight_bytes(config, w_bytes=BF16):
    return total_params(config) * w_bytes


def _flash(batch, heads, seq, head, matmuls, reads, writes):
    """A causal flash call: ``matmuls`` S x S x head products over the unmasked
    half; ``reads``/``writes`` (B,H,S,head) bf16 operands streamed once."""
    flops = matmuls * 2 * batch * heads * (seq * seq / 2) * head
    return flops, (reads + writes) * batch * heads * seq * head * BF16


def flash_train_micro_step(config, cell, obs):
    """The Mosaic flash calls of one training micro-step, per chip: per layer
    a forward (QK^T, PV), the same forward again where the cell trains under
    remat (the call is made, so its work is needed by the program as
    compiled), dq (recompute QK^T; dP; dQ) and dkv (recompute QK^T; dP; dV;
    dK)."""
    L, D, H, V, P = _dims(config)
    rows, seq = cell["train"]["micro_batch_per_chip"], cell["train"]["seq"]
    forward = (2, 3, 1)  # matmuls, operand reads, writes
    calls = [forward] * (2 if cell["train"]["remat"] else 1) + [(3, 4, 1), (4, 4, 2)]
    flops = bytes_ = 0.0
    for matmuls, reads, writes in calls:
        f, b = _flash(rows, H, seq, D // H, matmuls, reads, writes)
        flops, bytes_ = flops + L * f, bytes_ + L * b
    return {"flops": flops, "bytes": bytes_}


def decode_tick(config, cell, obs):
    """One decode tick: every weight read once, the live rows' cached keys and
    values read once, 2 operations per weight and live row."""
    rows = obs["mean_live_rows"]
    layer_w, table = dense_params(config)
    return {"flops": 2 * (layer_w + table) * rows,
            "bytes": weight_bytes(config) + obs["mean_live_kv_tokens"] * kv_bytes_per_position(config)}
