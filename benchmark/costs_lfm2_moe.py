"""Operations and bytes LFM2-24B-A2B's training step NEEDS, from shapes
alone (``cost_module`` of the ``*.moe_train`` metric files). Conventions as
``costs.py``: a multiply-add is 2 operations; a causal model needs only the
unmasked half of its S x S attention; recomputation (remat) is not required
work and is not counted in ``train_flops_per_token``. An expert layer counts
the EXPECTED assignments to the experts held here (``num_experts_per_tok`` x
held / published a token, what an even routing gives), not the run's own
count, so that the yardstick reads the same work whatever implements it and
however a seed's router happens to fall.

``train_flops_per_token`` takes ``(config, seq)``; every other cost function
takes ``(config, cell, obs)`` and returns ``{"flops", "bytes"}`` for ONE
micro-step.
"""

BF16 = 2


def shapes(config):
    m = config["model"]
    return dict(
        D=m["hidden_size"], H=m["num_attention_heads"], Hkv=m["num_key_value_heads"],
        hd=m["hidden_size"] // m["num_attention_heads"], Fd=m["intermediate_size"],
        F=m["moe_intermediate_size"], E=config["deployment"]["experts_published"],
        held=m["num_experts"], k=m["num_experts_per_tok"], V=m["vocab_size"],
        n_dense=m["num_dense_layers"], types=tuple(m["layer_types"]))


def held_assignments_per_token(s):
    """Expected assignments a token makes to the experts held here, a layer."""
    return s["k"] * s["held"] / s["E"]


def expert_params(s):
    return 3 * s["D"] * s["F"]


def forward_flops_per_token(config, seq):
    """Forward operations one token requires at sequence length ``seq``: a
    convolution mixer's two products (D -> 3D, D -> D; the taps are 2 K D and
    left out), an attention mixer's four projections and its QK^T and PV over
    the unmasked half, a dense SwiGLU or the router over the published experts
    and the held experts' expected share, and the tied head over the
    vocabulary kept."""
    s = shapes(config)
    D, kv = s["D"], s["Hkv"] * s["hd"]
    total = 2 * D * s["V"]
    for i, kind in enumerate(s["types"]):
        if kind == "conv":
            total += 2 * (3 * D * D + D * D)
        else:
            total += 2 * (2 * D * D + 2 * D * kv) + 2 * 2 * s["H"] * s["hd"] * (seq / 2)
        if i < s["n_dense"]:
            total += 2 * 3 * D * s["Fd"]
        else:
            total += 2 * D * s["E"] + held_assignments_per_token(s) * 2 * expert_params(s)
    return total


def train_flops_per_token(config, seq):
    """Forward + backward: every product of the forward has two gradients."""
    return 3 * forward_flops_per_token(config, seq)


def _flash(rows, seq, s, matmuls, q_sized, kv_sized):
    """A causal grouped-query flash call: ``matmuls`` S x S x head products a
    query head over the unmasked half; ``q_sized`` arrays of every query head
    and ``kv_sized`` of every key-value head streamed once, bfloat16."""
    flops = matmuls * 2 * rows * s["H"] * (seq * seq / 2) * s["hd"]
    return flops, rows * seq * s["hd"] * BF16 * (q_sized * s["H"] + kv_sized * s["Hkv"])


def flash_train_micro_step(config, cell, obs):
    """The Mosaic flash calls of one training micro-step: per attention layer
    ONE forward (QK^T, PV; q in, o out, k and v in), dq (QK^T again, dP, dQ;
    q and do in, dq out, k and v in) and dkv (QK^T again, dP, dV, dK; q and do
    in, k and v in, dk and dv out at the key-value heads: the kernel writes
    them a query head and sums, which no algorithm needs). One forward, not two, under remat: the
    layer checkpoint's policy keeps the kernel's output and log-sum-exp
    (``flash_saveable``), and the trace of the cell shows as many ``flash_fwd``
    calls as ``flash_bwd_dq`` calls (PERF.md section 6, PR 48)."""
    s = shapes(config)
    rows, seq = cell["train"]["micro_batch_per_chip"], cell["train"]["seq"]
    layers = sum(t != "conv" for t in s["types"])
    flops = bytes_ = 0.0
    for matmuls, q_sized, kv_sized in ((2, 2, 2), (3, 3, 2), (4, 2, 4)):
        f, b = _flash(rows, seq, s, matmuls, q_sized, kv_sized)
        flops, bytes_ = flops + layers * f, bytes_ + layers * b
    return {"flops": flops, "bytes": bytes_}


def ragged_products_micro_step(config, cell, obs):
    """The ragged products of one training micro-step (the chip's compiler
    runs each as a Mosaic call of its own, ``ragged-dot-*``): an expert layer
    three forward (gate, up, down), the same three again where the cell
    trains under remat (the layer checkpoint reruns them: the calls are made),
    and two gradients each (by the rows, by the weights) - twelve a layer,
    every one 2 D F operations an assignment over the EXPECTED assignments to
    the experts held, the held experts' weights (or their gradient) crossing
    HBM once a product and the assigned rows once in and once out."""
    s = shapes(config)
    t = cell["train"]
    assigned = t["micro_batch_per_chip"] * t["seq"] * held_assignments_per_token(s)
    layers = len(s["types"]) - s["n_dense"]
    products = layers * (3 * (2 if t["remat"] else 1) + 6)
    weights = s["held"] * s["D"] * s["F"] * BF16
    rows = assigned * (s["D"] + s["F"]) * BF16
    return {"flops": products * 2 * assigned * s["D"] * s["F"], "bytes": products * (weights + rows)}
