"""Which line of the model emitted ``fusion.172``? — HLO instruction name
to ``jax.named_scope`` path, read off a compiled program's own text.

The profiler names a device op by its HLO instruction (``fusion.172``,
``copy.61``); the model marks its parts with ``jax.named_scope`` (the
names of :class:`Scope`: ``embed``, ``attn.kv_write``, ...), which XLA
carries into every instruction's ``metadata={op_name="jit(run)/while/body/.../
attn.kv_write/scatter"}``. :func:`scope_table` joins the two, so a ledger
breakdown's op names can be put down to a scope without guessing
(``tools/ds_hlo_scopes.py`` prints it for a benchmark cell's programs).

Stdlib-only: takes the text (or anything with ``as_text()``), imports
nothing of jax.
"""

import re
from typing import Dict, Optional

# `  ROOT %name = type opcode(...), ..., metadata={op_name="..." ...}`
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# `%fused_computation.3 (param_0: f32[..]) -> f32[..] {` / `ENTRY %main.1 (...) -> ... {`
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_JIT_PREFIX = re.compile(r"^(?:jit|pjit)\([^)]*\)/")


class Scope:
    """The names the program gives ``jax.named_scope``: the ONE list. The
    model (``models/transformer.py``), the inference ops, the tick's tail
    (``inference/decoding.py``) and the training engine import these for
    their sites; :func:`model_scope` reads them back off an ``op_name``."""

    EMBED = "embed"
    ATTN_QKV = "attn.qkv"
    ATTN_KV_WRITE = "attn.kv_write"
    ATTN_KV_READ = "attn.kv_read"
    ATTN_CORE = "attn.core"
    ATTN_OUT = "attn.out"
    MLP = "mlp"
    ATTN_FULL = "attn.full"      # a layer plan's full-attention core
    ATTN_WINDOW = "attn.window"  # ... and its sliding-window core
    MOE_ROUTE = "moe.route"      # router scores, top-k, the sort by expert
    MOE_EXPERTS = "moe.experts"  # gather, grouped matmuls over the held experts, combine
    MOE_EXPERTS_WHOLE = "moe.experts.whole"  # ... a training layer whose routing overflowed its bucket
    MOE_SHARED = "moe.shared"    # the shared expert (and its sigmoid gate, where it has one)
    MOE_LATENT = "moe.latent"    # the projections into and out of the latent the routed experts work in
    ATTN_GATE = "attn.gate"      # the sigmoid gate on a plan's attention output
    MIX_GDN = "mix.gdn"          # a gated-delta-rule mixer: projections, gates, output norm
    GDN_CONV = "gdn.conv"        # ... its causal depthwise convolution
    GDN_SCAN = "gdn.scan"        # ... one prefill chunk's scan (the gdn_chunk_fwd kernel)
    GDN_STEP = "gdn.step"        # ... the rows' one-token state step
    MIX_SSM = "mix.ssm"          # a state-space (Mamba-2) mixer: projections, the step size, the gated norm
    SSM_CONV = "ssm.conv"        # ... its causal depthwise convolution and bias
    SSM_SCAN = "ssm.scan"        # ... one prefill chunk's scan (the ssd_chunk_fwd kernel)
    SSM_STEP = "ssm.step"        # ... the rows' one-token state step (the ssd_step kernel)
    MIX_CONV = "mix.conv"        # a gated short-convolution mixer: the in-projection, the two gates, the out-projection
    CONV_SHORT = "conv.short"    # ... its causal depthwise taps
    MIX_MLA = "mix.mla"          # a latent-attention mixer: everything between its two norms
    MLA_Q = "mla.q"              # ... the queries: down, norm, up, the rotary part turned
    MLA_LATENT = "mla.latent"    # ... the latent and the shared rotated key of each token
    MLA_ABSORB = "mla.absorb"    # ... W_UK folded into the rows' queries, W_UV out of their output
    MLA_EXPAND = "mla.expand"    # ... a row's latents through W_UKV into heads, for a chunk (mla_expand)
    ATTN_LATENT = "attn.latent"  # ... attention over the latent pool (mla_decode, or a chunk's)
    NORM = "norm"
    NORM_POST = "norm.post"      # a sandwich-norm layer's second norms, on what a sublayer returns
    LOOP_NORM = "loop.norm"      # a looped plan's final norm at the end of a pass
    LM_HEAD = "lm_head"
    LOSS = "loss"
    SAMPLE = "sample"
    ACCEPT = "accept"
    OPTIMIZER_APPLY = "optimizer.apply"
    GRAD_ACCUMULATE = "grad_accumulate"


MODEL_SCOPES = frozenset(v for k, v in vars(Scope).items() if k.isupper())


def scope_table(compiled) -> Dict[str, str]:
    """``{instruction name: op_name path}`` for every instruction of
    ``compiled`` (a ``jax.stages.Compiled``, or its ``as_text()`` string)
    that carries one. A fusion takes its ROOT instruction's path (what the
    fusion computes, whatever the fuser named the wrapper); any other
    instruction that calls a computation and has no ``op_name`` of its own
    takes the callee's root's too."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    own: Dict[str, str] = {}         # instruction -> its own op_name
    calls: Dict[str, str] = {}       # instruction -> called computation
    roots: Dict[str, Optional[str]] = {}  # computation -> its root's op_name
    fusions = set()
    current = None
    for line in text.splitlines():
        comp = _COMPUTATION.match(line)
        if comp and "=" not in line.split("(", 1)[0]:
            current = comp.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        is_root, name = bool(m.group(1)), m.group(2)
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
            if " fusion(" in line:
                fusions.add(name)
        if is_root and current is not None:
            roots[current] = op.group(1) if op else None
    table = dict(own)
    for name, comp in calls.items():
        root = roots.get(comp)
        if root and (name in fusions or name not in table):
            table[name] = root
    return table


def scope_of(op_name: str) -> str:
    """The path without its ``jit(<fn>)/`` head: ``while/body/closed_call/
    attn.kv_write/scatter``."""
    return _JIT_PREFIX.sub("", op_name)


def model_scope(op_name: str) -> Optional[str]:
    """The innermost of the program's own scopes on the path (autodiff wraps
    them: ``transpose(jvp(attn.qkv))`` reads ``attn.qkv``); None where the
    instruction lies outside all of them (a scan's own stacking, the tick's
    packing, the engine's glue)."""
    for part in reversed(op_name.split("/")):
        inner = part
        while "(" in inner and inner.endswith(")"):  # jvp(x), transpose(jvp(x))
            inner = inner[inner.index("(") + 1:-1]
        if inner in MODEL_SCOPES:
            return inner
    return None
