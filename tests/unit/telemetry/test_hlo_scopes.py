"""Names on the device (ISSUE 24, part D): ``jax.named_scope`` paths reach
the compiled programs' text, and ``scope_table`` reads an instruction's
scope back. Toy programs compiled for the CPU: the names and the table, not
what a TPU's fuser does with them."""

import collections
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.telemetry.hlo_scopes import MODEL_SCOPES, Scope, model_scope, scope_of, scope_table

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

HLO = """HloModule jit_run, entry_computation_layout={()->f32[4]}

%fused_computation.3 (param_0: f32[4], param_1: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %param_1 = f32[4]{0} parameter(1)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_1), metadata={op_name="jit(run)/while/body/closed_call/mlp/mul" stack_frame_id=3}
  ROOT %dynamic-update-slice.9 = f32[4]{0} add(%multiply.1, %param_1), metadata={op_name="jit(run)/while/body/closed_call/attn.kv_write/scatter" stack_frame_id=4}
}

%region_0.2 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  ROOT %copy.7 = f32[4]{0} copy(%a), metadata={op_name="jit(run)/while/body/dynamic_update_slice"}
}

ENTRY %main.5 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.172 = f32[4]{0} fusion(%p, %p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(run)/while/body/closed_call/mlp/mul"}
  %call.1 = f32[4]{0} call(%fusion.172), to_apply=%region_0.2
  %copy.61 = f32[4]{0} copy(%call.1), metadata={op_name="jit(run)/lm_head/dot_general"}
  ROOT %add.2 = f32[4]{0} add(%copy.61, %p)
}
"""


def test_table_reads_names_and_a_fusion_takes_its_roots_scope():
    table = scope_table(HLO)
    # the fusion's own metadata says mlp; what it computes is its root: the cache write
    assert scope_of(table["fusion.172"]) == "while/body/closed_call/attn.kv_write/scatter"
    assert model_scope(table["fusion.172"]) == "attn.kv_write"
    assert model_scope(table["copy.61"]) == "lm_head"
    assert scope_of(table["copy.7"]) == "while/body/dynamic_update_slice"
    assert model_scope(table["copy.7"]) is None          # the scan's own stacking: no scope
    assert "add.2" not in table and "p" not in table     # no metadata, no entry
    assert scope_table(type("C", (), {"as_text": lambda self: HLO})()) == table


@pytest.mark.parametrize("path,want", [
    ("jit(micro_fn)/transpose(jvp(attn.qkv))/dot_general", "attn.qkv"),
    ("jit(micro_fn)/jvp(checkpoint)/attn.core/flash_fwd", "attn.core"),
    ("jit(apply_fn)/optimizer.apply/mul", "optimizer.apply"),
    ("jit(run)/while/body/closed_call/norm/jit(_var)/square", "norm"),
    ("jit(run)/concatenate", None),
])
def test_model_scope_sees_through_autodiff_wrappers(path, want):
    assert model_scope(path) == want


@pytest.fixture(scope="module")
def toy_tick_tables():
    comm.destroy()
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=64, dtype="float32")
    model = TransformerModel(cfg)
    eng = ContinuousBatchingEngine(model, params=model.init(jax.random.PRNGKey(0)),
                                   config={"dtype": "float32"}, max_slots=4, cache_len=64)
    pool = eng._pools[0]
    out = {}
    for chunk in (None, 16):
        fn = eng._tick_fn(pool, 32, chunk=chunk)
        out[chunk] = scope_table(fn.lower(*eng._tick_arg_structs(pool, chunk)).compile())
    return out


@pytest.mark.parametrize("chunk", [None, 16])
def test_tick_program_carries_the_models_scopes(toy_tick_tables, chunk):
    table = toy_tick_tables[chunk]
    seen = collections.Counter(model_scope(v) for v in table.values())
    for scope in ("attn.kv_write", "lm_head", "attn.kv_read", "attn.core", "attn.qkv",
                  "attn.out", "mlp", "norm", "embed", "sample", "accept"):
        assert seen[scope] > 0, (scope, seen)
    assert set(seen) - {None} <= MODEL_SCOPES
    # the write into the cache is a scatter / dynamic-update-slice under attn.kv_write
    writes = [scope_of(v) for v in table.values() if model_scope(v) == "attn.kv_write"]
    assert any(w.endswith(("scatter", "dynamic_update_slice")) for w in writes), writes


def test_training_programs_carry_loss_optimizer_and_accumulate_scopes():
    import deepspeed_tpu

    comm.destroy()
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                            max_seq_len=32, dtype="float32")
    engine = deepspeed_tpu.initialize(model=TransformerModel(cfg), config={
        "train_micro_batch_size_per_gpu": 8, "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})[0]
    sds = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    batch = {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    micro = scope_table(engine._micro_fn.lower(
        sds(engine.params), sds(engine.grad_acc), batch, sds(engine._next_rng()),
        scalar, scalar).compile())
    apply = scope_table(engine._apply_fn.lower(
        sds(engine.params), sds(engine.master_params), sds(engine.opt_state),
        sds(engine.grad_acc), sds(engine.scale_state), scalar).compile())
    micro_scopes = {model_scope(v) for v in micro.values()}
    assert {"loss", "lm_head", "grad_accumulate", "attn.qkv", "mlp", "embed"} <= micro_scopes
    assert "optimizer.apply" in {model_scope(v) for v in apply.values()}


def test_every_scope_site_takes_its_name_from_the_one_list():
    """``MODEL_SCOPES`` is ``Scope``'s constants, and the program names a scope
    by constant: a string literal at a ``named_scope`` site would be a name
    ``model_scope`` cannot read back (``checkpoint_layer`` predates the list
    and is remat's wrapper, not a part of the model)."""
    assert MODEL_SCOPES == {v for k, v in vars(Scope).items() if k.isupper()}
    literal = re.compile(r"named_scope\(\s*[\"']([^\"']+)")
    found = set()
    for path in glob.glob(os.path.join(REPO, "deepspeed_tpu", "**", "*.py"), recursive=True):
        with open(path) as fh:
            found |= set(literal.findall(fh.read()))
    assert found == {"checkpoint_layer"}, found


def test_pallas_calls_are_named_in_interpret_mode():
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)
    loss = lambda q, k, v: flash_attention(q, k, v, causal=True).sum()
    table = scope_table(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile())
    paths = " ".join(table.values())
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in paths, name


def test_cli_prints_the_scopes_of_a_toy_cells_ops(tmp_path):
    toy = os.path.join(REPO, "tests", "benchmark", "toy", "MANIFEST.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "ds_hlo_scopes.py"), "--manifest", toy,
         "--cell", "toy-chat", "--summary", "--json", "--ops", "fusion no_such_op.1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    report = json.loads(proc.stdout[proc.stdout.index('{\n "cell"'):])  # after the engine's log lines
    assert len(report["variants"]) == 2 and report["ops"] == {"fusion no_such_op.1": {}}
    for counts in report["summary"].values():
        assert counts["attn.kv_write"] > 0 and counts["lm_head"] > 0
