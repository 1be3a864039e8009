"""GLM-4.7-Flash's family (latent attention whose one cached vector a token is
the keys and the values of every head, absorbed for the decoding rows and
expanded for a prefill chunk; sigmoid top-k routing with a selection bias and a
routed scaling factor, an ungated shared expert, every expert held): its plain
reference, which has the EXPANDED form only, against the program's model in
float32 — the whole forward, each kind of layer, and the serving tick's logits
through prefill chunks of several widths and rows at several depths in both
chunk forms —, each piece of its mathematics failing when left out, faults
planted in the program failing, the whole layer equal to the uncut reference
layer, its cost functions against hand counts, and its configuration file
against the published ``config.json``. Its toy cell
(``toy/cells/toy-glm-longdoc.json``) runs end to end, traced and untraced, with
every other toy cell (``test_bench_runners_cpu.py`` finds it by its file).
Everything of this family is a file of its own."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_glm4_moe_lite, harness, models_glm4_moe_lite
from benchmark.reference import glm4_moe_lite
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache

sys.path.insert(0, os.path.join(bench_toy.ROOT, "tools"))
import glm_cell_variant  # noqa: E402

TOY = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-glm4-moe-lite.json")), dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs", "glm-4.7-flash.json"))
ARCH = glm4_moe_lite.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]
LENGTH = 128


@pytest.fixture(scope="module")
def model():
    return models_glm4_moe_lite.build_model(TOY, max_seq_len=LENGTH, remat=False,
                                            attn_impl="pallas")


@pytest.fixture(scope="module")
def params(model):
    return models_glm4_moe_lite.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 3.0)


def logits(params, tokens, arch=ARCH, **kw):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(glm4_moe_lite.logits_at(params, tokens, at, arch, **kw))


def test_the_configuration_names_its_reference_and_builder(model):
    assert compare.reference_of(TOY) is glm4_moe_lite
    assert compare.builder_of(TOY) is models_glm4_moe_lite
    assert all(isinstance(TOY["model"][k], int) for k in models_glm4_moe_lite.REQUIRED_SIZES)
    assert (ARCH.kv_rank, ARCH.nope, ARCH.rope, ARCH.v_dim) == (40, 16, 8, 32)
    assert (ARCH.held_first, ARCH.held_count, ARCH.top_k, ARCH.routed_scale) == (0, 8, 2, 1.8)
    cfg = model.cfg
    assert [k.name for k in cfg.plan] == ["dense", "moe", "moe", "moe"]
    assert {k.mixer for k in cfg.layer_kinds} == {"mla"} == {k.mixer for k in cfg.plan}
    assert [k.pool for k in cfg.layer_kinds] == ["latent", "latent"]
    assert cfg.head_dim == 24 and cfg.v_head_dim == 32 and cfg.moe_score == "sigmoid"
    assert cfg.moe_routed_scale == 1.8 and not cfg.moe_shared_gated and cfg.moe_shared_size == 32
    assert model.num_params() == sum(x.size for x in jax.tree.leaves(model.init(jax.random.PRNGKey(1))))


def test_reference_forward_matches_the_model_in_float32(model, params):
    tokens = np.random.RandomState(0).randint(0, VOCAB, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=2e-4)


@pytest.mark.parametrize("kind", ["mla", "experts", "dense"])
def test_each_kind_of_layer_matches_the_reference(model, params, kind):
    cfg, rs = model.cfg, np.random.RandomState(1)
    h = jnp.asarray(rs.randn(37, cfg.hidden_size), jnp.float32)
    f32 = lambda tree: jax.tree.map(lambda a: a[0].astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        if kind == "mla":
            w = f32(params["layers"]["moe"]["mla"])
            mine = layer_plan._mla_plain(h, w, cfg.layer_kinds[1], cfg, 1, 37,
                                         jnp.arange(37, dtype=jnp.int32))
            theirs = glm4_moe_lite._mla(h, w, ARCH, glm4_moe_lite._as_is)
        elif kind == "experts":
            w = f32(params["layers"]["moe"]["mlp"])
            mine, stats = layer_plan._ffn(h, w, cfg.layer_kinds[1], cfg, None, grad=True)
            theirs = glm4_moe_lite._experts(h, w, ARCH, glm4_moe_lite._as_is)
            assert int(stats[0]) == 37 * 2 == int(stats[1])    # every expert is held
        else:   # a model of the one dense layer
            one = TransformerModel(dataclasses.replace(cfg, num_layers=1, layer_plan=(0,)))
            p = dict(params, layers={"dense": params["layers"]["dense"]})
            tokens = rs.randint(0, VOCAB, (1, 37)).astype(np.int32)
            mine = one.apply(p, tokens)
            theirs = logits(p, tokens, ARCH._replace(n_layers=1))
    assert np.allclose(mine, theirs, atol=2e-4) and float(jnp.abs(mine).max()) > 1e-2


# -- the serving tick's logits: chunks of several widths, rows at several depths --------

def drive(cfg, params, prompts, width, new=5):
    """The tick by hand: the prompts admitted one after another, each in chunks of ``width``
    that ride beside the rows already decoding, then ``new`` plain ticks. Returns, a row, its
    tokens (prompt + greedy) and [(position, the tick's logits there)]."""
    B = len(prompts)
    cache = tf.init_cache(cfg, B, LENGTH)
    pos, last = np.full(B, LENGTH, np.int32), np.zeros(B, np.int32)
    seqs, seen = [list(p) for p in prompts], [[] for _ in prompts]
    tick = jax.jit(lambda cache, last, pos, chunk: layer_plan.forward_plan_cached(
        params, cfg, last, pos, cache, read_len=64 if max(map(len, prompts)) < 50 else None,
        chunk=chunk))

    def run(chunk):
        nonlocal cache
        out, cache, _ = tick(cache, jnp.asarray(last), jnp.asarray(pos), chunk)
        for row in np.flatnonzero(pos < LENGTH):
            seen[row].append((int(pos[row]), np.asarray(out[row])))
            last[row] = int(np.argmax(out[row]))
            seqs[row].append(int(last[row]))
            pos[row] += 1
        return np.asarray(out)

    with jax.default_matmul_precision("highest"):
        for row, prompt in enumerate(prompts):
            for start in range(0, len(prompt), width):
                n = min(width, len(prompt) - start)
                toks, at = np.zeros(width, np.int32), np.full(width, LENGTH, np.int32)
                toks[:n], at[:n] = prompt[start:start + n], np.arange(start, start + n)
                out = run(layer_plan.Chunk(jnp.asarray(toks), jnp.asarray(at), jnp.int32(row),
                                           jnp.int32(n - 1)))
            seen[row].append((len(prompt) - 1, out[row]))    # the chunk's sampled column
            last[row], pos[row] = int(np.argmax(out[row])), len(prompt)
            seqs[row].append(int(last[row]))
        for _ in range(new):
            run(None)
    return seqs, seen


def worst_gap(params, seqs, seen, arch=ARCH, **kw):
    """Largest |tick logit - reference logit| over every position a tick gave logits for."""
    worst = 0.0
    for seq, marks in zip(seqs, seen):
        toks = np.zeros((1, LENGTH), np.int32)
        toks[0, :len(seq)] = seq
        ref = logits(params, toks, arch, **kw)[0]
        worst = max([worst] + [float(np.abs(ref[p] - got).max()) for p, got in marks])
    return worst


# (seed 2 gave a tie: two experts 6e-8 apart at one position, chosen by rounding)
PROMPTS = [np.random.RandomState(12).randint(0, VOCAB, n).astype(np.int32) for n in (37, 90, 5)]


@pytest.mark.parametrize("width", [8, 32, 96])
def test_tick_logits_match_the_reference_through_chunks_and_rows_at_other_depths(model, params,
                                                                                 width):
    """Prefill in chunks and decoding through the latent cache against the
    reference's full forward (which has the expanded form alone: the rows'
    absorbed attention is checked against an independent one)."""
    seqs, seen = drive(model.cfg, params, PROMPTS, width)
    assert [len(m) for m in seen] == [len(s) - len(p) for s, p in zip(seqs, PROMPTS)]
    assert worst_gap(params, seqs, seen) < 2e-3
    # the same streams fail against a reference whose operands are float8 (compare.fp8)
    assert worst_gap(params, seqs[:1], seen[:1], operand=compare.fp8) > 0.05


@pytest.mark.parametrize("first, width", [(0, 32), (17, 32), (40, 8), (96, 32)])
def test_the_absorbed_and_the_expanded_chunk_form_are_one_function(model, params, first, width):
    """A chunk over its row's cached entries: the program's form (the entries
    up to the chunk's end through W_UKV, then the flash chunk kernel) against
    the absorbed form the rows take, which for a chunk only this test writes
    out (a chunk's scores over the entries themselves, by einsum)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_chunk
    from deepspeed_tpu.ops.pallas.mla_attention import mla_expand

    cfg, rs = model.cfg, np.random.RandomState(first)
    p = jax.tree.map(lambda a: a[0].astype(jnp.float32), params["layers"]["moe"]["mla"])
    kr, dr, scale = cfg.mla_kv_rank, cfg.mla_rope_dim, cfg.head_dim ** -0.5
    row = layer_plan._stored(jnp.asarray(rs.randn(LENGTH, kr + dr), jnp.float32), cfg)
    q = jnp.asarray(rs.randn(width, cfg.num_heads, cfg.head_dim), jnp.float32)
    with jax.default_matmul_precision("highest"):
        wuk, wuv = (jnp.transpose(w, (1, 0, 2)) for w in layer_plan._mla_up(p, cfg))
        k, v = mla_expand(row, wuk, wuv, first + width, rank=kr, rope=dr)
        expanded = flash_attention_chunk(q, k, v, q_off=first, sm_scale=scale)
        s = jnp.einsum("whx,tx->hwt", layer_plan._mla_absorb(q, p, cfg), row) * scale
        ok = jnp.arange(LENGTH)[None, :] <= first + jnp.arange(width)[:, None]
        u = jnp.einsum("hwt,tx->whx", jax.nn.softmax(jnp.where(ok[None], s, -1e30), axis=-1), row)
        absorbed = layer_plan._mla_unabsorb(u, p, cfg)
    assert expanded.shape == absorbed.shape == (width, cfg.num_heads, cfg.mla_v_dim)
    assert float(jnp.abs(expanded - absorbed).max()) < 1e-4 < float(jnp.abs(absorbed).max())


@pytest.mark.parametrize("fault", ["absorbed_scale", "late_rope"])
def test_a_fault_planted_in_the_program_fails_the_same_comparison(model, params, fault):
    with glm_cell_variant.FAULTS[fault](TOY):
        seqs, seen = drive(model.cfg, params, PROMPTS, 32)
    assert worst_gap(params, seqs, seen) > 0.05
    seqs, seen = drive(model.cfg, params, PROMPTS, 32)     # ... and the variant is gone
    assert worst_gap(params, seqs, seen) < 2e-3


def serve(model, params, prompts, new=10, slots=1, chunk=32):
    eng = ContinuousBatchingEngine(
        model, config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
        params=params, max_slots=slots, cache_len=LENGTH, prefill_chunk=chunk)
    eng._chunk_floor = 16
    rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while eng.has_work():
        eng.step()
    return eng, [np.asarray(eng.result(r)) for r in rids]


def stream_gaps(params, prompt, out):
    """How far each emitted token's logit sits below the reference's top one."""
    toks = np.zeros((1, LENGTH), np.int32)
    toks[0, :len(out)] = out
    ref = logits(params, toks)[0, len(prompt) - 1:len(out) - 1]
    emitted = out[len(prompt):]
    return ref.max(-1) - ref[np.arange(len(emitted)), emitted]


def test_the_engine_serves_it_and_counts_what_the_latent_pool_did(model, params):
    rs = np.random.RandomState(3)
    two = [rs.randint(0, VOCAB, 80).astype(np.int32), rs.randint(0, VOCAB, 6).astype(np.int32)]
    with jax.default_matmul_precision("highest"):
        eng, outs = serve(model, params, two)            # the short one inherits the slot
    assert all(stream_gaps(params, p, o).max() < 1e-3 for p, o in zip(two, outs))
    stats = eng.tick_stats()
    assert stats["prefill_chunk_tokens"] == 86
    # a request's first token is its last chunk's; the nine others read the row to its length
    assert stats["mla_row_keys"] == sum(80 + i + 1 for i in range(9)) + sum(6 + i + 1 for i in range(9))
    # four chunks, each expanded to the end of the key tile that holds its last key: the whole
    # (toy) bucket
    assert stats["mla_expand_tokens"] == 4 * LENGTH
    assert stats["latent_pool_bytes"] == stats["kv_pool_bytes_latent"] == sum(
        a.nbytes for a in jax.tree.leaves(tf.init_cache(model.cfg, 1, LENGTH)))


# -- each piece of the mathematics ------------------------------------------------------

@pytest.mark.parametrize("left_out", ["routed_scale", "shared", "rope_key", "kv_norm", "q_norm",
                                      "scale_576", "bias"])
def test_each_piece_of_the_mathematics_fails_when_left_out(model, params, left_out):
    """The program's logits against a reference that lacks one piece: the
    comparison that passes above must fail."""
    tokens = np.random.RandomState(4).randint(0, VOCAB, (1, 48)).astype(np.int32)
    if left_out == "bias":   # init draws it small: make it decide
        params = jax.tree.map(lambda a: a, params)
        mlp = dict(params["layers"]["moe"]["mlp"])
        mlp["gate_bias"] = mlp["gate_bias"] * 50.0
        params = dict(params, layers=dict(params["layers"], moe=dict(params["layers"]["moe"], mlp=mlp)))
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=2e-4)
    wrong = logits(params, tokens, ARCH._replace(without=(left_out,)))
    assert not np.abs(wrong - theirs).max() < 0.02      # (a reference that blew up is not a match)


def test_the_whole_layer_is_the_uncut_reference_layer(model):
    """The share test of the guide at share 1: with every expert held, the
    program's layer is the model's, the shared expert and the factor in it,
    and two halves of the experts add up to it too."""
    from deepspeed_tpu.moe import held_experts as he

    rs = np.random.RandomState(6)
    E, D, F = 8, 64, 32
    h = jnp.asarray(rs.randn(29, D), jnp.float32)
    draw = lambda *shape, scale=0.2: jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
    whole = {"gate": draw(D, E, scale=0.3), "gate_bias": draw(E, scale=0.1), "wg": draw(E, D, F),
             "wi": draw(E, D, F), "wo": draw(E, F, D), "shared_wg": draw(D, F),
             "shared_wi": draw(D, F), "shared_wo": draw(F, D)}
    uncut = glm4_moe_lite._experts(h, whole, ARCH, glm4_moe_lite._as_is)
    cfg = model.cfg
    mine, stats = layer_plan._ffn(h, whole, cfg.layer_kinds[1], cfg, None, grad=True)
    assert np.allclose(mine, uncut, atol=1e-4) and int(stats[1]) == int(stats[0]) == 29 * 2
    shared = uncut - glm4_moe_lite._experts(h, whole, ARCH._replace(without=("shared",)),
                                            glm4_moe_lite._as_is)
    assert float(jnp.abs(shared).max()) > 0.05
    chosen, weights = he.route(h, whole["gate"], whole["gate_bias"], ARCH.top_k, "sigmoid", scale=1.8)
    total = shared
    for first in (0, 4):
        part, _ = he.held_experts_ffn(h, chosen, weights, {n: whole[n][first:first + 4]
                                                           for n in ("wg", "wi", "wo")}, first, 4, tm=8)
        total = total + part
    assert np.allclose(total, uncut, atol=1e-4)


def test_serving_comparison_passes_greedy_streams_and_fails_wrong_ones(params):
    rs = np.random.RandomState(8)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in (9, 14, 20, 27)]
    streams = []
    for p in prompts:  # greedy decoding by the reference itself: the right answer
        seq = list(p)
        for _ in range(6):
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            seq.append(int(np.argmax(logits(params, toks)[0, len(seq) - 1])))
        streams.append(np.array(seq[len(p):], np.int32))
    tol = dict(margin=0.25, share_within=0.99, control_share=0.0, distinct_per_request=1)
    verdict = lambda s: compare.serve_verdict(glm4_moe_lite, params, prompts, s, ARCH, 5, tol,
                                              width=64, new_max=6)
    ok, fields = verdict(streams)
    assert ok and fields["share_within_margin"] == 1.0 and fields["worst_gap"] == 0.0, fields
    assert fields["control_share_outside_margin"]["prompt_permuted"] > 0.2, fields
    ok, fields = verdict([rs.randint(0, VOCAB, 6).astype(np.int32) for _ in prompts])
    assert not ok and fields["share_within_margin"] < 0.5


def test_float8_operands_move_the_reference_by_more_than_float32_rounding(params):
    tokens = np.random.RandomState(9).randint(0, VOCAB, (1, 32)).astype(np.int32)
    assert np.abs(logits(params, tokens, operand=compare.fp8) - logits(params, tokens)).max() > 0.05


# -- the toy cell through the harness, and the variant tool -----------------------------

@pytest.fixture
def environment(tmp_path):
    saved = {k: os.environ.get(k) for k in ("JAX_COMPILATION_CACHE_DIR", "TMPDIR")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    os.environ["TMPDIR"] = str(tmp_path)
    leaked = {k: os.environ.pop(k) for k in ("DSTPU_COORDINATOR", "DSTPU_NUM_PROCESSES",
                                             "DSTPU_PROCESS_ID") if k in os.environ}
    yield
    os.environ.update(leaked)
    for k, v in saved.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


@pytest.mark.parametrize("variant, correct", [("no_rope_key", False), ("no_kv_norm", False)])
def test_the_variant_tool_runs_the_toy_cell_and_a_fault_is_refused(environment, capsys, variant,
                                                                   correct):
    sound_arch, sound_build = glm4_moe_lite.arch, models_glm4_moe_lite.build_model
    line = glm_cell_variant.main(
        ["--variant", variant, "--workload", "toy-glm-longdoc", "--seed", str(2 ** 31 + 7),
         "--seconds", "1.0"], manifest=bench_toy.manifest_path(), require_tpu=False)
    assert line["correct"] is correct and line["failed"] == 0 and line["attempted"] >= 1
    assert glm4_moe_lite.arch is sound_arch and models_glm4_moe_lite.build_model is sound_build
    out = capsys.readouterr().out
    assert f'"variant": "{variant}"' in out
    said = next(json.loads(l) for l in out.splitlines() if l.startswith('{"phase": "observations"'))
    # the new counters, among the runner's observations
    assert said["mla_row_keys_per_tick"] > 0 and said["latent_pool_gb"] > 0
    assert 0 < said["latent_bytes_share_pct"] < 100
    assert said["mla_expand_tokens"] > 0 and 0 < said["mla_expand_share_pct"] < 100


# -- the published configuration, cut to one chip ---------------------------------------

def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of published configurations is not on this machine")
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    return next(r for r in rows if r["source_url"] == REAL["source"])


def test_configuration_file_holds_the_published_config_but_for_what_it_lists_as_reduced():
    entry = catalog_entry()
    for key, value in entry["config"].items():
        assert REAL[key] == REAL["model"][key], key          # one set of values, in both places
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    changed = {k for k, v in entry["config"].items() if REAL[k] != v}
    assert changed == set(REAL["reduced"]) == {"num_hidden_layers", "num_nextn_predict_layers"}
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "num_experts_per_tok", "n_routed_experts", "vocab_size", "num_attention_heads")
    assert not set(widths) & set(REAL["reduced"])


def test_the_cut_keeps_the_guides_floors_and_states_the_deployment():
    m, dep = REAL["model"], REAL["deployment"]
    assert m["num_hidden_layers"] == 6 >= 4 and m["first_k_dense_replace"] == 1
    assert m["num_nextn_predict_layers"] == 0
    assert m["n_routed_experts"] == dep["held_experts"]["count"] == dep["experts_published"] == 64
    assert dep["held_experts"]["first"] == 0 and dep["vocabulary_split"] == 1
    assert m["vocab_size"] == dep["vocab_size_published"] == 154880
    assert dep["num_hidden_layers_published"] == 47 and m["num_experts_per_tok"] == 4
    assert set(REAL["assumed"]) >= {"weights", "rotary", "mla", "routing", "norm", "depth"}
    tol = REAL["compare"]["serve_latent"]
    assert {"margin", "share_within", "gap_p99_max", "control_share", "sample", "why"} <= set(tol)


def test_the_program_built_from_the_file_has_the_issues_parameter_count_and_pool():
    model = models_glm4_moe_lite.build_model(REAL, max_seq_len=16896, remat=False, attn_impl="pallas")
    cfg = model.cfg
    assert cfg.num_params() == 3_895_625_536
    assert cfg.head_dim == 256 == cfg.v_head_dim and cfg.num_heads == 20
    assert [k.name for k in cfg.plan] == ["dense"] + ["moe"] * 5
    assert cfg.held_experts == (0, 64) and cfg.moe_routed_scale == 1.8 and not cfg.moe_shared_gated
    assert kv_cache.latent_width(cfg) == 640 and layer_plan.pool_shapes(cfg) == {"latent": (6, 1, 0)}
    cache = jax.eval_shape(lambda: kv_cache.init(cfg, 32, 16896))
    assert jax.tree.map(lambda a: a.shape, cache) == {"latent": {"c": (6, 32, 1, 16896, 640)}}
    assert cache["latent"]["c"].size * 2 == 6 * 32 * 16896 * 1280                    # 4.15 GB
    assert kv_cache.read_bytes_by_pool(cfg, 4096) == {"latent": 6 * 4096 * 1280}
    assert kv_cache.rows_write_by_blocks(cfg, cache, None) and kv_cache.rows_write_by_blocks(cfg, cache, 512)
    assert not kv_cache.rows_write_by_blocks(cfg, cache, 256)    # a row's window under 512 KiB


# -- operations and bytes, against hand counts ------------------------------------------

def test_cost_shapes_and_parameter_counts_by_hand():
    c = costs_glm4_moe_lite
    s = c.shapes(REAL)
    assert (s["n_dense"], s["n_moe"], s["L"], c.stored_width(s)) == (1, 5, 6, 640)
    assert c.mla_params(s) == 21_759_232 and c.expert_params(s) == 9_437_184
    always = 6 * (21_759_232 + 4096) + 3 * 2048 * 10240 + 5 * (131_072 + 64 + 9_437_184) + 2048 * 154880
    assert c.always_read_params(s) == always
    # with the held experts, the embedding and the final norm: the issue's parameter count
    assert always + 5 * 64 * 9_437_184 + 154880 * 2048 + 2048 == 3_895_625_536
    assert c.latent_bytes_per_key(s) == 6 * 1280 and c.decode_flops_per_key(s) == 43_520
    assert c.expand_flops_per_token(s) == 2 * 512 * 20 * 448 == 9_175_040


def test_tick_and_chunk_costs_by_hand():
    c = costs_glm4_moe_lite
    obs = dict(mean_live_rows=30.0, moe_experts_hit_per_tick=270.0, moe_held_assignments_per_tick=600.0,
               mla_row_keys_per_tick=280_000.0, chunk_tokens=1000.0, chunk_keys_full=5096.0,
               chunk_pairs_full=1000 * 4096 + 1000 * 1001 / 2, mla_expand_tokens=8192.0)
    always = c.always_read_params(c.shapes(REAL))
    cost = c.decode_tick(REAL, {}, obs)
    assert cost["bytes"] == (always + 270 * 9_437_184) * 2 + 280_000 * 6 * 1280
    assert cost["flops"] == 2 * (always * 30 + 600 * 9_437_184) + 6 * 280_000 * 43_520
    assert c.latent_bytes_tick(REAL, obs) == 280_000 * 6 * 1280
    assert c.mla_decode_tick(REAL, {}, obs) == {"flops": 6 * 280_000 * 43_520,
                                                "bytes": 6 * 280_000 * 1280}
    gm = c.grouped_matmul_tick(REAL, {}, obs)
    assert gm["flops"] == 2 * 600 * 9_437_184
    assert gm["bytes"] == 270 * 9_437_184 * 2 + 600 * (2 * 2048 + 3 * 1536) * 2
    fl = c.flash_chunk(REAL, {}, obs)
    assert fl["flops"] == 2 * 6 * obs["chunk_pairs_full"] * 20 * 512
    assert fl["bytes"] == 6 * (1000 + 5096) * 20 * 512 * 2
    expand, attend = 8192 * 9_175_040, 2 * obs["chunk_pairs_full"] * 20 * 512
    assert c.expand_share_pct(REAL, obs) == pytest.approx(100 * expand / (expand + attend))
    assert c.expand_share_pct(REAL, dict(obs, mla_expand_tokens=0.0)) == 0.0


def test_a_program_without_the_counters_gives_no_reading():
    """The parent of the PR that brought the counters: its tick_stats() lacks
    them, the runner's observations carry None, which a reader returns as
    nothing; and its layer plan has no such mixer, which the builder reports
    as the harness's "the program is not here"."""
    from benchmark import readers
    from benchmark.runners import serve_latent

    class Bare(serve_latent.Runner):
        def __init__(self):
            self.records, self.live_rows, self.live_kv = [], [], []
            self.ctx = dict(config=REAL, cell={})

    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = Bare()._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert obs["mla_row_keys_per_tick"] is None and obs["mla_expand_tokens"] is None
    assert obs["latent_pool_gb"] is None
    assert "latent_bytes_share_pct" not in obs and "mla_expand_share_pct" not in obs
    ctx = readers.Context(obs=obs, config=REAL, cell={}, peaks=None, chips=1)
    assert readers.evaluate({"reduction": "value", "key": "latent_bytes_share_pct"}, ctx) is None
    fields = dataclasses.fields
    try:
        models_glm4_moe_lite.dataclasses.fields = lambda cls: [
            f for f in fields(cls) if not f.name.startswith("mla_")]
        with pytest.raises(ImportError, match="latent-attention"):
            models_glm4_moe_lite.build_model(REAL, max_seq_len=128, remat=False, attn_impl="xla")
    finally:
        models_glm4_moe_lite.dataclasses.fields = fields


# -- the accepted plan cells' programs are the parent's ---------------------------------

# sha256 of jit(forward_plan_cached).lower(...).as_text() of the toy MiMo and toy Qwen3-Next ticks
# (4 slots of 128, read 64; plain, and with a 32-token chunk). First recorded on the parent of the PR
# that brought the routed scaling factor and the ungated shared expert (1d691d7): at scale 1.0 and a
# gated shared expert the tick is the old program, to the text. RECORDED ANEW by PR 56 (benchmark) on
# its own tree, whose program is commit 88700f3's (= PR 54's, 67dbef5): PRs 43 (the chunk kernel),
# 46 (`_heads_product`) and 52 (the expert layers' way back) each MEANT to change these programs and,
# as program PRs, could not edit this file. A PR that MEANS to change them records them anew, and
# says so; a program PR leaves the cases red for the next `benchmark` PR.
PARENTS_TICKS = {
    ("toy-mimo-v2", None): "d84e8ab00dd44882327dfc1d401690ea40c7ca1199501451d8609d10cf6508ff",
    ("toy-mimo-v2", 32): "b27a54f14fea9e25e38fb7a2c6db1254c267a9f7594865d2d6fa944ebf69fb94",
    ("toy-qwen3-next", None): "9d8f4101c41a17d2bfaae7bc4186f01e0e72c214e01803c3bdd27c0da6deddc0",
    ("toy-qwen3-next", 32): "3a325d7a3a6ea57eb10a2b6ae1668f0974c9f42f34b101e190e08fe97c4ec6df",
}


@pytest.mark.parametrize("name,chunk", sorted(PARENTS_TICKS, key=str))
def test_the_accepted_plan_cells_ticks_lower_to_the_parents_text(name, chunk):
    import hashlib

    config = harness.load_json(os.path.join(bench_toy.ROOT, bench_toy.TOY_DIR, "configs", name + ".json"))
    cfg = compare.builder_of(config).build_model(config, max_seq_len=128, remat=False,
                                                 attn_impl="pallas").cfg
    assert cfg.moe_routed_scale == 1.0 and cfg.moe_shared_gated
    params = jax.eval_shape(TransformerModel(cfg).init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, 4, 128))
    row, scalar = jax.ShapeDtypeStruct((4,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)
    wide = jax.ShapeDtypeStruct((chunk or 1,), jnp.int32)
    ride = layer_plan.Chunk(wide, wide, scalar, scalar) if chunk else None
    text = jax.jit(lambda p, t, ps, ca, ch: layer_plan.forward_plan_cached(
        p, cfg, t, ps, ca, read_len=64, chunk=ch)).lower(params, row, row, cache, ride).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_TICKS[name, chunk]
