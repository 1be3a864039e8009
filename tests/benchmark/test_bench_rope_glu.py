"""The second family of the toy set (RoPE, RMSNorm, SwiGLU, grouped-query
attention, no biases, an untied head): its reference against the program's
model in float32, and its comparisons' power. Its two toy cells run end to
end with every other toy cell (``test_bench_runners_cpu.py`` finds them by
their files). Everything of this family is a file of its own: remove them
and the rest of ``tests/benchmark`` passes as before."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, harness, models_rope_glu
from benchmark.reference import rope_glu

CONFIG = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-rope-glu.json")), dtype="float32")
ARCH = rope_glu.arch(CONFIG)
VOCAB = CONFIG["model"]["vocab_size"]


@pytest.fixture(scope="module")
def model():
    return models_rope_glu.build_model(CONFIG, max_seq_len=64, remat=False, attn_impl="xla")


def test_the_configuration_names_its_reference_and_builder():
    assert compare.reference_of(CONFIG) is rope_glu and compare.builder_of(CONFIG) is models_rope_glu
    assert ARCH == (4, 2, 10000.0, 1e-05) and "vocab_size" in models_rope_glu.REQUIRED_SIZES
    assert all(isinstance(CONFIG["model"][k], int) for k in models_rope_glu.REQUIRED_SIZES)
    assert compare.reference_of({}).__name__.endswith(".gpt2")  # absent: the default
    assert compare.builder_of({}).__name__ == "benchmark.models"


def test_the_model_differs_from_gpt2_in_every_block(model):
    tree = model.init(jax.random.PRNGKey(0))
    assert set(tree) == {"embed", "layers", "final_norm", "lm_head"} and set(tree["embed"]) == {"tok"}
    assert set(tree["layers"]["attn"]) == {"wq", "wk", "wv", "wo"}  # no biases
    assert tree["layers"]["attn"]["wk"].shape[-1] == 64  # two key-value heads of 32 for four query heads
    assert set(tree["layers"]["mlp"]) == {"wg", "wi", "wo"} and set(tree["layers"]["ln1"]) == {"scale"}


def test_reference_forward_matches_the_model_in_float32(model):
    params = model.init(jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, VOCAB, (2, 32)).astype(np.int32)
    at = np.tile(np.arange(32, dtype=np.int32), (2, 1))
    ours = rope_glu.logits_at(params, tokens, at, ARCH)
    theirs = model.apply(params, tokens)
    assert np.allclose(np.asarray(ours), np.asarray(theirs, np.float32), atol=2e-4)


def test_reference_loss_and_grads_match_the_models(model):
    params = model.init(jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, VOCAB, (4, 32)), jnp.int32)
    loss, grads = rope_glu.loss_and_grads(params, tokens, ARCH, rows_per_pass=2)
    want, want_g = jax.value_and_grad(lambda p: model.loss(p, {"input_ids": tokens}))(params)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("fault", rope_glu.FAULTS)
def test_each_training_fault_leaves_the_tolerances(model, fault):
    tokens = np.random.RandomState(2).randint(0, VOCAB, (4, 32)).astype(np.int32)
    opt = dict(lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.01)
    key = jax.random.PRNGKey(2)
    run = lambda f: compare.train_reference(rope_glu, model.init, key, tokens, ARCH, 3, opt,
                                            jax.devices()[:1], rows_per_pass=2, fault=f)
    good, bad = run(None), run(fault)
    tol = dict(loss_abs=0.005, grad_norm_rel=0.01, min_fall=0.01)
    assert compare.train_verdict(good["losses"], good["grad_norms"][0], good, tol)[0]
    ok, fields = compare.train_verdict(good["losses"], good["grad_norms"][0], bad, tol)
    assert not ok, fields
    assert good["checksum"] == bad["checksum"]  # same start: only the trainer differs


def test_serving_comparison_passes_greedy_streams_and_fails_wrong_ones(model):
    params = compare.seed_params(model, 5, lambda p: models_rope_glu.sharpen(p, CONFIG, 3.0))
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in (9, 14, 20, 27)]
    streams = []
    for p in prompts:  # greedy decoding by the reference itself: the right answer
        seq = list(p)
        for _ in range(6):
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            logits = rope_glu.logits_at(params, toks, np.array([[len(seq) - 1]], np.int32), ARCH)
            seq.append(int(np.argmax(np.asarray(logits)[0, 0])))
        streams.append(np.array(seq[len(p):], np.int32))
    # rotary positions are relative: a context one position early loses only its first token
    tol = dict(margin=0.25, share_within=0.99, control_share=0.0, distinct_per_request=1)
    verdict = lambda s: compare.serve_verdict(rope_glu, params, prompts, s, ARCH, 5, tol,
                                              width=64, new_max=6)
    ok, fields = verdict(streams)
    assert ok and fields["share_within_margin"] == 1.0 and fields["worst_gap"] == 0.0, fields
    assert fields["control_share_outside_margin"]["prompt_permuted"] > 0.2, fields
    ok, fields = verdict([rs.randint(0, VOCAB, 6).astype(np.int32) for _ in prompts])
    assert not ok and fields["share_within_margin"] < 0.5
    assert not verdict([np.roll(s, 1) for s in streams])[0]  # the right tokens, one position off
