"""Speculative decoding inside the pooled serving tick
(decoding.compile_spec_pool_tick_fn + the continuous.py spec wiring).

The acceptance invariant throughout: speculation is LOSSLESS — it changes
how many tokens a tick emits, never which. Greedy speculative streams are
bitwise identical to plain pooled ticks across pipeline depths, prefill
fusion, int8 KV, and tensor-parallel meshes; sampled streams are
scheduling-invariant (per-(rid, token, lane) keys) and distribution-
equivalent to plain sampled pooled decode; the ngram self-drafting
fallback needs no second model (docs/inference.md "Speculative
decoding")."""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from serving_toys import SMALL, built, prompts as _prompts, serve as _serve

FLOOR = 32  # a tight-read floor under the 64-slot pools: ONE crossing (32 -> the whole pool) a stream


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    model, params = built(SMALL)
    dcfg = TransformerConfig(vocab_size=128, hidden_size=32, num_layers=1,
                             num_heads=4, max_seq_len=128, dtype="float32")
    draft = TransformerModel(dcfg)
    draft_params = draft.init(jax.random.PRNGKey(1))
    return model, params, draft, draft_params


def _cb(setup, spec=None, tensor=None, use_draft=False, **kw):
    """Pool engine; ``spec=(gamma, mode)`` turns the speculative tick on.
    Donation stays off — the CPU backend blocks at dispatch under
    donation (docs/serving.md caveat) and depth parity is what we sweep."""
    model, params, draft, draft_params = setup
    cfg = {"dtype": "float32", "kv_read_floor": FLOOR}
    if tensor is not None:
        cfg["mesh"] = {"shape": {"data": 1, "tensor": tensor}}
    if spec is not None:
        gamma, mode = spec
        cfg["speculative"] = {"enabled": True, "pool": True, "mode": mode,
                              "num_draft_tokens": gamma}
    cfg.update(kw.pop("config", {}))
    kw.setdefault("max_slots", 3)
    kw.setdefault("cache_len", 64)
    kw.setdefault("donate_cache", False)
    if use_draft:
        kw.update(draft_model=draft, draft_params=draft_params)
    return ContinuousBatchingEngine(model, params=params, config=cfg, **kw)


class TestSpecPoolGreedyParity:
    def test_ngram_matches_plain_across_depths(self, setup):
        """Acceptance: ngram self-drafting greedy streams == plain pooled
        greedy streams bitwise, at pipeline depths 0 / 1 / 2, under mixed
        mid-flight admission (slot churn re-owns freed slots)."""
        subs = list(zip((0, 0, 0, 1, 3), _prompts((5, 9, 3, 20, 7), 1),
                        (12, 40, 8, 10, 6)))
        plain = _serve(_cb(setup), subs)
        for depth in (0, 1, 2):
            spec = _serve(_cb(setup, spec=(4, "ngram"),
                              pipeline_depth=depth), subs)
            for a, b in zip(plain, spec):
                np.testing.assert_array_equal(a, b)

    def test_draft_model_matches_plain_across_depths(self, setup):
        """Draft-model mode (second param tree on the same mesh): an
        unrelated draft accepts per-row-variable counts, streams still
        equal plain greedy bitwise at depths 0 / 1."""
        subs = list(zip((0, 0, 2), _prompts((6, 11, 4), 2), (10, 14, 8)))
        plain = _serve(_cb(setup), subs)
        for depth in (0, 1):
            spec = _serve(_cb(setup, spec=(3, "draft"), use_draft=True,
                              pipeline_depth=depth), subs)
            for a, b in zip(plain, spec):
                np.testing.assert_array_equal(a, b)

    def test_fused_and_separate_prefill_parity(self, setup):
        """Admission mode must not touch the verify math: fused-prefill
        chunks riding the spec tick == separate-prefill == plain."""
        subs = list(zip((0, 1, 1), _prompts((5, 26, 2), 4), (8, 8, 8)))
        plain = _serve(_cb(setup), subs)
        fused = _serve(_cb(setup, spec=(4, "ngram"), fused_prefill=True), subs)
        sep = _serve(_cb(setup, spec=(4, "ngram"), fused_prefill=False), subs)
        for p, f, s in zip(plain, fused, sep):
            np.testing.assert_array_equal(p, f)
            np.testing.assert_array_equal(p, s)

    def test_int8_kv_parity_both_modes(self, setup):
        """int8 KV quantizes writes identically on the plain and the
        gamma-wide verify path (and the draft's own cache), so streams
        stay bitwise equal under quantized caches too."""
        subs = list(zip((0, 0, 1), _prompts((5, 9, 4), 3), (10, 12, 8)))
        int8 = {"config": {"kv_cache_dtype": "int8"}}
        plain = _serve(_cb(setup, **int8), subs)
        ngram = _serve(_cb(setup, spec=(4, "ngram"), pipeline_depth=1,
                           **int8), subs)
        drafted = _serve(_cb(setup, spec=(2, "draft"), use_draft=True,
                             **int8), subs)
        for p, n, d in zip(plain, ngram, drafted):
            np.testing.assert_array_equal(p, n)
            np.testing.assert_array_equal(p, d)

    def test_tp2_matches_single_chip(self, setup):
        """Sharded spec ticks (tensor=2 over the virtual 8-device host):
        the mesh changes WHERE the verify math runs, never WHAT tokens
        come out — both modes equal the single-chip plain streams."""
        subs = list(zip((0, 0, 1), _prompts((6, 9, 4), 5), (10, 10, 8)))
        plain = _serve(_cb(setup), subs)
        ngram = _serve(_cb(setup, spec=(4, "ngram"), tensor=2,
                           pipeline_depth=1), subs)
        drafted = _serve(_cb(setup, spec=(2, "draft"), use_draft=True,
                             tensor=2), subs)
        for p, n, d in zip(plain, ngram, drafted):
            np.testing.assert_array_equal(p, n)
            np.testing.assert_array_equal(p, d)

    def test_gamma_edges(self, setup):
        """gamma=1 (minimal round) and gamma=8 (wider than most quotas
        left mid-request) both reproduce plain streams."""
        subs = list(zip((0, 0), _prompts((5, 8), 6), (9, 11)))
        plain = _serve(_cb(setup), subs)
        for gamma in (1, 8):
            spec = _serve(_cb(setup, spec=(gamma, "ngram")), subs)
            for a, b in zip(plain, spec):
                np.testing.assert_array_equal(a, b)

    def test_eos_mid_round_matches_plain(self, setup):
        """A request hitting EOS inside a verify round stops exactly where
        the plain pooled stream stops (the round tail past the accepted
        EOS is masked on device, like burst waste)."""
        subs = list(zip((0, 0), _prompts((5, 7), 7), (14, 14)))
        probe = _serve(_cb(setup), subs)
        eos = int(probe[0][len(subs[0][1]) + 3])  # fires mid-round at gamma 4
        plain = _serve(_cb(setup, eos_token_id=eos), subs)
        spec = _serve(_cb(setup, spec=(4, "ngram"), eos_token_id=eos), subs)
        for a, b in zip(plain, spec):
            np.testing.assert_array_equal(a, b)
        assert len(plain[0]) < len(probe[0])  # the early stop really fired


class TestSpecPoolSampled:
    def test_sampled_scheduling_invariance_draft_mode(self, setup):
        """Draft-mode sampled draws key off (seed, rid, token index, lane)
        and the proposal scan runs ON DEVICE from device-threaded state:
        pipeline depth, prefill fusion, and slot placement must not move a
        single draw — streams bitwise equal across scheduling modes.
        (Ngram proposals come from the HOST context, which lags the device
        under dispatch-ahead pipelining — sampled ngram streams are
        distribution-equivalent across depths, not bitwise; see
        test_sampled_distribution_equivalence.)"""
        subs = list(zip((0, 0, 2), _prompts((6, 11, 4), 8), (10, 10, 8)))
        kw = dict(spec=(3, "draft"), use_draft=True, temperature=0.9,
                  top_k=20, top_p=0.9, seed=11)
        base = _serve(_cb(setup, pipeline_depth=0, **kw), subs)
        variants = [
            _serve(_cb(setup, pipeline_depth=2, **kw), subs),
            _serve(_cb(setup, pipeline_depth=1, fused_prefill=False, **kw),
                   subs),
        ]
        for other in variants:
            for a, b in zip(base, other):
                np.testing.assert_array_equal(a, b)
        # and the draws really are sampled (greedy spec run differs)
        greedy = _serve(_cb(setup, spec=(3, "draft"), use_draft=True,
                            seed=11), subs)
        assert any(not np.array_equal(a, b) for a, b in zip(base, greedy))

    def test_sampled_distribution_equivalence(self, setup):
        """Lossless rejection sampling: emitted sampled tokens follow the
        TARGET distribution regardless of the proposal stream. Same prompt
        submitted many times (independent per-rid keys); the empirical
        token histogram of each speculative mode must match the plain
        pooled sampler's. Deterministic given the seeds — the total-
        variation bound is a regression pin, not a flaky statistic."""
        prompt = _prompts((6,), 9)[0]
        subs = [(i // 3, prompt, 6) for i in range(48)]
        kw = dict(temperature=1.0, top_k=3, seed=7)

        def hist(outs):
            toks = np.concatenate([o[len(prompt):] for o in outs])
            return np.bincount(toks, minlength=128) / toks.size

        plain = hist(_serve(_cb(setup, **kw), subs, max_ticks=800))
        for spec in ((3, "ngram"), (2, "draft")):
            h = hist(_serve(_cb(setup, spec=spec, use_draft=spec[1] == "draft",
                                **kw), subs, max_ticks=800))
            tv = 0.5 * np.abs(plain - h).sum()
            assert tv < 0.2, f"{spec}: total variation {tv:.3f} vs plain"


class TestSpecPoolValidation:
    def test_requires_single_token_ticks(self, setup):
        with pytest.raises(ValueError, match="tokens_per_tick=1"):
            _cb(setup, spec=(4, "ngram"), tokens_per_tick=2)

    def test_rejects_unknown_mode(self, setup):
        with pytest.raises(ValueError, match="'draft' or 'ngram'"):
            _cb(setup, spec=(4, "retrieval"))

    def test_rejects_bad_gamma(self, setup):
        with pytest.raises(ValueError, match="num_draft_tokens"):
            _cb(setup, spec=(0, "ngram"))

    def test_draft_mode_without_model_names_ngram_fallback(self, setup):
        """The draft-missing error must teach the fix that needs no second
        model: mode='ngram'."""
        with pytest.raises(ValueError, match="ngram"):
            _cb(setup, spec=(4, "draft"))

    def test_draft_model_without_spec_pool(self, setup):
        with pytest.raises(ValueError, match="speculative"):
            _cb(setup, use_draft=True)

    def test_draft_vocab_mismatch(self, setup):
        model, params, _, _ = setup
        other = TransformerModel(TransformerConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
            max_seq_len=128, dtype="float32"))
        with pytest.raises(ValueError, match="vocab"):
            ContinuousBatchingEngine(
                model, params=params,
                config={"dtype": "float32",
                        "speculative": {"enabled": True, "pool": True,
                                        "mode": "draft",
                                        "num_draft_tokens": 4}},
                max_slots=2, cache_len=64, draft_model=other,
                draft_params=other.init(jax.random.PRNGKey(2)))

    def test_engine_generate_ngram_mode_needs_pool(self, setup):
        """engine.generate() has no token-history scheduler to self-draft
        from: speculative without a draft model raises and the message
        routes to the pooled serving path."""
        model, params, _, _ = setup
        eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32",
                    "speculative": {"enabled": True, "mode": "ngram"}})
        with pytest.raises(ValueError, match="pooled serving"):
            eng.generate(_prompts((6,), 10)[0][None, :], max_new_tokens=4)

    def test_engine_generate_rejects_bad_gamma(self, setup):
        model, params, draft, draft_params = setup
        eng = deepspeed_tpu.init_inference(
            model, params=params, config={"dtype": "float32"})
        draft_eng = deepspeed_tpu.init_inference(
            draft, params=draft_params, config={"dtype": "float32"})
        with pytest.raises(ValueError, match="num_draft_tokens"):
            eng.generate(_prompts((6,), 10)[0][None, :], max_new_tokens=4,
                         draft=draft_eng, num_draft_tokens=0)


class TestSpecPoolTelemetry:
    def test_tick_stats_spec_fields(self, setup):
        """tick_stats() carries the acceptance counters the bench and
        ds_trace_report aggregate: gamma, mode, drafted/accepted raws, and
        the derived acceptance rate."""
        subs = list(zip((0, 0), _prompts((5, 8), 11), (10, 10)))
        cb = _cb(setup, spec=(4, "ngram"))
        _serve(cb, subs)
        st = cb.tick_stats()
        assert st["spec_gamma"] == 4 and st["spec_mode"] == "ngram"
        assert st["spec_drafted"] > 0
        assert 0 <= st["spec_accepted"] <= st["spec_drafted"]
        assert st["spec_acceptance"] == pytest.approx(
            st["spec_accepted"] / st["spec_drafted"], abs=1e-3)


class TestEngineDraftPath:
    def test_int8_kv_with_chunk_config(self, setup):
        """The single-request draft path under int8 KV: quantized writes
        are identical plain vs gamma-wide verify, so outputs match the
        plain int8 engine. A configured prefill_chunk_size must not break
        the spec path (chunked prefill is skipped when speculating — the
        verify window needs the unchunked cache geometry)."""
        model, params, draft, draft_params = setup
        spec_eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "kv_cache_dtype": "int8",
                    "prefill_chunk_size": 16,
                    "speculative": {"enabled": True, "num_draft_tokens": 3}},
            draft_model=draft, draft_params=draft_params)
        plain_eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "kv_cache_dtype": "int8"})
        prompt = np.stack(_prompts((20, 20), 12))
        spec = np.asarray(spec_eng.generate(prompt, max_new_tokens=10))
        plain = np.asarray(plain_eng.generate(prompt, max_new_tokens=10))
        np.testing.assert_array_equal(plain, spec)


class TestNgramProposer:
    def test_suffix_match_and_continuation(self):
        from deepspeed_tpu.inference import ngram

        np.testing.assert_array_equal(
            ngram.propose([1, 2, 3, 1, 2], 3), [3, 1, 2])

    def test_most_recent_occurrence_wins(self):
        from deepspeed_tpu.inference import ngram

        assert ngram.propose([5, 1, 2, 7, 1, 2], 1)[0] == 7

    def test_fallback_repeats_last_token(self):
        from deepspeed_tpu.inference import ngram

        np.testing.assert_array_equal(ngram.propose([9], 3), [9, 9, 9])
        np.testing.assert_array_equal(ngram.propose([1, 2, 3], 3), [3, 3, 3])

    def test_continuation_past_match_repeats_tail(self):
        from deepspeed_tpu.inference import ngram

        # match runs off the context end: the last matched token repeats
        np.testing.assert_array_equal(
            ngram.propose([1, 2, 1, 2, 1, 2], 4), [1, 2, 2, 2])

    def test_empty_context_and_rows(self):
        from deepspeed_tpu.inference import ngram

        np.testing.assert_array_equal(ngram.propose([], 2), [0, 0])
        rows = ngram.propose_rows([[1, 2], [7]], 3)
        assert rows.shape == (2, 3) and rows.dtype == np.int32

    def test_gamma_validation(self):
        from deepspeed_tpu.inference import ngram

        with pytest.raises(ValueError, match="gamma"):
            ngram.propose([1, 2], 0)
