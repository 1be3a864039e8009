"""Multi-chip tensor-parallel serving (parallel/partition.py + the
InferenceConfig ``mesh`` block): regex partition rules, subset serving
meshes over the virtual 8-CPU-device host, and the acceptance invariant —
the tensor width may change WHERE the math runs, never WHAT tokens come
out. Token streams are bitwise identical sharded (tensor 2/4) vs
single-chip, greedy AND sampled, across pipeline depths, fused/separate
prefill, bucket migration, and prefix splice; ``kv_bytes_read`` becomes
exact PER-CHIP bytes under a sharded cache."""

import json

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.config import InferenceConfig, MeshConfig
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.inference.decoding import decode_kv_bytes, read_bucket
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.transformer.kv_cache import read_bytes_per_row as kv_read_bytes_per_row
from deepspeed_tpu.parallel.partition import (
    DEFAULT_RULES,
    kv_shard_width,
    match_partition_rules,
    parse_mesh_arg,
    partition_params,
    serving_mesh,
)
from serving_toys import SMALL, built, prompts as _prompts, serve as _serve

FLOOR = 32  # a tight-read floor under the 64-slot pools: ONE crossing (32 -> the whole pool) a stream


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    return built(SMALL)


def _cb(setup, tensor=None, **kw):
    """Continuous engine, optionally on a 1xTENSOR serving mesh.
    Donation stays OFF: the CPU backend implements donation by blocking
    at dispatch (docs/serving.md caveat), and parity across pipeline
    depths is exactly what these tests sweep."""
    model, params = setup
    cfg = {"dtype": "float32", "kv_read_floor": FLOOR}
    if tensor is not None:
        cfg["mesh"] = {"shape": {"data": 1, "tensor": tensor}}
    cfg.update(kw.pop("config", {}))
    kw.setdefault("max_slots", 3)
    kw.setdefault("cache_len", 64)
    kw.setdefault("donate_cache", False)
    return ContinuousBatchingEngine(model, params=params, config=cfg, **kw)


class TestPartitionRules:
    def test_first_match_wins_and_scalars_replicate(self):
        params = {"attn": {"wq": np.zeros((8, 8)), "scale": np.zeros(())},
                  "mlp": {"wi": np.zeros((8, 16))}}
        rules = [(r"attn/wq", PartitionSpec(None, "tensor")),
                 (r"attn", PartitionSpec("tensor")),  # never reached for wq
                 (r".*", PartitionSpec())]
        specs = match_partition_rules(rules, params)
        assert specs["attn"]["wq"] == PartitionSpec(None, "tensor")
        assert specs["attn"]["scale"] == PartitionSpec()  # scalar
        assert specs["mlp"]["wi"] == PartitionSpec()      # catch-all

    def test_unmatched_param_raises_by_default(self):
        with pytest.raises(ValueError, match="no partition rule"):
            match_partition_rules([(r"attn", PartitionSpec())],
                                  {"mlp": {"wi": np.zeros((4, 4))}})
        specs = match_partition_rules([(r"attn", PartitionSpec())],
                                      {"mlp": {"wi": np.zeros((4, 4))}},
                                      on_miss="replicate")
        assert specs["mlp"]["wi"] == PartitionSpec()

    def test_json_rule_form_and_stacked_layers_dim(self):
        # config-file rule shape: [regex, [axis|None, ...]]; a stacked
        # layers/ leaf gets None prepended for the scan dim
        params = {"layers": {"attn": {"wq": np.zeros((3, 8, 8))}}}
        specs = match_partition_rules(
            [["attn/wq", [None, "tensor"]], [".*", []]], params)
        assert specs["layers"]["attn"]["wq"] == PartitionSpec(None, None, "tensor")

    def test_specs_align_to_trailing_dims(self):
        # rules name a weight's TRAILING (matmul) dims: a stacked MoE wi
        # (layers, expert, embed, mlp) must land "tensor" on mlp hidden,
        # never on the expert dim a trailing pad would hit
        params = {"layers": {"mlp": {"wi": np.zeros((4, 8, 16, 32)),
                                     "wo": np.zeros((4, 8, 32, 16))}}}
        specs = match_partition_rules(DEFAULT_RULES, params)
        assert specs["layers"]["mlp"]["wi"] == \
            PartitionSpec(None, None, None, "tensor")
        assert specs["layers"]["mlp"]["wo"] == \
            PartitionSpec(None, None, "tensor", None)

    def test_partition_params_clips_non_divisible_dims(self):
        mesh = serving_mesh(1, 2)
        params = {"attn": {"wq": np.zeros((8, 8)), "wk": np.zeros((8, 3))}}
        sh = partition_params(mesh, params,
                              rules=[[r"attn/w[qk]$", [None, "tensor"]]])
        assert sh["attn"]["wq"].spec == PartitionSpec(None, "tensor")
        # 3 doesn't divide over tensor=2: the weight replicates instead
        # of raising — per-weight fallback, the rest stays sharded
        assert sh["attn"]["wk"].spec == PartitionSpec(None, None)

    def test_default_rules_cover_builtin_naming(self, setup):
        model, params = setup
        specs = match_partition_rules(DEFAULT_RULES, params)
        assert specs["layers"]["attn"]["wq"] == PartitionSpec(None, None, "tensor")
        assert specs["layers"]["mlp"]["wo"] == PartitionSpec(None, "tensor", None)
        assert specs["embed"]["tok"] == PartitionSpec("tensor", None)
        assert specs["layers"]["ln1"]["scale"] == PartitionSpec()

    def test_module_inject_exports_family_rules(self):
        from deepspeed_tpu.module_inject import partition_rules

        table = partition_rules()
        assert table[-len(DEFAULT_RULES):] == tuple(DEFAULT_RULES)

    def test_parse_mesh_arg_forms(self):
        assert parse_mesh_arg("1:2") == {"data": 1, "tensor": 2}
        assert parse_mesh_arg("data=2,tensor=4") == {"data": 2, "tensor": 4}
        with pytest.raises(ValueError):
            parse_mesh_arg("3")

    def test_serving_mesh_subset_and_bounds(self):
        mesh = serving_mesh(1, 2)
        assert mesh.shape["tensor"] == 2 and mesh.devices.size == 2
        with pytest.raises(ValueError, match="devices"):
            serving_mesh(4, 4)  # 16 > the 8 virtual devices


class TestMeshConfig:
    def test_plain_dict_is_shape_and_block_form_parses(self):
        old = InferenceConfig.parse({"mesh": {"data": 1, "tensor": 2}})
        assert old.mesh.shape == {"data": 1, "tensor": 2}
        assert old.mesh.rules is None and not old.mesh.use_rules
        block = InferenceConfig.parse(
            {"mesh": {"shape": {"data": 1, "tensor": 4},
                      "rules": [["attn/", []]], "use_rules": True}})
        assert block.mesh.shape == {"data": 1, "tensor": 4}
        assert block.mesh.rules == [["attn/", []]] and block.mesh.use_rules

    def test_default_is_degenerate(self):
        cfg = InferenceConfig.parse({"dtype": "float32"})
        assert isinstance(cfg.mesh, MeshConfig)
        assert cfg.mesh.shape is None and not cfg.mesh.use_rules

    def test_engine_builds_subset_mesh_and_shards_params(self, setup):
        model, params = setup
        eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32",
                    "mesh": {"shape": {"data": 1, "tensor": 2}}})
        assert dict(eng.mesh.shape)["tensor"] == 2
        assert eng.mesh.devices.size == 2  # subset of the 8-device host
        wq = eng.params["layers"]["attn"]["wq"]
        assert "tensor" in [ax for ax in wq.sharding.spec if ax is not None]
        # each device holds half the heads dim
        shard_shapes = {s.data.shape for s in wq.addressable_shards}
        assert shard_shapes == {(2, 64, 32)}

    def test_rule_overrides_replicate_attention(self, setup):
        """use_rules=True: the whole-tree regex path — the user rule
        fronts DEFAULT_RULES, which still shard the rest."""
        model, params = setup
        eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32",
                    "mesh": {"shape": {"data": 1, "tensor": 2},
                             "use_rules": True, "rules": [["attn/", []]]}})
        wq = eng.params["layers"]["attn"]["wq"]
        assert all(ax is None for ax in wq.sharding.spec)
        wi = eng.params["layers"]["mlp"]["wi"]  # defaults still apply
        assert "tensor" in [ax for ax in wi.sharding.spec if ax is not None]

    def test_rules_overlay_per_leaf_on_annotated_model(self, setup):
        """rules WITHOUT use_rules on a model carrying logical_specs:
        only matched leaves change placement — unmatched params keep
        their annotation-derived sharding (one attention override must
        not strip the rest of the tree's intent)."""
        model, params = setup
        eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32",
                    "mesh": {"shape": {"data": 1, "tensor": 2},
                             "rules": [["attn/", []]]}})
        wq = eng.params["layers"]["attn"]["wq"]
        assert all(ax is None for ax in wq.sharding.spec)  # overridden
        wi = eng.params["layers"]["mlp"]["wi"]  # annotation survives
        assert "tensor" in [ax for ax in wi.sharding.spec if ax is not None]
        tok = eng.params["embed"]["tok"]       # annotation survives
        assert "tensor" in [ax for ax in tok.sharding.spec if ax is not None]


class TestStreamParity:
    """Sharded vs single-chip bitwise token-stream parity — the PR
    acceptance gate. The single-chip reference is served once per class
    (module params are shared, streams are deterministic)."""

    SUBS = None  # (tick, prompt, max_new) — prompts cross read buckets

    @classmethod
    def _submissions(cls):
        if cls.SUBS is None:
            cls.SUBS = list(zip((0, 0, 1, 3), _prompts((5, 20, 9, 7), 1),
                                (12, 10, 24, 8)))
        return cls.SUBS

    def test_greedy_parity_across_depths_and_widths(self, setup):
        subs = self._submissions()
        base = _serve(_cb(setup), subs)
        for tensor in (2, 4):
            for depth in (0, 1, 2):
                outs = _serve(_cb(setup, tensor=tensor, pipeline_depth=depth),
                              subs)
                for a, b in zip(base, outs):
                    np.testing.assert_array_equal(a, b)

    def test_sampled_parity(self, setup):
        subs = self._submissions()
        kw = dict(temperature=0.8, top_k=8, seed=3)
        base = _serve(_cb(setup, **kw), subs)
        for tensor in (2, 4):
            outs = _serve(_cb(setup, tensor=tensor, **kw), subs)
            for a, b in zip(base, outs):
                np.testing.assert_array_equal(a, b)

    def test_separate_prefill_and_burst_parity(self, setup):
        subs = self._submissions()
        base = _serve(_cb(setup), subs)
        sep = _serve(_cb(setup, tensor=2, fused_prefill=False), subs)
        burst = _serve(_cb(setup, tensor=2, fused_prefill=False,
                           tokens_per_tick=4), subs)
        for a, b, c in zip(base, sep, burst):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)

    def test_bucketed_pools_parity(self, setup):
        # mixed pool lengths: admission placement + per-pool tick
        # programs, each pool sharded on the same mesh
        subs = self._submissions()
        base = _serve(_cb(setup, max_slots=None, cache_len=None,
                          cache_buckets=[(2, 32), (2, 64)]), subs)
        outs = _serve(_cb(setup, tensor=2, max_slots=None, cache_len=None,
                          cache_buckets=[(2, 32), (2, 64)]), subs)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)

    def test_prefix_splice_parity(self, setup):
        prefix = np.arange(1, 11, dtype=np.int32)
        sufs = _prompts((4, 6), 5)

        def run(cb):
            pid = cb.register_prefix(prefix)
            rids = [cb.submit_with_prefix(pid, s, max_new_tokens=10)
                    for s in sufs]
            while cb.has_work():
                cb.step()
            res = cb.finished()
            return [res[r] for r in rids]

        base = run(_cb(setup))
        outs = run(_cb(setup, tensor=2))
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(a, b)

    def test_degenerate_mesh_is_bit_identical(self, setup):
        subs = self._submissions()
        base = _serve(_cb(setup), subs)
        one = _serve(_cb(setup, tensor=1), subs)
        for a, b in zip(base, one):
            np.testing.assert_array_equal(a, b)

    def test_engine_generate_parity_fused_and_migrating(self, setup):
        """InferenceEngine paths on the mesh: the fused whole-generation
        program and the bucket-migrated per-token loop both match their
        single-chip streams."""
        model, params = setup
        toks = np.asarray(_prompts((9,), 7)[0])[None, :]

        def gen(mesh_cfg, fused):
            cfg = {"dtype": "float32", "kv_read_floor": FLOOR,
                   "fused_generate": fused}
            if mesh_cfg:
                cfg["mesh"] = mesh_cfg
            eng = deepspeed_tpu.init_inference(model, params=params, config=cfg)
            return np.asarray(eng.generate(toks, max_new_tokens=40))

        for fused in (True, False):
            base = gen(None, fused)
            out = gen({"shape": {"data": 1, "tensor": 2}}, fused)
            np.testing.assert_array_equal(base, out)


class TestPerChipKvBytes:
    def _events(self, path):
        with open(path) as fh:
            return [json.loads(l) for l in fh if l.strip()]

    def test_continuous_event_is_per_chip(self, setup, tmp_path):
        """Exact per-chip accounting on a 1x2 virtual mesh: each chip
        holds half the kv heads, so every tick's row-read bytes halve —
        asserted against the same simulated-tick walk the single-chip
        test uses, divided by the shard width."""
        model, params = setup
        trace = tmp_path / "t2.jsonl"
        cb = ContinuousBatchingEngine(
            model, params=params,
            config={"dtype": "float32", "kv_read_floor": FLOOR,
                    "mesh": {"shape": {"data": 1, "tensor": 2}},
                    "telemetry": {"enabled": True, "trace_file": str(trace)}},
            max_slots=1, cache_len=64, donate_cache=False)
        assert kv_shard_width(cb.mesh, cb.cfg) == 2
        prompt = np.arange(2, 9, dtype=np.int32)  # len 7
        rid = cb.submit(prompt, max_new_tokens=12)
        while cb.has_work():
            cb.step()
        cb.finished()
        expect = 0
        for i in range(12):
            r = read_bucket(7 + i, 64, FLOOR)
            expect += kv_read_bytes_per_row(cb.cfg, r if r < 64 else 64, tp=2)
        ev = [e for e in self._events(trace)
              if e.get("path") == "continuous" and e.get("request") == rid][0]
        assert ev["kv_bytes_read"] == expect
        # per-chip bytes are EXACTLY half the replicated-cache bytes
        assert ev["kv_bytes_read"] * 2 == sum(
            kv_read_bytes_per_row(cb.cfg, min(read_bucket(7 + i, 64, FLOOR), 64))
            for i in range(12))

    def test_engine_event_is_per_chip(self, setup, tmp_path):
        model, params = setup
        trace = tmp_path / "eng.jsonl"
        eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "kv_read_floor": FLOOR,
                    "mesh": {"shape": {"data": 1, "tensor": 4}},
                    "telemetry": {"enabled": True, "trace_file": str(trace)}})
        toks = np.asarray(_prompts((6,), 9)[0])[None, :]
        eng.generate(toks, max_new_tokens=20)
        ev = [e for e in self._events(trace)
              if e.get("kind") == "inference_request"][-1]
        expect = decode_kv_bytes(eng.cfg, 6, 20, ev["cache_len"], FLOOR, tp=4)
        assert ev["kv_bytes_read"] == expect

    def test_non_divisible_heads_fall_back_to_full_rows(self, setup):
        mesh = serving_mesh(1, 2)
        cfg = TransformerConfig(vocab_size=64, hidden_size=60, num_layers=1,
                                num_heads=3, max_seq_len=64, dtype="float32")
        assert kv_shard_width(mesh, cfg) == 1  # 3 heads don't split 2 ways
        assert kv_read_bytes_per_row(cfg, 32, tp=1) == \
            kv_read_bytes_per_row(cfg, 32)


class TestTickStateSharding:
    def test_row_state_and_packed_fetch_replicated(self, setup):
        """The per-row scheduling state threads through ticks FULLY
        replicated on the mesh (the host fetch stays one coalesced get)
        while the pool KV cache shards its heads axis on ``tensor``."""
        cb = _cb(setup, tensor=2)
        rid = cb.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        while cb.has_work():
            cb.step()
        cb.finished()
        pool = cb._pools[0]
        assert pool.last_tok_dev.sharding.is_fully_replicated
        assert pool.done_dev.sharding.is_fully_replicated
        k_spec = jax.tree.leaves(pool.cache)[0].sharding.spec
        assert "tensor" in [ax for ax in k_spec if ax is not None]
