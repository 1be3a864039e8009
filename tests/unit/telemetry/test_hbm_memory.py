"""HBM memory accounting (telemetry/memory.py) + the compile flight
recorder (telemetry/compile_log.py): exact-bytes asserts for the
params/KV component split on the virtual mesh (tp=1 and tp=2 PER-CHIP),
headroom math, the memory_snapshot / compile_event trace kinds on engine
build and forced bucket migration, and recompile flagging through
cached_fn eviction."""

import json

import numpy as np
import pytest

import jax

from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.telemetry import Telemetry, TelemetryConfig, read_trace
from deepspeed_tpu.telemetry import memory as hbm

LIMIT = 100_000_000  # deterministic headroom on the CPU virtual mesh


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=64, dtype="float32")
    model = TransformerModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _tele_cfg(tmp_path, name):
    return {"enabled": True, "trace_file": str(tmp_path / name),
            "hbm_limit_bytes": LIMIT}


def _events(path, kind):
    return [e for e in read_trace(str(path)) if e.get("kind") == kind]


def _spec_width(mesh, sharding):
    """Independent per-chip divisor: the product of the mesh-axis sizes a
    leaf's PartitionSpec actually uses (1 = replicated)."""
    width = 1
    for entry in tuple(sharding.spec):
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        for ax in axes:
            width *= mesh.shape[ax]
    return width


def _expected_param_bytes(engine):
    leaves = jax.tree.leaves(engine.params)
    shardings = jax.tree.leaves(engine.param_shardings)
    assert len(leaves) == len(shardings)
    return sum(leaf.nbytes // _spec_width(engine.mesh, sh)
               for leaf, sh in zip(leaves, shardings))


def _expected_kv_bytes(cfg, slots, length, tp):
    assert cfg.kv_heads % tp == 0
    per = cfg.num_layers * slots * length * (cfg.kv_heads // tp) * cfg.head_dim
    return 2 * per * np.dtype(cfg.jnp_dtype).itemsize  # K and V


# -- exact component split on the virtual mesh -------------------------
def test_exact_bytes_tp1(setup, tmp_path):
    cfg, model, params = setup
    cb = ContinuousBatchingEngine(
        model, params=params,
        config={"dtype": "float32",
                "telemetry": _tele_cfg(tmp_path, "tp1.jsonl")},
        max_slots=3, cache_len=32)
    comps = cb.hbm_components()
    assert comps["params"] == _expected_param_bytes(cb._eng)
    assert comps["kv_cache"] == _expected_kv_bytes(cb.cfg, 3, 32, tp=1)
    assert comps["tick_state"] == 2 * 3 * 4  # last_tok + done, int32/slot
    # a registered prefix pins a bucket cache: kv_cache grows by exactly it
    cb.register_prefix(np.arange(1, 6, dtype=np.int32))
    grown = cb.hbm_components()
    assert (grown["kv_cache"] - comps["kv_cache"]
            == _expected_kv_bytes(cb.cfg, 1, 16, tp=1))  # bucket(5) = 16
    # the build memory_snapshot carries the same numbers + the headroom
    snaps = _events(tmp_path / "tp1.jsonl", "memory_snapshot")
    build = [s for s in snaps if s["reason"] == "build"
             and "kv_cache" in s["components"]]
    assert build and build[-1]["components"] == comps
    assert build[-1]["limit_bytes"] == LIMIT
    assert build[-1]["headroom_bytes"] == LIMIT - sum(comps.values())
    reg = cb.telemetry.registry.dump()["gauges"]
    assert reg["hbm_bytes{component=params}"] == comps["params"]
    # gauges reflect the last SNAPSHOT (build) — live prefix growth shows
    # up in hbm_components()/statusz, gauges update on the next snapshot
    assert reg["hbm_total_bytes"] == sum(comps.values())


def test_exact_bytes_tp2_per_chip(setup, tmp_path):
    cfg, model, params = setup
    if jax.device_count() < 2:
        pytest.skip("needs the virtual multi-device mesh")
    cb = ContinuousBatchingEngine(
        model, params=params,
        config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 2}},
                "telemetry": _tele_cfg(tmp_path, "tp2.jsonl")},
        max_slots=2, cache_len=32)
    comps = cb.hbm_components()
    # per-chip: tensor-sharded leaves divide by 2, replicated ones do not
    expected_params = _expected_param_bytes(cb._eng)
    assert comps["params"] == expected_params
    assert expected_params < sum(l.nbytes
                                 for l in jax.tree.leaves(cb._eng.params))
    # the KV cache shards its heads axis over tensor=2: half per chip
    assert comps["kv_cache"] == _expected_kv_bytes(cb.cfg, 2, 32, tp=2)
    # threaded tick state is replicated: full size on every chip
    assert comps["tick_state"] == 2 * 2 * 4


def test_headroom_and_host_leaves():
    tele = Telemetry(TelemetryConfig(enabled=True, trace_file="",
                                     hbm_limit_bytes=1000))
    assert hbm.headroom_bytes(tele, {"a": 300, "b": 100}) == 600
    assert hbm.leaf_device_bytes(np.zeros(8, np.float32)) == 0  # host, not HBM
    assert hbm.tree_device_bytes(None) == 0
    assert hbm.program_memory(object()) == {}  # no memory_analysis: empty
    # no limit configured and no backend stats (CPU): headroom unknown
    tele2 = Telemetry(TelemetryConfig(enabled=True, trace_file=""))
    assert hbm.headroom_bytes(tele2, {"a": 1}) is None


# -- forced bucket migration: snapshot + recompile-flagged event -------
def test_migration_emits_snapshot_and_recompile_event(setup, tmp_path):
    cfg, model, params = setup
    trace = tmp_path / "mig.jsonl"
    eng = InferenceEngine(
        model, params=params,
        config={"dtype": "float32", "fused_generate": False,
                "kv_tight_read": True, "kv_read_floor": 16,
                "telemetry": _tele_cfg(tmp_path, "mig.jsonl")})
    prompt = np.arange(1, 6, dtype=np.int32)[None]  # alloc starts bucket(6)=16
    eng.generate(prompt, max_new_tokens=40)         # walks 16 -> 32 -> 64
    snaps = _events(trace, "memory_snapshot")
    migs = [s for s in snaps if s["reason"] == "migration"]
    assert len(migs) == 2
    # each migration snapshot carries the GROWN allocation exactly
    for s, alloc in zip(migs, (32, 64)):
        assert s["components"]["kv_cache"] == _expected_kv_bytes(
            eng.cfg, 1, alloc, tp=1)
        assert s["components"]["params"] == _expected_param_bytes(eng)
    compiles = _events(trace, "compile_event")
    # the decode family first-compiles once, then each fresh migration
    # bucket re-traces it at runtime — recompile-flagged, alloc attached
    steps = [e for e in compiles if e["family"] == "decode_step"]
    assert [e["recompile"] for e in steps] == [False, True, True]
    assert [e.get("cache_alloc") for e in steps] == [None, 32, 64]
    assert all(e["compile_ms"] > 0 for e in steps)
    # the jitted grow programs journal too (one per target length)
    assert {e["key"] for e in compiles if e["family"] == "grow_cache"} \
        == {"(1, 32)", "(1, 64)"}
    reg = eng.telemetry.registry.dump()["counters"]
    assert reg["recompile_total{family=decode_step}"] == 2.0
    # an identical second request re-migrates (snapshots) but meets only
    # traced geometries: NO new compile_event, no phantom recompiles
    eng.generate(prompt, max_new_tokens=40)
    assert len(_events(trace, "memory_snapshot")) == len(snaps) + 2
    assert len(_events(trace, "compile_event")) == len(compiles)


def test_start_bucket_retrace_journaled(setup, tmp_path):
    """A request can pay a runtime re-trace at its STARTING allocation
    bucket (longer prompt, no migration involved) — the flight recorder
    journals that compile too, recompile-flagged with the alloc."""
    cfg, model, params = setup
    trace = tmp_path / "startb.jsonl"
    eng = InferenceEngine(
        model, params=params,
        config={"dtype": "float32", "fused_generate": False,
                "kv_tight_read": True, "kv_read_floor": 16,
                "telemetry": _tele_cfg(tmp_path, "startb.jsonl")})
    # traces bucket 16 (and fires the decode first-call timer)
    eng.generate(np.arange(1, 6, dtype=np.int32)[None], max_new_tokens=4)
    n0 = len(_events(trace, "compile_event"))
    # a longer prompt OPENS untraced bucket 32: real XLA re-trace
    long_prompt = np.arange(1, 21, dtype=np.int32)[None]
    eng.generate(long_prompt, max_new_tokens=4)
    steps = [e for e in _events(trace, "compile_event")[n0:]
             if e["family"] == "decode_step"]
    assert [(e["recompile"], e.get("cache_alloc")) for e in steps] \
        == [(True, 32)]
    # replayed: the bucket is traced now — no phantom event
    n1 = len(_events(trace, "compile_event"))
    eng.generate(long_prompt, max_new_tokens=4)
    assert len(_events(trace, "compile_event")) == n1


# -- recorder unit behavior --------------------------------------------
def test_record_build_resolves_hub_at_first_call():
    """The serving-rebuild flow: programs are built while the factory's
    telemetry is off, the shared hub is injected afterwards, and jit
    compiles lazily — so the wrapper must consult the hub at FIRST
    DISPATCH, not wrap time."""
    from deepspeed_tpu.telemetry.compile_log import record_build

    hub = {"tele": Telemetry(TelemetryConfig(enabled=False))}
    fn = lambda x: x * 2  # noqa: E731 — the wrapped "program"
    w = record_build(fn, "fam", (1,), hub=lambda: hub["tele"])
    assert w(2) == 4  # hub disabled at first call: journalled, no event
    hub["tele"] = Telemetry(TelemetryConfig(enabled=True, trace_file=""))
    assert w(3) == 6  # first call already burned: stays a passthrough
    assert "compile_event_total{family=fam}" \
        not in hub["tele"].registry.dump()["counters"]
    # program built before injection, dispatched after: journaled
    w2 = record_build(fn, "fam", (1,), hub=lambda: hub["tele"])
    assert w2(4) == 8 and w2(5) == 10
    dump = hub["tele"].registry.dump()
    assert dump["counters"]["compile_event_total{family=fam}"] == 1.0
    assert dump["histograms"]["compile_ms{family=fam}"]["count"] == 1


def test_cached_fn_eviction_flags_recompile():
    from deepspeed_tpu.inference.decoding import cached_fn

    class Holder:
        telemetry = Telemetry(TelemetryConfig(enabled=True, trace_file=""))

    holder = Holder()
    built = []

    def builder_for(key):
        def build():
            built.append(key)
            return lambda: key

        return build

    # slots=1: alternating keys evict each other; the SECOND build of a
    # key is a recompile the moment its wrapped entry is dispatched
    assert cached_fn(holder, "fam", "a", builder_for("a"), slots=1)() == "a"
    assert cached_fn(holder, "fam", "b", builder_for("b"), slots=1)() == "b"
    assert cached_fn(holder, "fam", "a", builder_for("a"), slots=1)() == "a"
    assert built == ["a", "b", "a"]
    dump = holder.telemetry.registry.dump()["counters"]
    assert dump["compile_event_total{family=fam}"] == 3.0
    assert dump["recompile_total{family=fam}"] == 1.0


def test_record_build_is_transparent():
    from deepspeed_tpu.telemetry.compile_log import record_build

    tele = Telemetry(TelemetryConfig(enabled=True, trace_file=""))

    class FnWithLower:
        def __call__(self, x):
            return x + 1

        def lower(self, x):  # the AOT surface engines rely on
            return "lowered"

    home = {}
    fn = FnWithLower()
    wrapped = record_build(fn, "f", (1,), hub=lambda: tele,
                           settle=lambda bare: home.update(fn=bare))
    assert wrapped.lower(0) == "lowered" and not home
    assert wrapped(1) == 2 and wrapped(2) == 3
    hist = tele.registry.dump()["histograms"]["compile_ms{family=f}"]
    assert hist["count"] == 1  # only the first call was timed
    # the cache that owns the program has the bare callable back
    assert home["fn"] is fn
