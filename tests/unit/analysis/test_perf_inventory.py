"""ds-perf unit tests: the inventory fingerprint parsers, the sync-vs-
async collective accounting, the roofline cost model, and — the
load-bearing part — seeded regressions asserting the EXACT rule id +
program key the diff reports (a gate that fires under the wrong id or
on the wrong family trains people to ignore it).

Stdlib-only by contract: this file runs inside tools/ci_jaxfree_tests.py
(the CLI exercises run ds_perf.py's jax-free --diff side in
subprocesses), so nothing here may import jax, directly or transitively.
"""

import json
import os
import subprocess
import sys

import pytest

from deepspeed_tpu.analysis.core import SEVERITY_ERROR, SEVERITY_WARNING
from deepspeed_tpu.analysis.program.artifact import (
    ProgramArtifact,
    parse_collectives,
)
from deepspeed_tpu.analysis.program.costmodel import (
    overlap_readiness,
    peaks_for,
    predict,
    roofline_ms,
)
from deepspeed_tpu.analysis.program.inventory import (
    RULE_BLOAT,
    RULE_DRIFT,
    RULE_SYNC,
    RULE_UPCAST,
    build_inventory,
    diff_inventories,
    load_baseline,
    op_histogram,
    program_key,
    save_baseline,
)
from deepspeed_tpu.analysis.program.rules import (
    HotDotUpcastRule,
    SyncCollectiveRule,
    perf_rules,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DS_PERF = os.path.join(REPO, "tools", "ds_perf.py")

# compiled-HLO fixture with every collective form the accounting must
# split: one blocking all-reduce, one async (-start/-done) all-reduce,
# one blocking all-gather — per-shard operand bytes 32768 / 128 / 64
MIXED_HLO = """\
HloModule mixed, entry_computation_layout={(f32[128,64])->f32[128,64]}

ENTRY %main (p0: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %all-reduce.1 = f32[128,64]{1,0} all-reduce(f32[128,64]{1,0} %p0), to_apply=%add
  %all-reduce-start.2 = (f32[4,8]{1,0}, f32[4,8]{1,0}) all-reduce-start(f32[4,8]{1,0} %p0), to_apply=%add
  %all-reduce-done.3 = f32[4,8]{1,0} all-reduce-done((f32[4,8]{1,0}, f32[4,8]{1,0}) %all-reduce-start.2)
  %all-gather.4 = bf16[8,8]{1,0} all-gather(bf16[4,8]{1,0} %p0), dimensions={0}
  %fusion.5 = f32[128,64]{1,0} fusion(f32[128,64]{1,0} %all-reduce.1), kind=kLoop
  ROOT %copy.6 = f32[128,64]{1,0} copy(f32[128,64]{1,0} %fusion.5)
}
"""

STABLE_UPCAST = """\
module @jit_tick {
  func.func public @main(%arg0: tensor<4x8xf32>, %arg1: tensor<8x16xf32>) -> (tensor<4x16xf32>) {
    %0 = stablehlo.dot_general %arg0, %arg1, contracting_dims = [1] x [0] : (tensor<4x8xf32>, tensor<8x16xf32>) -> tensor<4x16xf32>
    return %0 : tensor<4x16xf32>
  }
}
"""


def _inv(**over):
    """A plausible tp2 tick-program inventory; kwargs override fields."""
    inv = {
        "family": "pool_tick",
        "variant": "plain",
        "tp": 2,
        "ops": {"fusion": 10, "convert": 48, "dot": 5, "copy": 7},
        "fusions": 10,
        "collectives": {"all-reduce": {"sync": 0, "async": 2,
                                       "bytes": 1024, "async_bytes": 1024}},
        "dots": {"count": 5, "signatures": {"bf16,bf16->f32": 5}},
        "program_bytes": 40000,
        "flops": 100000.0,
        "bytes_accessed": 50000.0,
        "peak_bytes": 80000,
    }
    inv.update(over)
    return inv


KEY = "program://pool_tick[plain]@tp2#greedy"


def _diff(cur_inv, base_inv=None, key=KEY):
    return diff_inventories({key: cur_inv}, {key: base_inv or _inv()})


def run_cli(*args, timeout=120):
    return subprocess.run([sys.executable, DS_PERF, *args],
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# parsers + artifact accounting (satellite: sync-vs-async split)
# ---------------------------------------------------------------------------

class TestParsers:
    def test_op_histogram_counts_every_instruction(self):
        ops = op_histogram(MIXED_HLO)
        assert ops["parameter"] == 1
        assert ops["all-reduce"] == 1
        # async halves are their own kinds: a dropped pair changes the shape
        assert ops["all-reduce-start"] == 1
        assert ops["all-reduce-done"] == 1
        assert ops["all-gather"] == 1
        assert ops["fusion"] == 1
        assert ops["copy"] == 1

    def test_parse_collectives_marks_async_form(self):
        ops = parse_collectives(MIXED_HLO)
        # the -done half never double-counts
        assert len(ops) == 3
        by_form = {(op.kind, op.async_form): op for op in ops}
        assert by_form[("all-reduce", False)].operand_bytes == 128 * 64 * 4
        assert by_form[("all-reduce", True)].operand_bytes == 4 * 8 * 4
        assert by_form[("all-gather", False)].operand_bytes == 4 * 8 * 2

    def test_collective_forms_splits_sync_async_bytes(self):
        art = ProgramArtifact(family="pool_tick", hlo_text=MIXED_HLO,
                              meta={"tp": 2})
        forms = art.collective_forms()
        assert forms["all-reduce"] == {
            "sync": 1, "async": 1,
            "bytes": 128 * 64 * 4 + 4 * 8 * 4, "async_bytes": 4 * 8 * 4}
        assert forms["all-gather"] == {"sync": 1, "async": 0,
                                       "bytes": 64, "async_bytes": 0}

    def test_build_inventory_fingerprint(self):
        art = ProgramArtifact(
            family="pool_tick", variant="plain",
            stable_text=STABLE_UPCAST, hlo_text=MIXED_HLO,
            memory={"argument_bytes": 100, "output_bytes": 40,
                    "temp_bytes": 20, "alias_bytes": 40, "code_bytes": 0},
            cost={"flops": 123.0, "bytes accessed": 456.0},
            meta={"tp": 2, "sampled": False})
        inv = build_inventory(art)
        assert inv["tp"] == 2
        assert inv["fusions"] == 1
        assert inv["dots"] == {"count": 1,
                               "signatures": {"f32,f32->f32": 1}}
        assert inv["collectives"]["all-reduce"]["async"] == 1
        # code_bytes == 0 (virtual-CPU backend) -> HLO text length proxy
        assert inv["program_bytes"] == len(MIXED_HLO)
        assert inv["peak_bytes"] == 100 + 40 + 20 - 40
        assert program_key(art) == KEY

    def test_program_key_disambiguates_sampler_mode(self):
        greedy = ProgramArtifact(family="pool_tick", variant="plain",
                                 meta={"tp": 1, "sampled": False})
        sampled = ProgramArtifact(family="pool_tick", variant="plain",
                                  meta={"tp": 1, "sampled": True})
        plain = ProgramArtifact(family="train_micro", meta={"tp": 1})
        assert program_key(greedy).endswith("#greedy")
        assert program_key(sampled).endswith("#sampled")
        assert program_key(plain) == "program://train_micro@tp1"


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_peaks_for_substring_match(self):
        assert peaks_for("TPU v5 lite").flops == 197e12
        assert peaks_for("TPU v5p").hbm_bw == 2765e9
        assert peaks_for("TPU v5 lite").hbm_bw == peaks_for("v5e").hbm_bw

    @pytest.mark.parametrize("kind", ["weird", "", "cpu"])
    def test_unknown_kind_raises(self, kind):
        # no default row and no host-CPU row: a device the table does
        # not know is an error, never priced as a v5e
        with pytest.raises(ValueError, match="no peaks row"):
            peaks_for(kind)
        with pytest.raises(ValueError, match="no peaks row"):
            predict(_inv(), kind)

    def test_roofline_is_max_of_resource_bounds(self):
        peaks = peaks_for("v5e")
        b = roofline_ms(197e9, 819e3, 200e3, peaks)
        assert b["mxu_ms"] == pytest.approx(1.0)
        assert b["hbm_ms"] == pytest.approx(0.001)
        assert b["ici_ms"] == pytest.approx(0.001)
        assert b["lb_ms"] == b["mxu_ms"]

    def test_overlap_readiness(self):
        assert overlap_readiness({}) is None
        assert overlap_readiness(
            {"all-reduce": {"bytes": 0, "async_bytes": 0}}) is None
        assert overlap_readiness(
            {"all-reduce": {"bytes": 100, "async_bytes": 25},
             "all-gather": {"bytes": 100, "async_bytes": 75}}) == 0.5

    def test_predict_names_binding_resource(self):
        pred = predict(_inv(flops=1e9, bytes_accessed=1e3), "v5e")
        assert pred["bound_by"] == "mxu"
        assert pred["collective_bytes"] == 1024
        assert pred["overlap_readiness"] == 1.0
        pred = predict(_inv(flops=1.0, bytes_accessed=1e9), "v5e")
        assert pred["bound_by"] == "hbm"


# ---------------------------------------------------------------------------
# inventory diff — the seeded regressions the gate must catch
# ---------------------------------------------------------------------------

class TestDiff:
    def test_clean_self_diff(self):
        assert _diff(_inv()) == []

    def test_tolerance_absorbs_recompile_noise(self):
        noisy = _inv(ops={"fusion": 10, "convert": 49, "dot": 5, "copy": 7},
                     program_bytes=41000, flops=101000.0)
        assert _diff(noisy) == []

    def test_dropped_async_pair_is_sync_collective(self):
        cur = _inv(collectives={"all-reduce": {
            "sync": 2, "async": 0, "bytes": 1024, "async_bytes": 0}})
        findings = _diff(cur)
        assert [(f.rule_id, f.path, f.code) for f in findings] == [
            (RULE_SYNC, KEY, "all-reduce async 2->0")]
        assert findings[0].severity == SEVERITY_ERROR

    def test_grown_collective_count_is_drift(self):
        cur = _inv(collectives={"all-reduce": {
            "sync": 0, "async": 4, "bytes": 2048, "async_bytes": 2048}})
        findings = _diff(cur)
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_DRIFT, "all-reduce count 2->4")]

    def test_fp32_upcast_dot_is_hot_dot_upcast(self):
        cur = _inv(dots={"count": 5, "signatures": {"f32,f32->f32": 5}})
        findings = _diff(cur)
        assert [(f.rule_id, f.path, f.code) for f in findings] == [
            (RULE_UPCAST, KEY, "dot f32,f32->f32 +5")]
        assert "narrower bf16,bf16->f32" in findings[0].message

    def test_same_width_signature_move_is_drift_not_upcast(self):
        cur = _inv(dots={"count": 5, "signatures": {"bf16,bf16->bf16": 5}})
        findings = _diff(cur)
        assert [f.rule_id for f in findings] == [RULE_DRIFT]
        assert "+5 bf16,bf16->bf16" in findings[0].message

    def test_grown_op_histogram_is_drift(self):
        cur = _inv(ops={"fusion": 10, "convert": 98, "dot": 5, "copy": 7})
        findings = _diff(cur)
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_DRIFT, "ops convert 48->98")]

    def test_program_growth_is_bloat_warning(self):
        findings = _diff(_inv(program_bytes=60000))
        assert [(f.rule_id, f.severity) for f in findings] == [
            (RULE_BLOAT, SEVERITY_WARNING)]
        assert "+50%" in findings[0].message

    def test_program_shrink_is_drift_not_bloat(self):
        findings = _diff(_inv(program_bytes=20000))
        assert [f.rule_id for f in findings] == [RULE_DRIFT]

    def test_flops_move_is_drift_either_direction(self):
        for flops in (200000.0, 10000.0):
            findings = _diff(_inv(flops=flops))
            assert [f.rule_id for f in findings] == [RULE_DRIFT], flops

    def test_stale_baseline_entry_is_a_finding(self):
        findings = diff_inventories({}, {KEY: _inv()})
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_DRIFT, f"stale {KEY}")]

    def test_unbaselined_program_is_a_finding(self):
        findings = diff_inventories({KEY: _inv()}, {})
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_DRIFT, f"unbaselined {KEY}")]

    def test_tp_change_short_circuits_field_diffs(self):
        cur = _inv(tp=1, flops=9e9, program_bytes=1)
        findings = _diff(cur)
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_DRIFT, "tp 2->1")]


# ---------------------------------------------------------------------------
# baseline file round-trip
# ---------------------------------------------------------------------------

class TestBaselineFile:
    def test_round_trip(self, tmp_path):
        path = str(tmp_path / "base.json")
        save_baseline(path, {KEY: _inv()}, device_kind="cpu")
        loaded = load_baseline(path)
        assert loaded == {KEY: _inv()}
        assert diff_inventories({KEY: _inv()}, loaded) == []

    def test_unsupported_version_raises(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text(json.dumps({"version": 99, "programs": {}}))
        with pytest.raises(ValueError, match="unsupported version"):
            load_baseline(str(path))

    def test_checked_in_baseline_loads_and_self_diffs_clean(self):
        programs = load_baseline(
            os.path.join(REPO, "tools", "ds_perf_baseline.json"))
        assert programs, "shipped baseline must not be empty"
        assert diff_inventories(programs, programs) == []
        # both widths the gate compiles are fingerprinted
        tps = {inv["tp"] for inv in programs.values()}
        assert tps == {1, 2}


# ---------------------------------------------------------------------------
# live perf rules (artifact-side, no baseline needed)
# ---------------------------------------------------------------------------

class TestLiveRules:
    def test_perf_rule_catalog(self):
        assert {r.id for r in perf_rules()} == {
            RULE_DRIFT, RULE_BLOAT, RULE_SYNC, RULE_UPCAST}

    def test_sync_collective_fires_on_declared_kind(self):
        art = ProgramArtifact(family="pool_tick", hlo_text=MIXED_HLO,
                              meta={"tp": 2})
        contract = {"perf": {"overlap_collectives": ("all-reduce",),
                             "dot_operands": "meta"}}
        findings = list(SyncCollectiveRule().check_program(art, contract))
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_SYNC, "sync all-reduce x1")]

    def test_sync_collective_quiet_at_tp1_and_undeclared(self):
        art1 = ProgramArtifact(family="pool_tick", hlo_text=MIXED_HLO,
                               meta={"tp": 1})
        contract = {"perf": {"overlap_collectives": ("all-reduce",)}}
        assert list(SyncCollectiveRule().check_program(art1, contract) or ()) == []
        art2 = ProgramArtifact(family="pool_tick", hlo_text=MIXED_HLO,
                               meta={"tp": 2})
        empty = {"perf": {"overlap_collectives": ()}}
        assert list(SyncCollectiveRule().check_program(art2, empty) or ()) == []

    def test_hot_dot_upcast_fires_outside_policy(self):
        art = ProgramArtifact(family="pool_tick", stable_text=STABLE_UPCAST,
                              meta={"tp": 1, "dot_dtypes": ("bf16",)})
        contract = {"perf": {"overlap_collectives": (),
                             "dot_operands": "meta"}}
        findings = list(HotDotUpcastRule().check_program(art, contract))
        assert [(f.rule_id, f.code) for f in findings] == [
            (RULE_UPCAST, "dot f32,f32->f32")]

    def test_hot_dot_upcast_quiet_inside_policy(self):
        art = ProgramArtifact(family="pool_tick", stable_text=STABLE_UPCAST,
                              meta={"tp": 1, "dot_dtypes": ("f32",)})
        contract = {"perf": {"dot_operands": "meta"}}
        assert list(HotDotUpcastRule().check_program(art, contract) or ()) == []


# ---------------------------------------------------------------------------
# the ds_perf CLI --diff side (subprocess; stays jax-free by contract)
# ---------------------------------------------------------------------------

def _write_doc(path, programs):
    path.write_text(json.dumps({"version": 1, "tool": "ds-perf",
                                "device_kind": "cpu",
                                "programs": programs}))
    return str(path)


class TestCli:
    def test_diff_clean_exits_zero(self, tmp_path):
        cur = _write_doc(tmp_path / "cur.json", {KEY: _inv()})
        base = _write_doc(tmp_path / "base.json", {KEY: _inv()})
        proc = run_cli("--diff", cur, "--baseline", base)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "clean" in proc.stdout
        assert "overlap" in proc.stdout  # readiness column always prints

    def test_diff_regression_exits_one_with_rule_id(self, tmp_path):
        bad = _inv(collectives={"all-reduce": {
            "sync": 2, "async": 0, "bytes": 1024, "async_bytes": 0}})
        cur = _write_doc(tmp_path / "cur.json", {KEY: bad})
        base = _write_doc(tmp_path / "base.json", {KEY: _inv()})
        proc = run_cli("--diff", cur, "--baseline", base)
        assert proc.returncode == 1
        assert "sync-collective" in proc.stdout
        assert KEY in proc.stdout

    def test_diff_sarif_carries_rule_ids(self, tmp_path):
        bad = _inv(dots={"count": 5, "signatures": {"f32,f32->f32": 5}})
        cur = _write_doc(tmp_path / "cur.json", {KEY: bad})
        base = _write_doc(tmp_path / "base.json", {KEY: _inv()})
        proc = run_cli("--diff", cur, "--baseline", base,
                       "--format", "sarif")
        assert proc.returncode == 1
        results = json.loads(proc.stdout)["runs"][0]["results"]
        assert [r["ruleId"] for r in results] == ["hot-dot-upcast"]

    @pytest.mark.parametrize("device_args, kind", [
        ((), "v5e"),  # the documents say "cpu" (where they were lowered);
                      # predictions are for the named target, default v5e
        (("--device", "v5p"), "v5p"),
    ])
    def test_diff_json_out_feeds_trace_report(self, tmp_path, device_args,
                                              kind):
        cur = _write_doc(tmp_path / "cur.json", {KEY: _inv()})
        base = _write_doc(tmp_path / "base.json", {KEY: _inv()})
        out = tmp_path / "report.json"
        proc = run_cli("--diff", cur, "--baseline", base,
                       "--json-out", str(out), *device_args)
        assert proc.returncode == 0
        report = json.loads(out.read_text())
        assert report["device_kind"] == kind
        pred = report["programs"][KEY]["predicted"]
        assert pred["device_kind"] == kind
        assert pred["lb_ms"] >= 0
        assert pred["bound_by"] in ("mxu", "hbm", "ici")

    def test_unknown_device_is_usage_error(self, tmp_path):
        cur = _write_doc(tmp_path / "cur.json", {KEY: _inv()})
        proc = run_cli("--diff", cur, "--device", "cpu")
        assert proc.returncode == 2
        assert "no peaks row" in proc.stderr

    def test_write_baseline_plus_diff_is_usage_error(self, tmp_path):
        cur = _write_doc(tmp_path / "cur.json", {KEY: _inv()})
        proc = run_cli("--diff", cur, "--write-baseline")
        assert proc.returncode == 2

    def test_list_rules_names_all_four(self):
        proc = run_cli("--list-rules")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        for rule_id in (RULE_DRIFT, RULE_BLOAT, RULE_SYNC, RULE_UPCAST):
            assert rule_id in proc.stdout

    def test_diff_side_never_imports_jax(self, tmp_path):
        """The read side must run on hosts without jax — same standalone
        contract (and probe) as tools/ds_lint.py."""
        cur = _write_doc(tmp_path / "cur.json", {KEY: _inv()})
        base = _write_doc(tmp_path / "base.json", {KEY: _inv()})
        probe = (
            "import sys; sys.argv=['ds_perf'];"
            "import runpy; ctx=runpy.run_path(%r, run_name='not_main');"
            "rc=ctx['main'](['--diff', %r, '--baseline', %r]);"
            "assert 'jax' not in sys.modules, 'jax was imported';"
            "assert 'deepspeed_tpu' not in sys.modules, 'package was imported';"
            "sys.exit(rc)"
        ) % (DS_PERF, cur, str(base))
        proc = subprocess.run([sys.executable, "-c", probe],
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
