#!/usr/bin/env python
"""ds-perf launcher — static performance gate over the compiled XLA
program families: inventory fingerprints diffed against the checked-in
baseline, analytic roofline predictions, and overlap-readiness, on the
same virtual-CPU mesh ds-audit uses.

Two modes (docs/static_analysis.md "Performance audit"):

- **Live** (default): lowers + compiles the full family table
  (tp ∈ {1,2}), fingerprints every program
  (:mod:`deepspeed_tpu.analysis.program.inventory`), runs the live perf
  rules (sync-collective, hot-dot-upcast), and diffs the inventories
  against ``tools/ds_perf_baseline.json``. Needs jax.
- **``--diff CURRENT.json``**: compares two inventory JSON documents
  (a prior ``--json-out`` report or baseline file) with NO jax in the
  interpreter — the analysis package loads through the same standalone
  alias loader as ``tools/ds_lint.py``, so CI boxes without jax can run
  the read side (``tools/ci_jaxfree_tests.py`` proves it).

Accepting an intentional program change is ``--write-baseline`` — the
inventory baseline IS the accepted state (there is no findings-baseline
to park perf debt in; a drift is either fixed or consciously accepted
in review as a baseline diff).

Usage:
    python tools/ds_perf.py                        # live gate, text report
    python tools/ds_perf.py --format sarif         # CI annotation pairing
    python tools/ds_perf.py --json-out perf.json   # artifact for --diff /
                                                   #   ds_trace_report --perf
    python tools/ds_perf.py --diff perf.json       # jax-free re-diff
    python tools/ds_perf.py --write-baseline       # accept current programs
    python tools/ds_perf.py --device v5p           # predict at v5p peaks

Exit codes match ds-lint: 0 clean, 1 findings, 2 usage error.
"""

import argparse
import importlib
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG_DIR = os.path.join(REPO, "deepspeed_tpu", "analysis")
_DEFAULT_BASELINE = os.path.join(REPO, "tools", "ds_perf_baseline.json")
_VIRTUAL_DEVICES = 8
_ALIAS = "_ds_perf_analysis"


def _load_analysis():
    """The analysis package under an alias, WITHOUT importing
    ``deepspeed_tpu`` (and with it jax) — same standalone contract as
    tools/ds_lint.py."""
    if _ALIAS in sys.modules:
        return sys.modules[_ALIAS]
    spec = importlib.util.spec_from_file_location(
        _ALIAS,
        os.path.join(_PKG_DIR, "__init__.py"),
        submodule_search_locations=[_PKG_DIR],
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[_ALIAS] = module
    spec.loader.exec_module(module)
    return module


def _program_pkg():
    _load_analysis()
    return importlib.import_module(_ALIAS + ".program")


def _prepare_platform(max_width: int):
    """Force a CPU platform with enough virtual devices BEFORE jax
    initializes (see tools/ds_audit.py — the flag is read at first
    backend use)."""
    if "jax" in sys.modules:
        import jax

        if len(jax.devices()) >= max_width:
            return
        print(f"ds-perf: jax already initialized with "
              f"{len(jax.devices())} device(s) but --mesh needs "
              f"{max_width}; run in a fresh process", file=sys.stderr)
        raise SystemExit(2)
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
                    f"{max(_VIRTUAL_DEVICES, max_width)}").strip()


def _parse_meshes(spec: str):
    """'1:1,1:2' -> [(1, 1), (1, 2)] (same syntax as ds-audit)."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        if len(fields) != 2 or not all(f.isdigit() and int(f) >= 1
                                       for f in fields):
            raise ValueError(
                f"--mesh wants DATA:TENSOR[,DATA:TENSOR...], got {part!r}")
        out.append((int(fields[0]), int(fields[1])))
    if not out:
        raise ValueError("--mesh parsed to no meshes")
    return out


def build_parser():
    parser = argparse.ArgumentParser(
        prog="ds-perf",
        description="static cost model + compiled-program inventory "
                    "regression gate (the performance sibling of ds-audit)")
    parser.add_argument(
        "--mesh", default="1:1,1:2", metavar="DATA:TENSOR[,..]",
        help="serving-mesh widths to fingerprint (default 1:1,1:2 — the "
             "widths the checked-in baseline covers)")
    parser.add_argument(
        "--format", choices=("text", "json", "sarif"), default="text",
        dest="fmt", help="report format (default: text)")
    parser.add_argument(
        "--diff", metavar="CURRENT_JSON", default=None,
        help="diff this inventory document (a --json-out report or a "
             "baseline file) against the baseline WITHOUT lowering "
             "anything — runs jax-free")
    parser.add_argument(
        "--device", default="v5e", metavar="KIND",
        help="the TARGET part the roofline predictions are priced for, a "
             "row of costmodel.DEVICE_PEAKS (default: v5e). The programs "
             "lower on the virtual-CPU mesh; where they lowered is not "
             "where they will run")
    parser.add_argument(
        "--layers", type=int, default=1,
        help="tiny-model depth (the layer scan keeps the inventory "
             "depth-invariant; >1 only re-verifies that)")
    parser.add_argument(
        "--baseline", default=None, metavar="FILE",
        help=f"inventory baseline (default: "
             f"{os.path.relpath(_DEFAULT_BASELINE, REPO)} when present)")
    parser.add_argument(
        "--no-baseline", action="store_true",
        help="ignore any baseline; every program reports as unbaselined")
    parser.add_argument(
        "--write-baseline", action="store_true",
        help="rewrite the inventory baseline accepting every current "
             "program fingerprint")
    parser.add_argument(
        "--json-out", default=None, metavar="FILE",
        help="additionally write the full JSON report here (the CI "
             "artifact; also the input to ds_trace_report --perf and "
             "--diff)")
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the perf rule catalog and exit")
    return parser


def _build_report(findings, programs, device_kind, baselined_keys):
    by_rule = {}
    for f in findings:
        by_rule[f.rule_id] = by_rule.get(f.rule_id, 0) + 1
    return {
        "version": 1,
        "tool": "ds-perf",
        "device_kind": device_kind,
        "findings": [f.to_dict() for f in findings],
        "summary": {
            "programs": len(programs),
            "new": len(findings),
            "baselined_programs": baselined_keys,
            "by_rule": dict(sorted(by_rule.items())),
        },
        "programs": programs,
    }


def _print_text(report):
    """Findings, then the per-program prediction table — overlap-
    readiness per family is an acceptance surface (ROADMAP item 3 reads
    it here), so it prints in the default format."""
    for f in report["findings"]:
        print(f"{f['path']}: [{f['severity']}] {f['rule']}: {f['message']}")
    programs = report.get("programs") or {}
    if programs:
        name_w = max(len("program"), max(len(k) for k in programs))
        header = (f"{'program'.ljust(name_w)} {'flops':>12} {'bytes':>12} "
                  f"{'lb_ms':>10} {'bound':>6} {'overlap':>8}")
        print(header)
        print("-" * len(header))
        for key in sorted(programs):
            prog = programs[key]
            pred = prog.get("predicted") or {}
            ready = pred.get("overlap_readiness")
            print(f"{key.ljust(name_w)} "
                  f"{int(prog.get('flops', 0)):>12} "
                  f"{int(prog.get('bytes_accessed', 0)):>12} "
                  f"{pred.get('lb_ms', 0):>10.4f} "
                  f"{pred.get('bound_by', '-'):>6} "
                  f"{('-' if ready is None else format(ready, '.2f')):>8}")
    s = report["summary"]
    verdict = "clean" if not report["findings"] else "FAIL"
    print(f"ds-perf: {s['programs']} program(s) at "
          f"{report['device_kind']} peaks, {s['new']} "
          f"finding(s) — {verdict}")


def _render(report, fmt, prog_pkg) -> int:
    if fmt == "json":
        print(json.dumps(report, indent=2, sort_keys=True))
    elif fmt == "sarif":
        sarif_mod = importlib.import_module(_ALIAS + ".sarif") \
            if _ALIAS in sys.modules else None
        if sarif_mod is None:
            from deepspeed_tpu.analysis.sarif import render_sarif
        else:
            render_sarif = sarif_mod.render_sarif
        print(json.dumps(
            render_sarif(report, prog_pkg.perf_rules(), tool_name="ds-perf"),
            indent=2))
    else:
        _print_text(report)
    return 1 if report["findings"] else 0


def _load_programs(path):
    """{key: inventory} from a --json-out report, a baseline, or a bare
    programs mapping."""
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    if isinstance(data, dict) and "programs" in data:
        return dict(data["programs"] or {})
    if isinstance(data, dict):
        return dict(data)
    raise ValueError(f"{path}: not an inventory document")


def _check_device(device_kind, prog_pkg) -> bool:
    """False (after a usage message) when ``--device`` names a part the
    peaks table does not hold."""
    try:
        prog_pkg.peaks_for(device_kind)
    except ValueError as exc:
        print(f"ds-perf: --device: {exc}", file=sys.stderr)
        return False
    return True


def _attach_predictions(programs, device_kind, prog_pkg):
    """A ``predicted`` block per program (non-destructive copy)."""
    out = {}
    for key, inv in programs.items():
        entry = dict(inv)
        entry["predicted"] = prog_pkg.predict(inv, device_kind)
        out[key] = entry
    return out


def _resolve_baseline(args):
    if args.no_baseline:
        return None
    if args.baseline:
        return args.baseline
    return _DEFAULT_BASELINE if os.path.exists(_DEFAULT_BASELINE) else None


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)

    if args.list_rules:
        prog_pkg = _program_pkg()
        for rule in sorted(prog_pkg.perf_rules(), key=lambda r: r.id):
            print(f"{rule.id:24s} [{rule.severity}] {rule.description}")
        return 0

    if args.write_baseline and args.diff:
        print("ds-perf: --write-baseline needs the live table, not a "
              "--diff document (rerun without --diff)", file=sys.stderr)
        return 2

    if args.diff:
        # jax-free read side: both documents are pure data
        prog_pkg = _program_pkg()
        if not _check_device(args.device, prog_pkg):
            return 2
        inventory = importlib.import_module(_ALIAS + ".program.inventory")
        try:
            current = _load_programs(args.diff)
        except (OSError, ValueError, json.JSONDecodeError) as exc:
            print(f"ds-perf: cannot read {args.diff}: {exc}",
                  file=sys.stderr)
            return 2
        baseline_path = _resolve_baseline(args)
        baseline = {}
        if baseline_path is not None:
            try:
                baseline = inventory.load_baseline(baseline_path)
            except (OSError, ValueError, json.JSONDecodeError) as exc:
                print(f"ds-perf: cannot read baseline {baseline_path}: "
                      f"{exc}", file=sys.stderr)
                return 2
        findings = inventory.diff_inventories(current, baseline)
        programs = _attach_predictions(current, args.device, prog_pkg)
        report = _build_report(findings, programs, args.device,
                               len(set(current) & set(baseline)))
        if args.json_out:
            with open(args.json_out, "w", encoding="utf-8") as fh:
                json.dump(report, fh, indent=2, sort_keys=True)
                fh.write("\n")
        return _render(report, args.fmt, prog_pkg)

    # -- live mode: lower + compile the family table --------------------
    try:
        meshes = _parse_meshes(args.mesh)
    except ValueError as exc:
        print(f"ds-perf: {exc}", file=sys.stderr)
        return 2
    _prepare_platform(max(d * t for d, t in meshes))
    sys.path.insert(0, REPO)

    import jax

    import deepspeed_tpu.analysis.program as prog_pkg
    from deepspeed_tpu.analysis.program import ProgramAuditor, perf_rules
    from deepspeed_tpu.analysis.program import inventory as inventory_mod
    from deepspeed_tpu.analysis.program.families import (
        build_family_artifacts,
    )

    if not _check_device(args.device, prog_pkg):
        return 2

    # quiet the stack's stdout INFO logger for machine formats (see
    # ds_audit.py — must run AFTER the package import set the level)
    if args.fmt != "text":
        import logging

        logging.getLogger("deepspeed_tpu").setLevel(logging.WARNING)

    widths = sorted({t for _, t in meshes})
    artifacts = build_family_artifacts(
        tensor_widths=widths, donate=True, layers=args.layers)
    inventories = inventory_mod.build_inventories(artifacts)
    device_kind = jax.devices()[0].device_kind

    if args.write_baseline:
        path = args.baseline or _DEFAULT_BASELINE
        inventory_mod.save_baseline(path, inventories,
                                    device_kind=device_kind)
        print(f"ds-perf: wrote {len(inventories)} program fingerprint(s) "
              f"to {path}")
        return 0

    live = ProgramAuditor(rules=perf_rules()).audit(artifacts).findings
    baseline_path = _resolve_baseline(args)
    baseline = {}
    if baseline_path is not None:
        try:
            baseline = inventory_mod.load_baseline(baseline_path)
        except (OSError, ValueError, json.JSONDecodeError) as exc:
            print(f"ds-perf: cannot read baseline {baseline_path}: {exc}",
                  file=sys.stderr)
            return 2
    findings = sorted(
        live + inventory_mod.diff_inventories(inventories, baseline),
        key=lambda f: (f.path, f.rule_id, f.code))
    programs = _attach_predictions(inventories, args.device, prog_pkg)
    report = _build_report(findings, programs, args.device,
                           len(set(inventories) & set(baseline)))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return _render(report, args.fmt, prog_pkg)


if __name__ == "__main__":
    sys.exit(main())
