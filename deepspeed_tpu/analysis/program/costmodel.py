"""Analytic roofline cost model over compiled-program inventories.

ONE device-peaks table for the whole repo: ``_bench_impl.py``'s
``peak_flops()/peak_bw()`` MFU math, ``tools/perf_budget.py``'s
compile-time roofline, and ds-perf's predicted-time gate all read
:data:`DEVICE_PEAKS` — a perf number printed anywhere in this codebase
traces back to exactly one set of constants, and a device that is not
in the table is an error (:func:`peaks_for` raises), not a default.

The model is a lower bound, deliberately: for one dispatch of a program
whose inventory reports ``flops``, ``bytes_accessed`` and per-kind
collective bytes,

    predicted_ms >= max(flops / MXU_peak,
                        bytes_accessed / HBM_bw,
                        collective_bytes / ICI_bw)

A measured time BELOW the bound (beyond slack) means the two sides are
not describing the same program — the trace and the artifact disagree —
which ``ds_trace_report --perf`` surfaces as a WARN, mirroring the PR 10
comm cross-check. A measured time far above it is headroom, not an
error: the bound ignores overlap failures, launch overhead, and host
gaps by construction.

Overlap-readiness — the static metric ROADMAP item 3 must move — is the
fraction of a program's collective bytes compiled in async
(``-start/-done``) form: bytes the scheduler is *allowed* to hide under
compute. A sync-form collective serializes the stream no matter how the
runtime schedules it, so readiness is computable from the artifact text
alone, before any silicon run.

Stdlib-only (the ds-lint/ds-perf standalone loaders import this without
jax); callers pass the device kind string in.
"""

from dataclasses import dataclass

# ds-perf predictions quote ms at fixed precision; keep in one place so
# text reports, JSON reports and tests round identically
MS_DIGITS = 6


@dataclass(frozen=True)
class DevicePeaks:
    """Per-chip peak rates for one accelerator kind.

    ``flops``: dense bf16 MXU peak (flops/s). ``hbm_bw``: HBM bytes/s.
    ``ici_bw``: per-chip interconnect bytes/s (one direction, the rate a
    collective's per-chip operand bytes drain at in the bound).
    """

    kind: str
    flops: float
    hbm_bw: float
    ici_bw: float


# Substring-matched against ``jax.devices()[0].device_kind.lower()`` in
# declaration order — "v5 lite" is what the runtime reports for v5e, so
# both spellings ride the same row. flops/hbm_bw are the published
# per-chip peaks (Google Cloud TPU documentation: v5e 197 TFLOP/s bf16,
# 819 GB/s HBM); ici_bw is the per-chip one-direction ICI rate of the
# same generation. There is deliberately no row for a host CPU and no
# default: a rate priced on the wrong part is worse than no rate.
DEVICE_PEAKS = (
    DevicePeaks("v5 lite", 197e12, 819e9, 200e9),
    DevicePeaks("v5e", 197e12, 819e9, 200e9),
    DevicePeaks("v5p", 459e12, 2765e9, 600e9),
    DevicePeaks("v4", 275e12, 1228e9, 300e9),
    DevicePeaks("v6e", 918e12, 1640e9, 448e9),
)


def peaks_for(device_kind: str) -> DevicePeaks:
    """The peaks row for a ``device_kind`` string (case-insensitive
    substring match, e.g. 'TPU v5 lite' -> the v5e row). A kind the
    table does not know raises ``ValueError`` — never a default row."""
    kind = (device_kind or "").lower()
    for row in DEVICE_PEAKS:
        if row.kind in kind:
            return row
    raise ValueError(
        f"no peaks row for device kind {device_kind!r} (known: "
        f"{', '.join(r.kind for r in DEVICE_PEAKS)}); add the part to "
        f"DEVICE_PEAKS with its source rather than pricing it as another")


def roofline_ms(flops: float, bytes_accessed: float,
                collective_bytes: float, peaks: DevicePeaks) -> dict:
    """Per-resource lower bounds (ms) for one dispatch, and their max
    (``lb_ms`` — the predicted floor no real dispatch may beat)."""
    mxu = float(flops) / peaks.flops * 1e3
    hbm = float(bytes_accessed) / peaks.hbm_bw * 1e3
    ici = float(collective_bytes) / peaks.ici_bw * 1e3
    return {
        "mxu_ms": round(mxu, MS_DIGITS),
        "hbm_ms": round(hbm, MS_DIGITS),
        "ici_ms": round(ici, MS_DIGITS),
        "lb_ms": round(max(mxu, hbm, ici), MS_DIGITS),
    }


def overlap_readiness(collectives: dict):
    """Fraction of a program's collective bytes compiled in async form
    (``collectives`` is the inventory's ``{kind: {sync, async, bytes,
    async_bytes}}`` block). None when the program moves no collective
    bytes at all — a replicated program is not "0% ready", it has
    nothing to overlap."""
    total = sum(int(c.get("bytes", 0)) for c in collectives.values())
    if total <= 0:
        return None
    ready = sum(int(c.get("async_bytes", 0)) for c in collectives.values())
    return round(ready / total, 4)


def predict(inventory: dict, device_kind: str) -> dict:
    """Roofline prediction block for one program inventory dict (see
    :mod:`.inventory` for the shape) on the NAMED target ``device_kind``
    — where the program happened to be lowered says nothing about where
    it will run: the per-resource bounds, the binding resource, and
    overlap-readiness."""
    peaks = peaks_for(device_kind)
    coll = inventory.get("collectives") or {}
    coll_bytes = sum(int(c.get("bytes", 0)) for c in coll.values())
    bounds = roofline_ms(inventory.get("flops", 0.0),
                         inventory.get("bytes_accessed", 0.0),
                         coll_bytes, peaks)
    binding = max(("mxu_ms", "hbm_ms", "ici_ms"), key=lambda k: bounds[k])
    return {
        "device_kind": peaks.kind,
        **bounds,
        "bound_by": binding[:-3],  # 'mxu' | 'hbm' | 'ici'
        "collective_bytes": coll_bytes,
        "overlap_readiness": overlap_readiness(coll),
    }
