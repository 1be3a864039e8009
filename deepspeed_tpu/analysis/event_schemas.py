"""Checked-in telemetry event schema registry.

One entry per trace-event ``kind`` the stack emits (``telemetry.emit``
sites across runtime/, inference/, serving/, telemetry/). The
telemetry-schema rule lints every emit site against this registry —
unknown kinds, missing required fields, type-inconsistent fields — and
``tests/unit/analysis/test_event_schemas.py`` asserts docs/telemetry.md
documents every field registered here, so the schema, the emit sites,
and the docs can only move together.

Types are names from :data:`TYPE_NAMES`; ``"number"`` accepts int or
float. A field may list alternatives as a tuple (``("dict", "null")``).
``required`` fields appear in every event of the kind; ``optional``
fields are conditional. The envelope fields the hub/writer stamp on
every event (``role``/``ts``/``schema``/``kind``) live in
:data:`ENVELOPE_FIELDS`, not per-kind.
"""

TYPE_NAMES = frozenset(
    {"int", "float", "number", "str", "bool", "dict", "list", "null"})

# stamped by Telemetry.emit / TraceWriter.write, never by emit sites
ENVELOPE_FIELDS = {
    "role": "str",      # "train" | "inference"
    "ts": "number",     # wall-clock seconds
    "schema": "int",    # trace schema version (trace.SCHEMA_VERSION)
    "kind": "str",
}

EVENT_SCHEMAS = {
    "train_step": {
        "required": {
            "step": "int",
            "micro_steps": "int",
            "samples": "int",
            "fwd_ms": "number",
            "bwd_ms": "number",
            "step_ms": "number",
            "iter_ms": "number",
            "samples_per_sec": "number",
            "avg_samples_per_sec": "number",
            "lr": "number",
            "loss_scale": "number",
            "grad_norm": "number",
            "overflow": "bool",
            "skipped_steps": "int",
            "mfu": "number",
            "model_flops_per_step": "number",
            "comm_bytes": "dict",
            "comm_bytes_total": "number",
        },
        "optional": {
            "loss": "number",
            "tokens_per_sec": "number",
            # a model that counts beside its loss (engine.moe_stats()): totals so far
            "moe_assignments": "int",
            "moe_held_assignments": "int",
            "moe_expert_tokens_most": "int",
            "moe_expert_layers": "int",
            "moe_experts_hit": "int",
        },
    },
    "comm_summary": {
        "required": {"step": "int", "ops": "dict"},
        "optional": {},
    },
    "inference_request": {
        "required": {
            "request": "int",
            "path": "str",
            "batch": "int",
            "prompt_tokens": "int",
            "new_tokens": "int",
        },
        "optional": {
            "total_ms": "number",
            "ttft_ms": "number",
            "decode_tokens_per_sec": "number",
            "tokens_per_sec": "number",
            "cache_len": "int",
            "compile_cache_hit": "bool",
            "kv_dtype": "str",
            "kv_bytes_read": "int",
            "kv_bytes_per_token": "number",
            "cache_utilization": "number",
            "queue_ms": "number",
            "priority": "int",
            "tenant": "str",
            "deadline_ms": "number",
            "deadline_met": "bool",
            "recoveries": "int",
            "recovered_finish": "bool",
            "replica": "str",
            "spec_gamma": "int",
            "spec_drafted": "int",
            "spec_accepted": "int",
            "trace_id": "str",
        },
    },
    "span": {
        # request-scoped tracing (telemetry/spans.py write side,
        # telemetry/timeline.py read side): one closed span per line,
        # kinds enumerated in timeline.SPAN_KINDS (queue | admission |
        # prefill_chunk | decode_window | spec_verify_round | migration |
        # recovery_replay | drain_wait | train_step | train_retry |
        # train_rebuild). t0/t1 are monotonic-clock seconds in one clock
        # domain per trace file; parent_id stitches causality (absent on
        # roots); attrs carries kind-specific detail.
        "required": {
            "span": "str",
            "trace_id": "str",
            "span_id": "str",
            "t0": "number",
            "t1": "number",
            "dur_ms": "number",
        },
        "optional": {
            "parent_id": "str",
            "attrs": "dict",
            "replica": "str",
        },
    },
    "serving_event": {
        # discriminated by "event": shed | expired | cancelled | drain |
        # resume; every other field is event-specific
        "required": {"event": "str"},
        "optional": {
            "reason": "str",
            "request": "int",
            "detail": "str",
            "queue_ms": "number",
            "retry_after_s": "number",
            "queue_depth": "int",
            "running": "int",
            "committed_tokens": "int",
            "prompt_tokens": "int",
            "need_tokens": "int",
            "tokens_emitted": "int",
            "deadline_ms": "number",
            "replica": "str",
        },
    },
    "router_event": {
        # fleet router lifecycle (serving/router.py), discriminated by
        # "event": route | spillover | shed | backoff | migrated |
        # rebalanced | rebalance | replica_added | replica_dead |
        # replica_drained | drain | kill | replica_recovering |
        # replica_recovered | replica_failed | rolling_restart |
        # rolling_restart_done
        "required": {"event": "str"},
        "optional": {
            "replica": "str",
            "from_replica": "str",
            "to_replica": "str",
            "request": "int",
            "reason": "str",
            "detail": "str",
            "health": "str",
            "verdict": "str",
            "retry_after_s": "number",
            "attempts": "int",
            "need_tokens": "int",
            "tokens_emitted": "int",
            "gen_base": "int",
            "migrated": "int",
            "lost": "int",
            "replicas": "int",
            "tick": "int",
        },
    },
    "fleet_scale": {
        # fleet autoscaler journal (serving/autoscaler.py) plus the
        # scenario marker (serving/scenarios.py), discriminated by
        # "event": autoscaler | scenario | scale_up | scale_down |
        # scale_down_skipped | degrade
        "required": {"event": "str"},
        "optional": {
            "replica": "str",
            "replicas": "int",
            "reason": "str",
            "from_level": "int",
            "to_level": "int",
            "queue_depth": "int",
            "shed_recent": "int",
            "committed_frac": "number",
            "breakers_open": "int",
            "tick": "int",
            "min_replicas": "int",
            "max_replicas": "int",
            "cooldown_s": "number",
            "rebalanced": "int",
            "scenario": "str",
            "requests": "int",
            "seed": "int",
        },
    },
    "serving_tick": {
        "required": {
            "dispatch_ms": "number",
            "block_ms": "number",
            "inflight": "int",
            "emitted": "int",
            "wasted": "int",
            "fused_prefill": "bool",
        },
        "optional": {
            "replica": "str",
            "spec_gamma": "int",
            "spec_drafted": "int",
            "spec_accepted": "int",
            # a layer plan's counters, of the ticks the step retired (tick_stats() sums them)
            "moe_expert_layers": "int",
            "moe_experts_hit": "int",
            "moe_buffer_rows": "int",
            "moe_filled_rows": "int",
            "ssm_chunk_tokens": "int",
            "ssm_step_rows": "int",
        },
    },
    "serving_fault": {
        # discriminated by "event": fault | retried | retry_failed |
        # rebuild | rebuild_failed | breaker | unrecoverable
        "required": {"event": "str"},
        "optional": {
            "error": "str",
            "detail": "str",
            "poisoned": "bool",
            "consecutive": "int",
            "attempt": "int",
            "recovery_ms": "number",
            "readmitted": "int",
            "lost_ticks": "int",
            "degraded": "bool",
            "mesh": ("dict", "null"),
            "rebuilds": "int",
            "state": "str",
            "outage_ms": "number",
            "requests_lost": "int",
            "replica": "str",
        },
    },
    "train_fault": {
        # training-column fault/recovery lifecycle (runtime/resilience.py
        # TrainSupervisor + runtime/engine.py checkpoint refusal),
        # discriminated by "event": fault | retried | rebuild |
        # snapshot | ckpt_torn | ckpt_refused | failed
        "required": {"event": "str"},
        "optional": {
            "error": "str",
            "detail": "str",
            "step": "int",
            "micro": "int",
            "attempt": "int",
            "poisoned": "bool",
            "source": "str",        # rebuild provenance: memory | disk | cold
            "resume_step": "int",
            "replayed_steps": "int",
            "recovery_ms": "number",
            "checkpoint_ms": "number",
            "rebuilds": "int",
            "degraded": "bool",
            "world_size": "int",
            "tag": "str",
            "reason": "str",
            "committed": "bool",
        },
    },
    "numeric_health": {
        # numerical-health sentinel lifecycle (runtime/resilience.py
        # TrainSupervisor + runtime/numerics.py NumericSentinel),
        # discriminated by "event": anomaly | quarantine | rewind |
        # sdc_probe
        "required": {"event": "str", "step": "int"},
        "optional": {
            "verdict": "str",       # suspect | corrupt
            "reasons": "list",      # anomaly-kind slugs
            "loss": "number",
            "grad_norm": "number",
            "grad_ratio": "number",
            "zscore": "number",
            "epoch": "int",
            "batch": "int",
            "resume_step": "int",
            "replayed_steps": "int",
            "rewind_ms": "number",
            "digest": "int",
            "match": "bool",
            "detail": "str",
        },
    },
    "memory_snapshot": {
        "required": {
            "reason": "str",
            "total_bytes": "int",
            "components": "dict",
        },
        "optional": {
            "limit_bytes": "int",
            "headroom_bytes": "int",
            "programs": "dict",
            "replica": "str",
        },
    },
    "compile_event": {
        "required": {
            "family": "str",
            "key": "str",
            "compile_ms": "number",
            "recompile": "bool",
        },
        # the build journal's split of compile_ms (= the first dispatch's
        # wall time; telemetry/compile_log.py), its phase and tick
        "optional": {
            "trace_ms": "number",
            "lower_ms": "number",
            "backend_ms": "number",
            "load_ms": "number",
            "other_ms": "number",
            "cache_hit": "bool",
            "phase": "str",
            "tick": "int",
            "cache_alloc": "int",
            "replica": "str",
        },
    },
}


def known_kinds():
    return frozenset(EVENT_SCHEMAS)


def schema_for(kind: str):
    """{"required": {...}, "optional": {...}} or None for unknown kinds."""
    return EVENT_SCHEMAS.get(kind)


def field_types(kind: str, name: str):
    """Accepted concrete type names for ``kind.name`` (``"number"``
    expanded), or None when the field is not registered. Envelope fields
    resolve for every kind."""
    schema = EVENT_SCHEMAS.get(kind)
    if schema is None:
        return None
    declared = schema["required"].get(name, schema["optional"].get(name))
    if declared is None:
        declared = ENVELOPE_FIELDS.get(name)
    if declared is None:
        return None
    names = (declared,) if isinstance(declared, str) else tuple(declared)
    out = set()
    for t in names:
        out |= {"int", "float"} if t == "number" else {t}
    return frozenset(out)


def validate_registry():
    """Internal consistency: every declared type name is known, required
    and optional never overlap. Raises ValueError on violations (the
    registry test calls this)."""
    for kind, schema in EVENT_SCHEMAS.items():
        overlap = set(schema["required"]) & set(schema["optional"])
        if overlap:
            raise ValueError(f"{kind}: fields both required and optional: "
                             f"{sorted(overlap)}")
        for section in ("required", "optional"):
            for name, declared in schema[section].items():
                names = ((declared,) if isinstance(declared, str)
                         else tuple(declared))
                unknown = [t for t in names if t not in TYPE_NAMES]
                if unknown:
                    raise ValueError(
                        f"{kind}.{name}: unknown type name(s) {unknown}")
