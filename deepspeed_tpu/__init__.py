"""deepspeed_tpu: a TPU-native large-model training & inference framework.

Public API parity with the reference's ``deepspeed/__init__.py``:
``initialize()`` (:54), ``init_inference()`` (:251), ``init_distributed``
(comm/comm.py:526), ``add_config_arguments()`` (:228) — re-designed for
JAX/XLA execution (see runtime/engine.py for the execution-model notes).
"""

from deepspeed_tpu.version import __version__
from deepspeed_tpu import comm
from deepspeed_tpu.comm import init_distributed
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.runtime.config import TpuConfig, DeepSpeedConfig
from deepspeed_tpu.runtime.engine import TpuEngine, DeepSpeedEngine
from deepspeed_tpu.telemetry import compile_log
from deepspeed_tpu.utils.logging import logger, log_dist


@compile_log.phase("engine_init")
def initialize(
    args=None,
    model=None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    loss_fn=None,
    params=None,
    mpu=None,
    dist_init_required=None,
    collate_fn=None,
    config=None,
    config_params=None,
    mesh=None,
):
    """Create a training engine (reference: deepspeed/__init__.py:54).

    Model forms accepted:
      - an object with ``init(rng) -> params`` and ``loss(params, batch, rng)``
        (e.g. ``deepspeed_tpu.models.TransformerModel``), or
      - ``loss_fn(params, batch, rng)`` + ``params`` pytree (any JAX model).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    assert config is not None, "provide config= (dict or path to JSON)"

    if model is None:
        assert loss_fn is not None and params is not None, "provide model= or (loss_fn=, params=)"
        from deepspeed_tpu.runtime.engine import _FnModel

        model = _FnModel(loss_fn, params)
        params = None  # consumed; below, a non-None params means model+params

    # multi-controller rendezvous FIRST: every later step (config device
    # count, autotuner memory model, engine mesh) queries the backend, and
    # the first query pins it — joining the coordinator after that would
    # leave each process seeing only its local devices (reference analogue:
    # dist.init_process_group before any engine setup, engine.py:249)
    from deepspeed_tpu.comm.comm import _maybe_init_multi_controller

    _maybe_init_multi_controller()

    # elastic restart (dstpu --elastic, launcher/runner.py): resume from the
    # latest checkpoint at the current chip count before building a fresh
    # engine. elastic_resume re-enters initialize() with the guard env set.
    import os as _os

    if _os.environ.get("DSTPU_ELASTIC") == "1" and _os.environ.get("_DSTPU_ELASTIC_ACTIVE") != "1":
        import json as _json

        from deepspeed_tpu.elasticity import maybe_elastic_resume

        raw_cfg = config if isinstance(config, dict) else _json.load(open(config))
        engine = maybe_elastic_resume(raw_cfg, model=model)
        if engine is not None:
            return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler

    # autotuning block (reference: --autotuning run): fast-mode tuning picks
    # ZeRO stage / micro-batch / remat from the memory model before the
    # engine is built; measured mode is the Autotuner API (autotuning/)
    raw = config if isinstance(config, dict) else None
    if raw is not None and (raw.get("autotuning") or {}).get("enabled", False) \
            and hasattr(model, "cfg"):
        import jax as _jax

        from deepspeed_tpu.accelerator import get_accelerator
        from deepspeed_tpu.autotuning.autotuner import autotune_config

        try:
            hbm = get_accelerator().total_memory()
        except Exception:
            hbm = 0
        if not hbm or hbm <= 0:  # CPU backend reports no device memory
            hbm = 16e9
        config = autotune_config(model.cfg, raw, _jax.device_count(), hbm)

    # an explicit mesh fixes the device count (it may cover a subset of local
    # devices, e.g. an elastic shrink — elasticity/elastic_agent.py)
    cfg = TpuConfig(config, mesh_device_count=mesh.devices.size if mesh is not None else None)

    pipe_axis = cfg.mesh_axis_sizes().get("pipe", 1)
    if cfg.pipeline.stages > 1 or pipe_axis > 1 or _is_pipeline_model(model):
        if params is not None:
            # fail loudly: the pipeline engine re-builds per-stage weights
            # from its module specs, so an in-memory tree cannot be pinned —
            # silently training from a fresh init was the original trap
            raise NotImplementedError(
                "initialize(model=..., params=...) is not supported with the "
                "pipeline engine; initialize without params= and restore the "
                "weights with load_checkpoint()"
            )
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

        engine = PipelineEngine(
            model, cfg, optimizer=optimizer, lr_scheduler=lr_scheduler, training_data=training_data, mesh=mesh,
            collate_fn=collate_fn,
        )
    elif cfg.hybrid_engine.enabled:
        # RLHF engine: train step + compiled generate on shared weights
        # (reference: deepspeed/__init__.py:141 hybrid-engine dispatch)
        from deepspeed_tpu.runtime.hybrid_engine import TpuHybridEngine

        model = _maybe_pin_params(model, params)
        engine = TpuHybridEngine(
            model, cfg, optimizer=optimizer, lr_scheduler=lr_scheduler, training_data=training_data, mesh=mesh,
            collate_fn=collate_fn,
        )
    else:
        model = _maybe_pin_params(model, params)
        engine = TpuEngine(
            model,
            cfg,
            optimizer=optimizer,
            lr_scheduler=lr_scheduler,
            training_data=training_data,
            mesh=mesh,
            collate_fn=collate_fn,
        )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def _maybe_pin_params(model, params):
    """Honor caller-provided params with a model object (the reference
    wraps an ALREADY-initialized module, deepspeed/__init__.py:54; silently
    re-initializing from the seed was a trap): init() returns the given
    tree as the fp32 masters."""
    if params is None:
        return model
    from deepspeed_tpu.runtime.engine import _PinnedParamsModel

    return _PinnedParamsModel(model, params)


def _is_pipeline_model(model) -> bool:
    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    return isinstance(model, PipelineModule)


def init_inference(model=None, config=None, params=None, mesh=None,
                   draft_model=None, draft_params=None, seed: int = 0, **kwargs):
    """Create an inference engine (reference: deepspeed/__init__.py:251).

    ``kwargs`` are reference-style config fields (mp_size=, dtype=, ...)
    merged into ``config``; ``params``/``mesh``/``seed`` pass through to the
    engine (seed is an engine argument, NOT a config field — it controls
    model.init when no params are given). ``draft_model`` attaches a
    speculative-decoding draft engine.
    """
    from deepspeed_tpu.inference.engine import init_inference as _init

    if kwargs:
        merged = dict(config or {})
        merged.update(kwargs)
        config = merged
    return _init(model, config=config, params=params, mesh=mesh,
                 draft_model=draft_model, draft_params=draft_params, seed=seed)


def add_config_arguments(parser):
    """Inject --deepspeed / --deepspeed_config CLI args (reference
    deepspeed/__init__.py:228)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str, help="Path to JSON config")
    group.add_argument("--deepscale", default=False, action="store_true", help=argparse_suppress())
    group.add_argument("--local_rank", type=int, default=-1)
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS
