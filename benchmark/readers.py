"""Per-layer metrics from their files: ``layer_metrics/<name>.json`` -> value.

A metric file names a ``reader``: a ``reduction`` from the fixed set below
and its parameters. A later PR adds a per-layer metric by adding one file
(and its entry in ``BENCHMARK.json``); only a new reduction or cost function
is code, and a new cost function is a new function in a new module named by
``cost_module``. A reader that finds nothing to read returns None and the
harness leaves the metric out of the line.

Reductions (``ctx.obs`` = the run's host observations and counters;
``ctx.trace`` = the device trace, None in an untraced run):

``value``            ``obs[key] * scale``
``module_ms_per_call``  device ms per run of the programs matching ``pattern``
                     (for a pattern that names ONE program)
``module_ms_per``    summed device ms of the programs matching ``pattern``
                     per ``obs[per]`` (for a family whose members share a name)
``collective_ms_per``   ms with a collective in flight per ``obs[per]``
``exposed_share``    % of collective time in which no other op ran
``roofline``         % : max(flops/peak, bytes/peak) of ``cost``, x ``obs[per]``
                     over the summed device time of the ops matching
                     ``pattern`` or of the programs matching ``module_pattern``
``mfu``              % : ``cost`` flops per token x ``obs[tokens_per_s_key]``
                     (tokens per second and chip) over one chip's peak
"""

import dataclasses
import importlib
import json
import os
from typing import Optional

from benchmark.reduce import reductions as R


@dataclasses.dataclass
class Context:
    obs: dict
    config: dict
    cell: dict
    peaks: Optional[dict]  # this device kind's row of peaks.json; None off the chip
    chips: int
    trace: Optional[object] = None
    window: Optional[tuple] = None


def _cost(reader):
    module = importlib.import_module(reader.get("cost_module", "benchmark.costs"))
    return getattr(module, reader["cost"])


def _get(obs, key):
    value = obs.get(key)
    return None if value is None else float(value)


def evaluate(reader: dict, ctx: Context) -> Optional[float]:
    kind = reader["reduction"]
    obs = ctx.obs
    if ctx.peaks is None and kind in ("roofline", "mfu"):
        return None  # a rehearsal off the chip knows no peak: nothing is made up
    if kind == "value":
        v = _get(obs, reader["key"])
        return None if v is None else v * reader.get("scale", 1.0)
    if kind == "mfu":
        rate = _get(obs, reader["tokens_per_s_key"])
        if rate is None:
            return None
        flops = _cost(reader)(ctx.config, ctx.cell["train"]["seq"])
        return 100.0 * flops * rate / ctx.peaks["flops_per_s"]  # the rate is already per chip
    if ctx.trace is None:
        return None
    if kind == "module_ms_per_call":
        return R.module_ms_per_call(ctx.trace, reader["pattern"], ctx.window)
    if kind == "module_ms_per":
        ms, per = R.module_ms(ctx.trace, reader["pattern"], ctx.window), _get(obs, reader["per"])
        return None if ms is None or not per else ms / per
    if kind == "collective_ms_per":
        ms, per = R.collective_ms(ctx.trace, ctx.window)[0], _get(obs, reader["per"])
        return None if ms is None or not per else ms / per
    if kind == "exposed_share":
        share = R.collective_ms(ctx.trace, ctx.window)[1]
        return None if share is None else 100.0 * share
    if kind == "roofline":
        if "module_pattern" in reader:
            ms = R.module_ms(ctx.trace, reader["module_pattern"], ctx.window)
        else:
            ms = R.op_ms(ctx.trace, reader["pattern"], ctx.window)
        per = _get(obs, reader["per"])  # units of cost the summed time covers
        if ms is None or not per:
            return None
        cost = _cost(reader)(ctx.config, ctx.cell, obs)
        least_s = max(cost["flops"] / ctx.peaks["flops_per_s"],
                      cost["bytes"] / ctx.peaks["hbm_bytes_per_s"])
        return 100.0 * least_s * per / (ms / 1e3)
    raise ValueError(f"unknown reduction {kind!r}")


def load_metric(dirs, name: str) -> dict:
    for d in dirs:
        path = os.path.join(d, "layer_metrics", name + ".json")
        if os.path.exists(path):
            with open(path) as fh:
                return json.load(fh)
    raise FileNotFoundError(f"no layer_metrics/{name}.json under {dirs}")
