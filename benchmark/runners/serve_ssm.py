"""Serving cell of a model whose cache has two kinds of row: keys and values
in its attention layers, a state-space state (no time axis) in its Mamba-2
layers, beside expert layers of which the chip holds a share.
``runners/serve_hybrid.py``'s runner as it is (``serve_routed.py``'s loop,
clocks, warm-up, probes and reference comparison; ``serve_hybrid.py``'s
``controls_held`` and ``gap_p99_max``), reading the state pool's counters
under THIS mixer's names and costing them with this family's functions
(``serve_hybrid`` names ``gdn_*`` and ``costs_qwen3_next``):

``ssm_chunk_tokens``        real tokens a prefill chunk's scan took, a fused tick
``ssm_step_rows_per_tick``  rows whose state a tick stepped
``state_pool_gb``           the state pool as allocated
``state_bytes_share_pct``   state bytes a tick reads and writes back / all the
                            bytes a plain tick has to move (``costs_granitemoehybrid``)

The model has no positions at all (no rotary turn, no learned table; a
convolution and a recurrence that decays), so the streams scored against the
prompt one position early lose only the prompt's first token: that control
cannot fail by construction and is reported, not held (``controls_held``);
the permuted prompt carries the check.

The cell's group and the configuration's ``compare`` group are named after
this module (``serve_ssm``). A program whose ``tick_stats()`` lacks a counter
gives no reading for it, and the line leaves that metric out.
"""

from benchmark import costs_granitemoehybrid as costs
from benchmark.runners import serve_hybrid, serve_routed

NAME = "serve_ssm"


class Runner(serve_hybrid.Runner):
    def __init__(self, ctx):
        name = serve_hybrid.NAME
        cell = dict(ctx["cell"], **{name: ctx["cell"][NAME]})
        compare = dict(ctx["config"]["compare"], **{name: ctx["config"]["compare"][NAME]})
        super().__init__(dict(ctx, cell=cell, config=dict(ctx["config"], compare=compare)))

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        # serve_routed's observations, not serve_hybrid's: its counters and costs are another mixer's
        result = serve_routed.Runner._measure(self, closed, seconds, t_open, t_close, t_end,
                                              setup_s, stats0, stats1)
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        obs = result["obs"]
        obs.update(
            ssm_chunk_tokens=per(delta("ssm_chunk_tokens"), delta("fused_prefill_ticks")),
            ssm_step_rows_per_tick=per(delta("ssm_step_rows"), delta("moe_ticks")),
            state_pool_gb=per(stats1.get("state_pool_bytes"), 1e9))
        if None not in (obs["ssm_step_rows_per_tick"], obs.get("moe_experts_hit_per_tick")):
            moved = costs.decode_tick(self.ctx["config"], self.ctx["cell"], obs)["bytes"]
            obs["state_bytes_share_pct"] = 100.0 * costs.state_bytes_tick(self.ctx["config"], obs) / moved
        return result
