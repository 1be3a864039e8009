"""Serving cell of a model whose layers are ONE sublayer each: a Mamba-2 mixer
(a state, no time axis), attention (keys and values) or an expert layer whose
routed experts work in a latent and of which the chip holds a share.
``runners/serve_ssm.py``'s runner as it is (``serve_routed.py``'s loop, clocks,
warm-up, probes and reference comparison; ``serve_hybrid.py``'s
``controls_held`` and ``gap_p99_max``; no positions at all, so only the
permuted prompt is a held control), costing the counters with THIS family's
functions (``costs_nemotron_h``) and reading the two counters its expert
layers add:

``ssm_chunk_tokens``        real tokens a prefill chunk's scan took, a fused tick
``ssm_step_rows_per_tick``  rows whose state a tick stepped
``state_pool_gb``           the state pool as allocated
``state_bytes_share_pct``   state bytes a tick reads and writes back / all the
                            bytes a plain tick has to move
``moe_expert_layers_per_tick``  expert layers a tick ran (not every layer is one)
``moe_buffer_fill_pct``     rows an assignment filled / rows of the sorted buffer
                            the grouped matmuls walked (each expert's rows padded
                            to whole row tiles), over the window's ticks

The cell's group and the configuration's ``compare`` group are named after
this module (``serve_latent_moe``). A program whose ``tick_stats()`` lacks a
counter gives no reading for it, and the line leaves that metric out.
"""

from benchmark import costs_nemotron_h as costs
from benchmark.runners import serve_routed, serve_ssm

NAME = "serve_latent_moe"


class Runner(serve_ssm.Runner):
    def __init__(self, ctx):
        name = serve_ssm.NAME
        cell = dict(ctx["cell"], **{name: ctx["cell"][NAME]})
        compare = dict(ctx["config"]["compare"], **{name: ctx["config"]["compare"][NAME]})
        super().__init__(dict(ctx, cell=cell, config=dict(ctx["config"], compare=compare)))

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        # serve_routed's observations: serve_ssm's are costed with another family's functions
        result = serve_routed.Runner._measure(self, closed, seconds, t_open, t_close, t_end,
                                              setup_s, stats0, stats1)
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        obs, filled = result["obs"], delta("moe_filled_rows")
        obs.update(
            ssm_chunk_tokens=per(delta("ssm_chunk_tokens"), delta("fused_prefill_ticks")),
            ssm_step_rows_per_tick=per(delta("ssm_step_rows"), delta("moe_ticks")),
            state_pool_gb=per(stats1.get("state_pool_bytes"), 1e9),
            moe_expert_layers_per_tick=per(delta("moe_expert_layers"), delta("moe_ticks")),
            moe_buffer_fill_pct=per(None if filled is None else 100.0 * filled, delta("moe_buffer_rows")))
        if None not in (obs["ssm_step_rows_per_tick"], obs.get("moe_experts_hit_per_tick")):
            moved = costs.decode_tick(self.ctx["config"], self.ctx["cell"], obs)["bytes"]
            obs["state_bytes_share_pct"] = 100.0 * costs.state_bytes_tick(self.ctx["config"], obs) / moved
        return result
