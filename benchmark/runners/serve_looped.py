"""Serving cell of a looped model: one kind of layer walked several times
over the same weights, with keys and values kept a pass (a pool of passes x
layers layer-steps) and no expert layer. ``runners/serve_routed.py``'s runner
(the same loop, clocks, warm-up, probes and reference comparison; its routing
observations find no counter and stay empty) with ``serve_hybrid.py``'s
``controls_held`` and ``gap_p99_max`` in the verdict, plus what a looped
program's ticks count, where per-layer metrics can read them:

``loop_passes_per_tick``      passes run a tick (what a later early exit would lower)
``loop_kv_overread``          positions of ONE layer-step's pool the rows' attention
                              fetched (every slot to the read bucket) / those the
                              live rows hold: what a read to each row's length saves
``loop_kv_bytes_share_pct``   the live rows' keys and values over every layer-step /
                              all the bytes a plain tick has to move (``costs_ouro``)
``kv_pool_gb``                the pools as allocated, every pass's layer-steps

The cell's group and the configuration's ``compare`` group are named after
this module (``serve_looped``). A program whose ``tick_stats()`` lacks a
counter gives no reading for it, and the line leaves that metric out.
"""

from benchmark import costs_ouro as costs
from benchmark.runners import serve_hybrid, serve_routed

NAME = "serve_looped"


class Runner(serve_hybrid.Runner):
    def __init__(self, ctx):
        name = serve_hybrid.NAME
        cell = dict(ctx["cell"], **{name: ctx["cell"][NAME]})
        compare = dict(ctx["config"]["compare"], **{name: ctx["config"]["compare"][NAME]})
        super().__init__(dict(ctx, cell=cell, config=dict(ctx["config"], compare=compare)))

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        # serve_routed's observations, not serve_hybrid's: this model has no state pool
        result = serve_routed.Runner._measure(self, closed, seconds, t_open, t_close, t_end,
                                              setup_s, stats0, stats1)
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        obs = result["obs"]
        obs.update(
            loop_passes_per_tick=per(delta("loop_passes"), delta("ticks")),
            loop_kv_overread=per(delta("loop_kv_positions_read"), delta("loop_kv_positions_live")),
            kv_pool_gb=per(stats1.get("kv_pool_bytes"), 1e9))
        if obs["loop_passes_per_tick"] is not None and obs.get("mean_live_kv_tokens"):
            moved = costs.decode_tick(self.ctx["config"], self.ctx["cell"], obs)["bytes"]
            obs["loop_kv_bytes_share_pct"] = 100.0 * costs.kv_bytes_tick(self.ctx["config"], obs) / moved
        return result
