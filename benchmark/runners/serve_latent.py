"""Serving cell of a model whose cache is a latent pool (MLA: one latent and
one rotated key a token, the keys and the values of every head), beside
expert layers. ``runners/serve_routed.py``'s runner as it is (the same loop,
clocks, warm-up, probes and reference comparison, the routing and prefill
counters among the observations), plus what the latent pool's ticks count,
where per-layer metrics can read them (``serve._measure`` copies only the keys
it names):

``mla_row_keys_per_tick``   cached entries the rows' kernel read, a tick and layer
``mla_expand_tokens``       entries a chunk expanded into heads again, a fused
                            tick and layer
``latent_pool_gb``          the latent pool as allocated
``latent_bytes_share_pct``  latent bytes a tick's rows read / all the bytes a
                            plain tick has to move (``costs_glm4_moe_lite``)
``mla_expand_share_pct``    the expansion's operations / the chunk ticks'
                            attention operations, expansion included

``compare.controls_held`` and ``compare.gap_p99_max`` as
``runners/serve_hybrid.py`` has them (by import): every position of this
family is relative (rotary angles), so the streams scored against the prompt
one position early lose only the prompt's first token and that control cannot
fail by construction; the permuted prompt carries the check.

The cell's group and the configuration's ``compare`` group are named after
this module (``serve_latent``). A program whose ``tick_stats()`` lacks a
counter gives no reading for it, and the line leaves that metric out.
"""

from benchmark import costs_glm4_moe_lite as costs
from benchmark.runners import serve_hybrid, serve_routed

NAME = "serve_latent"


class Runner(serve_hybrid.Runner):
    def __init__(self, ctx):
        name = serve_routed.NAME
        cell = dict(ctx["cell"], **{name: ctx["cell"][NAME]})
        compare = dict(ctx["config"]["compare"], **{name: ctx["config"]["compare"][NAME]})
        serve_routed.Runner.__init__(
            self, dict(ctx, cell=cell, config=dict(ctx["config"], compare=compare)))

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        result = serve_routed.Runner._measure(self, closed, seconds, t_open, t_close, t_end,
                                              setup_s, stats0, stats1)
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        obs = result["obs"]
        expanded = delta("mla_expand_tokens")
        obs.update(
            mla_row_keys_per_tick=per(delta("mla_row_keys"), delta("ticks")),
            mla_expand_tokens=(0.0 if expanded == 0 else
                               per(expanded, delta("fused_prefill_ticks"))),
            latent_pool_gb=per(stats1.get("latent_pool_bytes"), 1e9))
        config = self.ctx["config"]
        if None not in (obs["mla_row_keys_per_tick"], obs.get("moe_experts_hit_per_tick")):
            moved = costs.decode_tick(config, self.ctx["cell"], obs)["bytes"]
            obs["latent_bytes_share_pct"] = 100.0 * costs.latent_bytes_tick(config, obs) / moved
        if None not in (obs["mla_expand_tokens"], obs.get("chunk_pairs_full")):
            obs["mla_expand_share_pct"] = costs.expand_share_pct(config, obs)
        return result
