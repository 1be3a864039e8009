"""Serving cell of a model whose cache has two kinds of row: keys and values
in its full-attention layers, recurrent state (no time axis) in its
delta-rule layers, beside expert layers of which the chip holds a share.
``runners/serve_routed.py``'s runner as it is (the same loop, clocks,
warm-up, probes and reference comparison, the routing and prefill counters
among the observations), plus what the state pool's ticks count, where
per-layer metrics can read them (``serve._measure`` copies only the keys it
names):

``gdn_chunk_tokens``        real tokens a prefill chunk's scan took, a fused tick
``gdn_step_rows_per_tick``  rows whose recurrent state a tick stepped
``state_pool_gb``           the state pool as allocated
``state_bytes_share_pct``   state bytes a tick reads and writes back / all the
                            bytes a plain tick has to move (``costs_qwen3_next``)

``compare.controls_held`` (a list; absent: both) names the negative controls
that have to FAIL for ``correct``. Every position of this family is relative
(rotary angles, a convolution, a recurrence that decays), so the streams
scored against the prompt one position early lose only the prompt's first
token, thousands of positions back: that control cannot fail by construction
(0.0 of the tokens left the margin, my chip runs, PR 34) and is reported, not
held; the permuted prompt carries the check (0.87-0.94).

``compare.gap_p99_max`` (absent: no such limit) holds the 99th percentile of
the emitted tokens' gaps to the reference's top logit under a limit of its
own. The margin is set by precision (the float8 reference against the
program) and a fault that moves a hundredth of the tokens a little goes
through it; a recurrent state that was not zeroed is such a fault here: the
delta rule overwrites as it writes (beta ~ 0.5 over 128 key dimensions: 0.5 %
of a state is left after 2,048 tokens), so behind a prompt of 2,048 tokens and
more the leak reaches the scored tokens only through the early positions'
keys and values in the full-attention layers (PERF.md section 6, PR 34).

The cell's group and the configuration's ``compare`` group are named after
this module (``serve_hybrid``). A program whose ``tick_stats()`` lacks a
counter gives no reading for it, and the line leaves that metric out.
"""

from benchmark import costs_qwen3_next as costs
from benchmark.runners import serve_routed

NAME = "serve_hybrid"


class Runner(serve_routed.Runner):
    def __init__(self, ctx):
        name = serve_routed.NAME
        cell = dict(ctx["cell"], **{name: ctx["cell"][NAME]})
        compare = dict(ctx["config"]["compare"], **{name: ctx["config"]["compare"][NAME]})
        super().__init__(dict(ctx, cell=cell, config=dict(ctx["config"], compare=compare)))

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        result = super()._measure(closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1)
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        obs = result["obs"]
        obs.update(
            gdn_chunk_tokens=per(delta("gdn_chunk_tokens"), delta("fused_prefill_ticks")),
            gdn_step_rows_per_tick=per(delta("gdn_step_rows"), delta("moe_ticks")),
            state_pool_gb=per(stats1.get("state_pool_bytes"), 1e9))
        if None not in (obs["gdn_step_rows_per_tick"], obs.get("moe_experts_hit_per_tick")):
            moved = costs.decode_tick(self.ctx["config"], self.ctx["cell"], obs)["bytes"]
            obs["state_bytes_share_pct"] = 100.0 * costs.state_bytes_tick(self.ctx["config"], obs) / moved
        return result

    def finish(self):
        verdict = super().finish()
        f = verdict["fields"]
        held = self.tol.get("controls_held", sorted(f["control_share_outside_margin"]))
        p99_max = self.tol.get("gap_p99_max")
        ok = (f["share_within_margin"] >= f["share_within_required"]
              and (p99_max is None or f["gap_p99"] <= p99_max)
              and all(f["control_share_outside_margin"][c] >= f["control_share_required"] for c in held)
              and f["distinct_tokens"] >= f["distinct_required"]
              and not f["finished_with_wrong_token_count"])
        return dict(ok=ok, fields=dict(f, controls_held=held, gap_p99_max=p99_max))
