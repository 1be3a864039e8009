"""Training cell of a model with expert layers: ``runners/train.py``'s
runner as it is (the same steps, clocks and reference comparison), plus the
program's routing counters among the observations, where per-layer metrics
read them. ``engine.moe_stats()`` is read once before the window and once
after it, never inside (a read waits for the micro-steps in flight).

The comparison leaf by leaf holds every leaf's NORM. A leaf's PROJECTION
can go unheld (``proj_not_held`` in the configuration's ``compare`` group, a
pattern over a leaf's path): the held experts' own weights, where bfloat16
flips a token's fourth choice against its fifth and moves that token's row
of gradient from one expert to another (PERF.md section 6, PR 48). Those
gaps are reported (``grad_leaf_proj_gaps_not_held``)."""

import re

from benchmark import compare
from benchmark.runners import train

NAME = "train_routed"


class Runner(train.Runner):
    """The cell's ``train`` group is ``train.Runner``'s as it stands (the
    harness's readers and the tools read it under that name), and the
    configuration's ``compare`` group named after this module is handed to
    ``train.Runner`` as its own."""

    def __init__(self, ctx):
        groups = dict(ctx["config"]["compare"], train=ctx["config"]["compare"][NAME])
        super().__init__(dict(ctx, config=dict(ctx["config"], compare=groups)))

    def setup(self):
        super().setup()
        pattern = self.tol.get("proj_not_held")
        self.unheld = None
        if pattern and self.leaves is not None:
            # a projection of 0 on both sides is a gap of 0; the norms stay as they were read
            mute = lambda r: {k: (n, 0.0 if re.search(pattern, k) else p) for k, (n, p) in r.items()}
            mine, whole = self.leaves, self.reference
            self.leaves = mute(mine)

            def reference(*args, **kw):
                out = whole(*args, **kw)
                if not args and not kw:   # the sound reference: what the engine's own are read against
                    gaps = compare.worst_leaf_gaps(mine, out["leaf_readings"])[2]
                    self.unheld = {k: g[1] for k, g in gaps.items() if re.search(pattern, k)}
                return dict(out, leaf_readings=mute(out["leaf_readings"]))

            self.reference = reference

    def finish(self):
        verdict = super().finish()
        if self.unheld:
            verdict["fields"]["grad_leaf_proj_gaps_not_held"] = self.unheld
        return verdict

    def window(self, seconds, t_start):
        before = self.engine.moe_stats()
        result = super().window(seconds, t_start)   # ends in block_until_ready: nothing in flight
        after = self.engine.moe_stats()
        d = {k: after[k] - before[k] for k in after}
        micro = result["obs"]["micro_steps"]
        held_experts = self.ctx["config"]["deployment"]["held_experts"]["count"]
        mean = d["moe_held_assignments"] / (d["moe_expert_layers"] * held_experts)
        result["obs"].update(
            moe_assignments_per_micro_step=d["moe_assignments"] / micro,
            moe_held_assignments_per_micro_step=d["moe_held_assignments"] / micro,
            moe_held_share_pct=100.0 * d["moe_held_assignments"] / d["moe_assignments"],
            # the most one held expert got in a layer, a micro-step, over the mean a layer
            moe_load_imbalance=d["moe_expert_tokens_most"] / micro / mean,
            moe_experts_hit_per_layer=d["moe_experts_hit"] / d["moe_expert_layers"])
        return result
