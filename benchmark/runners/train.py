"""Training cell: ``deepspeed_tpu.initialize`` -> ``engine.train_batch``.

Set-up: the engine does K optimizer steps on fixed rows from the seed; these
steps are the warm-up too, and their losses and first gradient norm are kept.
The rows are one micro-batch fed at every micro-step or, where the cell's
``compare`` group says ``"micro_batches": "distinct"``, as many micro-batches
as a step accumulates, every row different (a step that drops or counts twice
one of them then differs from the reference). With a leaf limit there
(``compare.LEAF_LIMITS``), the first gradient is also compared leaf by leaf,
as the optimizer got it: read back from its first moment after one step.

Window: fresh token batches from the seed, one optimizer step dispatched
ahead of the one being waited for, until the time is up; the window ends in
``block_until_ready`` on the parameters.

``correct`` is decided in ``finish()``, after the window and outside the
profiler, with the engine released (the harness has read the chip's peak
memory by then, so the peak and ``setup_s`` are the engine's alone): the
float32 reference trains the same K steps from the same start
(``model.init`` from the same key; the checksums prove it) and the kept
losses and gradient norm are held to it.
"""

import gc
import itertools
import math
import time

import numpy as np

from benchmark import compare, trafficgen
from benchmark.harness import BenchmarkError, span


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx["cell"]["train"]

    def _ds_config(self, n, engine_seed):
        t = self.t
        return {
            "train_micro_batch_size_per_gpu": t["micro_batch_per_chip"],
            "gradient_accumulation_steps": t["gradient_accumulation_steps"],
            "optimizer": {"type": "AdamW", "params": dict(t["optimizer"])},
            "bf16": {"enabled": self.ctx["config"]["dtype"] == "bfloat16"},
            "zero_optimization": {"stage": t["zero_stage"]},
            "mesh": {"fsdp": n},
            "steps_per_print": 10 ** 9,
            "seed": engine_seed,
        }

    def setup(self):
        import jax

        import deepspeed_tpu
        from deepspeed_tpu import comm

        ctx, t = self.ctx, self.t
        n, devices, seed = ctx["chips"], ctx["devices"], ctx["seed"]
        comm.destroy()
        builder = compare.builder_of(ctx["config"])
        reference = compare.reference_of(ctx["config"])
        model = builder.build_model(ctx["config"], max_seq_len=t["seq"], remat=t["remat"],
                                    attn_impl=t["attn_impl"])
        self.vocab = int(ctx["config"]["model"]["vocab_size"])
        self.rows = t["micro_batch_per_chip"] * n
        tol = dict(ctx["config"]["compare"]["train"], **t.get("compare", {}))
        distinct = tol.get("micro_batches") == "distinct"
        gas = t["gradient_accumulation_steps"]
        fixed = np.random.RandomState(seed % (2 ** 32)).randint(
            0, self.vocab, (self.rows * (gas if distinct else 1), t["seq"])).astype(np.int32)
        engine_seed = seed % (2 ** 31 - 1)
        # the engine makes its weights as model.init(split(PRNGKey(seed))[1]); the
        # reference starts from the same call, and the checksums below prove it
        init_key = jax.random.split(jax.random.PRNGKey(engine_seed))[1]
        K = int(tol["steps"])
        opt = dict(t["optimizer"])
        opt.setdefault("betas", (0.9, 0.999))
        opt.setdefault("eps", 1e-8)
        opt.setdefault("weight_decay", 0.0)

        config = self._ds_config(n, engine_seed)
        mesh = (None if n == len(ctx["all_devices"])
                else comm.build_mesh(config["mesh"], devices=devices))
        t0 = time.perf_counter()
        engine = deepspeed_tpu.initialize(model=model, config=config, mesh=mesh)[0]
        self.start = compare.tree_checksum(
            engine.master_params if engine.master_params is not None else engine.params)
        feed = itertools.cycle([{"input_ids": fixed[i:i + self.rows]}
                                for i in range(0, len(fixed), self.rows)])
        self.losses, self.grad_norm, self.leaves = [], None, None
        for k in range(K):
            loss = engine.train_batch(feed)
            self.losses.append(float(loss))
            if k == 0:
                self.grad_norm = float(engine.get_global_grad_norm())
                if compare.by_leaf(tol):  # after one step Adam's first moment is (1 - beta1) g
                    moment = jax.device_get(
                        jax.jit(compare.leaf_readings)(engine.opt_state.exp_avg))
                    self.leaves = {leaf: tuple(float(x) / (1.0 - opt["betas"][0]) for x in pair)
                                   for leaf, pair in moment.items()}
        self.engine_s = time.perf_counter() - t0
        self.engine = engine
        # what finish() needs for the reference, which runs once the engine is gone
        arch = reference.arch(ctx["config"])
        self.reference = lambda steps=K, **wrong: compare.train_reference(
            reference, model.init, init_key, fixed, arch, steps, opt, devices,
            rows_per_pass=t["reference_rows_per_pass"], leaves=compare.by_leaf(tol), **wrong)
        self.tol, self.faults = tol, reference.FAULTS

    def window(self, seconds, t_start):
        import jax

        ctx, t, engine = self.ctx, self.t, self.engine
        batches = trafficgen.token_batches(ctx["seed"] + 1, self.rows, t["seq"], self.vocab)
        gas = t["gradient_accumulation_steps"]
        log_every = int(t.get("log_every", 10))
        jax.block_until_ready(engine.params)
        losses, ends = [], []
        with span("window"):
            t0 = time.perf_counter()
            prev = None
            while True:
                with span("train_batch"):
                    loss = engine.train_batch(batches)
                losses.append(loss)
                if prev is not None:
                    with span("wait_previous_step"):
                        jax.block_until_ready(prev)
                    ends.append(time.perf_counter())
                    if len(losses) % log_every == 0:
                        with span("loss_read"):
                            float(prev)  # a user's log line: the host fetches the loss
                    if ends[-1] - t0 >= seconds:
                        break
                prev = loss
            with span("wait_last_step"):
                jax.block_until_ready((loss, engine.params))
            t1 = time.perf_counter()
        values = np.asarray(jax.device_get(losses), np.float64)
        steps = len(losses)
        window_s = t1 - t0
        tokens = steps * gas * self.rows * t["seq"]
        per_chip = tokens / window_s / ctx["chips"]
        step_ms = np.diff(ends) * 1e3
        obs = dict(
            optimizer_steps=steps, micro_steps=steps * gas, window_s=window_s,
            tokens_per_s_per_chip=per_chip,
            step_ms_median=float(np.median(step_ms)) if len(step_ms) else window_s * 1e3 / steps,
            loss_first=float(values[0]), loss_last=float(values[-1]))
        return dict(
            end_to_end={"train_tokens_per_s": per_chip, "setup_s": t0 - t_start},
            obs=obs, attempted=steps, failed=int((~np.isfinite(values)).sum()),
            window_s=window_s)

    def finish(self):
        import jax

        from deepspeed_tpu import comm

        devices = self.ctx["devices"]
        self.engine = None       # the reference needs the chip's memory: state, moments and
        comm.destroy()           # accumulators go first
        gc.collect()
        in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices)
        live = sum(a.nbytes for a in jax.live_arrays())
        t0 = time.perf_counter()
        ref = self.reference()
        ref_s = time.perf_counter() - t0
        if not all(math.isclose(a, b, rel_tol=1e-5, abs_tol=1e-3)
                   for a, b in zip(self.start, ref["checksum"])):
            raise BenchmarkError(f"engine and reference start from different weights: "
                                 f"checksums {self.start} vs {ref['checksum']}")
        ok, fields = compare.train_verdict(self.losses, self.grad_norm, ref, self.tol,
                                           self.leaves)
        fields.update(reference_s=ref_s, engine_init_and_compared_steps_s=self.engine_s,
                      bytes_in_use_after_engine_release=in_use,
                      live_array_bytes_after_engine_release=live)
        controls = self.t.get("controls")
        if controls:  # on request (true, or a list of names): wrong trainers, and the reference in
            # the precision below the configuration's (its first step: the gradient is what it
            # moves), each in the program's place. Each must FAIL the comparison.
            names = list(self.faults) + ["lower_precision"] if controls is True else controls
            t0 = time.perf_counter()
            wrong = {f: self.reference(fault=f) for f in names if f in self.faults}
            if "lower_precision" in names:
                wrong["lower_precision"] = self.reference(steps=1, operand=compare.fp8)
            passed = {f: compare.train_verdict(r["losses"], r["grad_norms"][0], ref, self.tol,
                                               r.get("leaf_readings"))
                      for f, r in wrong.items()}
            fields["controls_passed_the_check"] = {f: p[0] for f, p in passed.items()}
            fields["controls"] = {
                f: {k: v for k, v in p[1].items() if k.endswith("_diff") or k == "grad_leaf_gaps"}
                for f, p in passed.items()}
            fields["controls_s"] = time.perf_counter() - t0
            ok = ok and not any(p[0] for p in passed.values())
        return dict(ok=ok, fields=fields)
