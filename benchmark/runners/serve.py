"""Serving cell: ``init_inference`` -> ``ContinuousBatchingEngine`` ->
``ServingEngine``, driven by one thread as a client would see it.

Open loop (``arrivals.process: poisson``): requests fall due on a schedule
fixed by the traffic file; each is timed from the instant it was DUE, not
from when this loop got round to submitting it, so a stall's wait on later
arrivals counts; how late the generator ran is reported. Closed loop
(``closed``): as many clients as the file says, each sending its next request
when its last completes. Every token's arrival is recorded when ``step()``
returns it.

``correct`` is decided in ``finish()``, after the window and outside the
profiler, with the engine's buffers released: a seeded sample of finished
requests against the float32 reference (``compare.serve_verdict``).
"""

import gc
import itertools
import time

import numpy as np

from benchmark import compare, trafficgen
from benchmark.harness import BenchmarkError, span

clock = time.monotonic  # the serving engine's own clock: its admit times compare with ours


# the rows of ``ServingEngine.tick_stats()`` whose sum is the server's wall time between two reads
ROWS = ("empty_ms", "between_steps_ms", "schedule_ms", "dispatch_ms", "block_ms", "attribute_ms",
        "emit_ms", "step_other_ms")


def ledger_observations(stats0, stats1, window_s):
    """Where the server's wall time went between the window's two reads of the program's
    host ledger (its docs/telemetry.md, "The serving loop's ledger"), as observations a
    ``"reduction": "value"`` metric file can read. A program whose ``tick_stats()`` lacks a
    key gives no reading for what is made of it.

    ``step_schedule_ms``      ms a ``step()`` in expiry, policy order and handovers
    ``tick_admit_ms``         ms a ``step()`` in the batcher's admission loop (inside
                              ``tick_dispatch_ms``)
    ``tick_attribute_ms``     ms a ``step()`` in the host's work on fetched results
    ``step_emit_ms``          ms a ``step()`` from the tick's return to its own: fan-out to
                              requests, finishing, gauges
    ``step_between_ms``       ms a ``step()`` outside it while a request is held: the caller's
                              time (here this runner's own loop)
    ``step_other_ms``         ms a ``step()`` of its wall that no row above names
    ``empty_share_pct``       % of the window outside ``step()`` with no request held at all
    ``starved_share_pct``     % of the window in which a request was held and no tick was in
                              flight: what the host costs the chip
    ``host_bound_tick_pct``   % of the ticks whose result was on hand before the host came to
                              fetch it: the host, not the device, set that tick's pace
    ``ledger_residual_pct``   the rows' sum against the window's wall on this runner's clock,
                              % of the window: the identity's error
    """
    delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
    per = lambda total, count: total / count if total is not None and count else None
    share = lambda ms: 100.0 * ms / (window_s * 1e3) if ms is not None else None
    steps, ticks = delta("steps"), delta("ticks")
    ready = delta("ticks_ready_at_retire")
    rows = [delta(key) for key in ROWS]
    return dict(
        empty_share_pct=share(delta("empty_ms")),
        starved_share_pct=share(delta("starved_ms")),
        host_bound_tick_pct=100.0 * ready / ticks if ready is not None and ticks else None,
        step_schedule_ms=per(delta("schedule_ms"), steps),
        tick_admit_ms=per(delta("admit_ms"), steps),
        tick_attribute_ms=per(delta("attribute_ms"), steps),
        step_emit_ms=per(delta("emit_ms"), steps),
        step_between_ms=per(delta("between_steps_ms"), steps),
        step_other_ms=per(delta("step_other_ms"), steps),
        ledger_residual_pct=(share(sum(rows) - window_s * 1e3)
                             if all(ms is not None for ms in rows) else None))


class Record:
    __slots__ = ("request", "due", "submitted", "rid", "times", "state", "admit", "tokens",
                 "prefill_start", "first_token")

    def __init__(self, request, due):
        self.request, self.due = request, due
        self.submitted = self.rid = self.admit = self.state = self.tokens = None
        self.prefill_start = self.first_token = None
        self.times = []


def warm_plan(prompt_range, cache_len, chunk, read_floor=16, chunk_floor=16):
    """Requests that make the engine run, before the window, every tick
    program the cell's lengths can reach: for each power-of-two read bucket
    an ``anchor`` request whose cached extent sits in it, and while it
    decodes one short request per power-of-two chunk width. Floors smaller
    than the program's own only make several targets share a program.
    Yields (anchor_prompt_len, anchor_new, [short prompt lens])."""
    shorts, w = [], chunk_floor
    while w <= chunk:
        shorts.append(w)
        w *= 2
    lo = prompt_range[0]
    bucket = read_floor
    while bucket < lo:
        bucket *= 2
    while True:
        top = min(bucket, cache_len)
        anchor = max(lo, top // 2 + 1)
        new = min(top - anchor, len(shorts) + 4)  # still decoding when the last short rides its tick
        if new >= 1:
            yield anchor, new, (shorts if new > len(shorts) + 1 else [])
        if top >= cache_len:
            return
        bucket *= 2


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.s = ctx["cell"]["serve"]
        self.records = []

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import deepspeed_tpu
        from deepspeed_tpu import comm
        from deepspeed_tpu.inference import ContinuousBatchingEngine
        from deepspeed_tpu.serving import ServingEngine

        ctx, s = self.ctx, self.s
        comm.destroy()
        seed = ctx["seed"]
        self.tol = dict(ctx["config"]["compare"]["serve"], **s.get("compare", {}))
        builder = compare.builder_of(ctx["config"])
        model = builder.build_model(ctx["config"], max_seq_len=s["cache_len"], remat=False,
                                    attn_impl=s["attn_impl"])
        self.vocab = int(ctx["config"]["model"]["vocab_size"])
        params = compare.seed_params(
            model, seed % (2 ** 31 - 1),
            lambda p: builder.sharpen(p, ctx["config"], self.tol["query_scale"]))
        config = {"dtype": ctx["config"]["dtype"],
                  "mesh": {"shape": {"data": 1, "tensor": ctx["chips"]}}}
        engine = deepspeed_tpu.init_inference(model, config=config, params=params)
        del params
        self.params = engine.params
        self.batcher = ContinuousBatchingEngine(
            model, config=config, params=engine.params, max_slots=s["slots"],
            cache_len=s["cache_len"], seed=seed % (2 ** 31 - 1), **s.get("engine", {}))
        self.serving = ServingEngine(self.batcher, **s.get("serving", {}))
        self.chunk = self.batcher.prefill_chunk
        with span("warm_up"):
            self._warm_up()

    def _drive(self, until):
        while not until():
            self.serving.step()

    def _warm_up(self):
        traffic, s = self.ctx["traffic"], self.s
        rs = np.random.RandomState(12345)
        lo, hi = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
        submit = lambda n, new: self.serving.submit(
            rs.randint(0, self.vocab, n).astype(np.int32), max_new_tokens=new)
        requests = 0
        for anchor_len, anchor_new, shorts in warm_plan((lo, hi), s["cache_len"], self.chunk):
            anchor = submit(anchor_len, anchor_new)
            req = self.serving.request(anchor.rid)
            self._drive(lambda: len(req.tokens) >= 1)          # its prefill is done
            for n in shorts:
                short = self.serving.request(submit(n, 1).rid)
                self._drive(lambda: len(short.tokens) >= 1)
            self._drive(lambda: not self.serving.has_work())
            self.serving.reap()
            requests += 1 + len(shorts)
        self.ctx["emit"](phase="warm_up", requests=requests)

    # -- the window -----------------------------------------------------
    def _submit(self, rec, now):
        r = rec.request
        rec.submitted = now
        adm = self.serving.submit(r.prompt, max_new_tokens=r.max_new_tokens)
        if adm:
            rec.rid = adm.rid
            self.by_rid[adm.rid] = rec
            self.live[adm.rid] = rec
        else:
            rec.state = "shed:" + adm.reason

    def _step(self, in_window):
        with span("step"):
            out = self.serving.step()
        t = clock()
        for rid, toks in out.items():
            rec = self.by_rid.get(rid)
            if rec is not None:
                rec.times.extend([t] * len(toks))
        if in_window and out:
            self.live_rows.append(len(out))
            self.live_kv.append(sum(self.by_rid[rid].request.prompt.size + len(self.by_rid[rid].times)
                                    for rid in out if rid in self.by_rid))
        finished = []
        for rid, req in self.serving.reap().items():
            rec = self.live.pop(rid, None)
            if rec is None:
                continue
            rec.state, rec.admit, rec.tokens = req.state, req.admit_t, list(req.tokens)
            rec.prefill_start = getattr(req, "prefill_start_t", None)
            rec.first_token = getattr(req, "first_token_t", None)
            finished.append(rec)
        return finished

    def window(self, seconds, t_start):
        ctx, traffic, serving = self.ctx, self.ctx["traffic"], self.serving
        arr = traffic["arrivals"]
        closed = arr["process"] == "closed"
        if arr["process"] not in ("closed", "poisson"):
            raise BenchmarkError(f"the serve runner cannot drive arrivals {arr['process']!r}")
        preroll = float(arr.get("preroll_s", 0.0))
        drain_s = float(traffic.get("drain_s", 0.0))
        self.by_rid, self.live, self.live_rows, self.live_kv = {}, {}, [], []
        if closed:
            pool = trafficgen.closed_loop(traffic, ctx["seed"], self.vocab)
            clients = self.s["slots"] if arr["clients"] == "slots" else int(arr["clients"])
            next_request = itertools.cycle(pool).__next__
        else:
            reqs = trafficgen.open_loop(traffic, ctx["seed"], seconds, self.vocab)
        min_finished = int(self.tol["sample"])

        t_begin = clock()
        t_open, t_close = t_begin + preroll, t_begin + preroll + seconds
        records = self.records
        if closed:
            for _ in range(clients):
                rec = Record(next_request(), None)
                records.append(rec)
                self._submit(rec, clock())
        else:
            records.extend(Record(r, t_open + r.due_s) for r in reqs)
        nxt, window_span, stats0, stats1 = 0, None, None, None
        setup_s = None
        n_finished = 0
        while True:
            now = clock()
            if window_span is None and now >= t_open:
                stats0 = serving.tick_stats()
                window_span = span("window")
                window_span.__enter__()
                setup_s = time.perf_counter() - t_start
                t_opened = now
            if stats1 is None and now >= t_close:
                stats1 = serving.tick_stats()
                window_span.__exit__(None, None, None)
                t_closed = now
            if not closed:
                while nxt < len(records) and records[nxt].due <= now:
                    self._submit(records[nxt], now)
                    nxt += 1
            if now >= t_close:
                waiting = (n_finished < min_finished) if closed else bool(self.live)
                if not waiting or now >= t_close + drain_s:
                    break
            if serving.has_work():
                for rec in self._step(stats0 is not None and stats1 is None):
                    n_finished += rec.state == "finished"
                    if closed and clock() < t_close:
                        new = Record(next_request(), None)
                        records.append(new)
                        self._submit(new, clock())
            else:
                nap = 0.002 if closed or nxt >= len(records) else records[nxt].due - now
                time.sleep(max(0.0, min(nap, 0.002)))
        t_end = clock()
        return self._measure(closed, seconds, t_opened, t_closed, t_end, setup_s, stats0, stats1)

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        window_s = t_close - t_open
        measured, failed = [], 0
        for rec in self.records:
            if closed:
                # a closed loop has no due time: a request counts once it ended in the window
                ended = rec.times[-1] if rec.times else rec.submitted
                if rec.state is None or not (t_open <= ended < t_close):
                    continue
            elif rec.request.due_s < 0:
                continue
            measured.append(rec)
            good = rec.state == "finished" and len(rec.tokens) == rec.request.max_new_tokens
            failed += not good
        all_times = np.array([t for rec in self.records for t in rec.times])
        tokens_in_window = int(((all_times >= t_open) & (all_times < t_close)).sum())
        dticks = max(1, stats1["ticks"] - stats0["ticks"])
        capacity = stats1["capacity_tokens"] - stats0["capacity_tokens"]
        obs = dict(
            window_s=window_s, requests_measured=len(measured), tokens_in_window=tokens_in_window,
            serve_tokens_per_s=tokens_in_window / window_s, ticks=dticks,
            tick_dispatch_ms=(stats1["dispatch_ms"] - stats0["dispatch_ms"]) / dticks,
            tick_block_ms=(stats1["block_ms"] - stats0["block_ms"]) / dticks,
            slot_use_pct=(100.0 * (stats1["tokens"] - stats0["tokens"]) / capacity
                          if capacity else None),
            mean_live_rows=float(np.mean(self.live_rows)) if self.live_rows else None,
            max_live_rows=int(max(self.live_rows)) if self.live_rows else None,
            mean_live_kv_tokens=float(np.mean(self.live_kv)) if self.live_kv else None,
            drain_s=t_end - t_close)
        # the two kinds of tick and the prefill queue (the program's counters since PR 24); a
        # program without a counter, or a window without that kind of tick, gives no reading
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        fused, plain = delta("fused_prefill_ticks"), delta("plain_ticks")
        obs.update(
            fused_tick_block_ms=per(delta("block_ms_fused"), fused),
            plain_tick_block_ms=per(delta("block_ms_plain"), plain),
            fused_tick_share_pct=(100.0 * fused / (fused + plain)
                                  if fused is not None and plain is not None and fused + plain
                                  else None),
            prefill_q_depth_mean=per(delta("prefill_q_depth_sum"), delta("steps")))
        obs.update(ledger_observations(stats0, stats1, window_s))
        e2e = {"setup_s": setup_s, "serve_tokens_per_s": obs["serve_tokens_per_s"]}
        if not closed:
            worst = t_end  # a request that never answered waited at least until now
            ttft = np.array([((rec.times[0] if rec.times else worst) - rec.due) * 1e3
                             for rec in measured])
            gaps = np.concatenate([np.diff(rec.times) for rec in measured if len(rec.times) > 1]
                                  or [np.zeros(1)]) * 1e3
            late = np.array([(rec.submitted - rec.due) * 1e3 for rec in measured])
            waits = np.array([(rec.admit - rec.due) * 1e3 for rec in measured
                              if rec.admit is not None])
            p95 = lambda ms: float(np.percentile(ms, 95)) if ms else None
            obs.update(  # the request's own timestamps (the program's, on this clock, since PR 24)
                prefill_wait_p95_ms=p95([(r.prefill_start - r.admit) * 1e3 for r in measured
                                         if r.prefill_start is not None and r.admit is not None]),
                prefill_p95_ms=p95([(r.first_token - r.prefill_start) * 1e3 for r in measured
                                    if r.first_token is not None and r.prefill_start is not None]))
            ended = sum(rec.state == "finished" and t_open <= rec.times[-1] < t_close
                        for rec in self.records if rec.times)
            e2e.update(ttft_p95_ms=float(np.percentile(ttft, 95)),
                       ttft_p50_ms=float(np.median(ttft)),
                       gap_p95_ms=float(np.percentile(gaps, 95)))
            obs.update(requests_ended_in_window=ended,
                       requests_shed=sum(str(rec.state).startswith("shed") for rec in measured),
                       ttft_p50_ms=e2e["ttft_p50_ms"], ttft_p95_ms=e2e["ttft_p95_ms"],
                       gap_p50_ms=float(np.median(gaps)),
                       gaps_measured=int(gaps.size),
                       generator_late_p95_ms=float(np.percentile(late, 95)),
                       generator_late_max_ms=float(late.max()),
                       queue_wait_p95_ms=float(np.percentile(waits, 95)) if waits.size else None,
                       offered_per_s=len(measured) / window_s,
                       completed_per_s=sum(r.state == "finished" for r in measured) / window_s)
        return dict(end_to_end=e2e, obs=obs, attempted=len(measured), failed=failed,
                    window_s=window_s)

    # -- the comparison -------------------------------------------------
    def finish(self):
        ctx, tol = self.ctx, self.tol
        done = [r for r in self.records if r.state == "finished" and r.tokens]
        wrong_count = [r for r in done if len(r.tokens) != r.request.max_new_tokens]
        if not done:
            raise BenchmarkError("no request finished: nothing to hold to the reference")
        rs = np.random.RandomState((ctx["seed"] + 2) % (2 ** 32))
        longest = max(range(len(done)), key=lambda i: done[i].request.prompt.size + len(done[i].tokens))
        rest = [i for i in rs.permutation(len(done)) if i != longest]
        sample = [done[i] for i in [longest] + rest[:int(tol["sample"]) - 1]]
        params = self.params
        reference = compare.reference_of(ctx["config"])
        self.serving.close()
        self.serving = self.batcher = self.params = None   # the KV pool's memory is the reference's now
        gc.collect()
        out_spec = ctx["traffic"]["output_tokens"]
        ok, fields = compare.serve_verdict(
            reference, params, [r.request.prompt for r in sample],
            [np.asarray(r.tokens, np.int32) for r in sample], reference.arch(ctx["config"]),
            ctx["seed"], tol, width=self.s["cache_len"], new_max=int(out_spec["max"]))
        fields.update(requests_finished=len(done), finished_with_wrong_token_count=len(wrong_count))
        return dict(ok=ok and not wrong_count, fields=fields)
