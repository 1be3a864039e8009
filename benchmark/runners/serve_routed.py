"""Serving cell of a model with expert layers and a cache of two sizes:
``runners/serve.py``'s runner as it is (the same loop, clocks and reference
comparison), plus the program's routing and prefill counters among the
observations, where per-layer metrics can read them (``serve._measure``
copies only the keys it names).

The cell's group and the configuration's ``compare`` group are named after
this module (``serve_routed``) and handed to ``serve.Runner`` as its own.
A program whose ``tick_stats()`` lacks a counter (the parent of the PR that
brought them) gives no reading for it, and the line leaves that metric out.
The warm-up is this module's own (see ``_warm_up``).

Beside them, what tells a slow run's cause apart (PR 27: one run in twelve
read a tenth low with the same programs and requests): the rate of a fixed
matrix product on the chip just before the pre-roll and just after the
window (the chip's own speed, whatever the tick does), the time of a fixed
loop on the host at the same two moments, this process's share of a core
over pre-roll and window, the longest single ``step()`` of each, and, to
say where a long step stood, the longest pause of Python's collector and the
longest oversleep of a 50 ms ticker thread (the whole process or machine
stood still: a ticker keeps time while the main thread waits on the chip).
The matrix product is read on a TPU only and is ``null`` anywhere else: its
3.3 TFLOP are 20 ms there, and on the CPU of the tests 6 s of every core,
four times a run, for a reading that no CPU run reports.
"""

import gc
import threading
import time

from benchmark.runners import serve

NAME = "serve_routed"
PROBE_N, PROBE_REPEATS = 4096, 24


def _host_loop_ms():
    """Milliseconds this core takes for a fixed loop of Python arithmetic."""
    t0 = time.perf_counter()
    sum(i * i for i in range(100_000))
    return 1e3 * (time.perf_counter() - t0)


class _Pauses(threading.Thread):
    """From ``start()`` to ``stop()``: the longest oversleep of a 50 ms sleep
    in this thread (at 10 ms the ticker took a quarter of a core beside a
    main thread that waits on the chip: my chip run, PR 27b), and the longest
    collection of ``gc``, in milliseconds."""

    def __init__(self):
        super().__init__(daemon=True)
        self.oversleep_ms = self.gc_ms = 0.0
        self._done, self._gc_t0 = threading.Event(), None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_ms = max(self.gc_ms, 1e3 * (time.perf_counter() - self._gc_t0))

    def run(self):
        gc.callbacks.append(self._on_gc)
        while not self._done.is_set():
            t0 = time.perf_counter()
            time.sleep(0.050)
            self.oversleep_ms = max(self.oversleep_ms, 1e3 * (time.perf_counter() - t0) - 50.0)
        gc.callbacks.remove(self._on_gc)

    def stop(self):
        self._done.set()
        self.join()


class Runner(serve.Runner):
    probe0 = host0 = None

    def __init__(self, ctx):
        cell = dict(ctx["cell"], serve=ctx["cell"][NAME])
        compare = dict(ctx["config"]["compare"], serve=ctx["config"]["compare"][NAME])
        super().__init__(dict(ctx, cell=cell, config=dict(ctx["config"], compare=compare)))
        self.longest_step = {False: 0.0, True: 0.0}   # by "inside the window"; not the warm-up's

    def setup(self):
        super().setup()
        self._chip_tflops()                            # compiles the probe: set-up's
        self.probe0 = self._chip_tflops()
        self.host0 = (_host_loop_ms(), time.process_time(), time.perf_counter())
        self.pauses = _Pauses()
        self.pauses.start()

    def _chip_tflops(self):
        """TFLOP/s of PROBE_REPEATS bfloat16 products of two PROBE_N-square
        matrices, nothing else on the chip: 0.1 GB held for ~20 ms. Off a
        TPU nothing is built, compiled or run, and there is no reading."""
        if self.ctx["devices"][0].platform != "tpu":
            return None
        import jax
        import jax.numpy as jnp

        if not hasattr(self, "_probe"):
            a = jnp.full((PROBE_N, PROBE_N), 1.0 / PROBE_N, jnp.bfloat16)
            self._probe = jax.jit(lambda x: jax.lax.fori_loop(
                0, PROBE_REPEATS, lambda _, y: (y @ x).astype(x.dtype), x)), a
        fn, a = self._probe
        t0 = time.perf_counter()
        fn(a).block_until_ready()
        return 2.0 * PROBE_N ** 3 * PROBE_REPEATS / (time.perf_counter() - t0) / 1e12

    def _step(self, in_window):
        t0 = time.perf_counter()
        out = super()._step(in_window)
        self.longest_step[in_window] = max(self.longest_step[in_window], time.perf_counter() - t0)
        return out

    def _warm_up(self):
        """Every tick program the cell's lengths can reach, run before the
        window: for each read bucket an anchor request decoding at a depth
        inside it and, while it decodes, one short request of each chunk
        width. ``serve.warm_plan`` gives an anchor a fixed number of tokens
        and hopes it outlives its short requests, and reaches a bucket from
        half its length, which misses the last bucket of a pool whose
        length is no power of two (16,896); here an anchor that is nearly
        spent is replaced before the next short request is sent."""
        import numpy as np

        lo = self.ctx["traffic"]["prompt_tokens"]["min"]
        length, new = self.s["cache_len"], 12
        depths = sorted({lo} | {2 ** k + 1 for k in range(length.bit_length())
                                if lo < 2 ** k + 1 <= length - new})
        widths, w = [], 128
        while w < self.chunk:
            widths.append(w)
            w *= 2
        widths = [w for w in widths if w >= 16] + [self.chunk]
        rs = np.random.RandomState(12345)
        submit = lambda n, out: self.serving.request(self.serving.submit(
            rs.randint(0, self.vocab, n).astype(np.int32), max_new_tokens=out).rid)
        requests = 0
        for depth in depths:
            anchor = None
            for width in [None] + widths:  # None: the anchor alone, its own chunks and plain ticks
                if anchor is None or len(anchor.tokens) > new - 4:
                    self._drive(lambda: not self.serving.has_work())
                    anchor = submit(depth, new)
                    self._drive(lambda: len(anchor.tokens) >= 1)
                    requests += 1
                if width is not None:
                    short = submit(min(width, length - 1), 1)
                    self._drive(lambda: len(short.tokens) >= 1)
                    requests += 1
            self._drive(lambda: not self.serving.has_work())
            self.serving.reap()
        self.ctx["emit"](phase="warm_up", requests=requests, depths=depths, widths=widths)

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        result = super()._measure(closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1)
        if self.host0 is not None:   # set-up ran: the chip's and the host's state beside the counters
            loop0, cpu0, wall0 = self.host0
            self.pauses.stop()
            cpu_share = (time.process_time() - cpu0) / (time.perf_counter() - wall0)
            self._chip_tflops()      # waits behind the ticks still in flight; the next one is alone
            result["obs"].update(
                chip_probe_tflops_before=self.probe0, chip_probe_tflops_after=self._chip_tflops(),
                host_loop_ms_before=loop0, host_loop_ms_after=_host_loop_ms(),
                process_cpu_share=cpu_share, gc_pause_ms_longest=self.pauses.gc_ms,
                ticker_oversleep_ms_longest=self.pauses.oversleep_ms,
                longest_step_ms_preroll=1e3 * self.longest_step[False],
                longest_step_ms_window=1e3 * self.longest_step[True])
        delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
        per = lambda total, count: total / count if total is not None and count else None
        ticks, fused = delta("moe_ticks"), delta("fused_prefill_ticks")
        made, held = delta("moe_assignments"), delta("moe_held_assignments")
        result["obs"].update(
            fused_ticks=fused, plain_ticks=delta("plain_ticks"),
            # routing, as the ticks retired in the window reported it
            moe_ticks=ticks, moe_held_share_pct=per(100.0 * held if held is not None else None, made),
            moe_held_assignments_per_tick=per(held, ticks),
            moe_experts_hit_per_tick=per(delta("moe_experts_hit"), ticks),
            moe_expert_tokens_most=per(delta("moe_expert_tokens_most_sum"), ticks),
            moe_expert_tokens_mean=per(delta("moe_expert_tokens_mean_sum"), ticks),
            moe_load_imbalance=per(delta("moe_imbalance_sum"), ticks),
            # what the chunks' attention had to do, a chunk
            chunk_tokens=per(delta("prefill_chunk_tokens"), fused),
            chunk_pairs_full=per(delta("prefill_pairs_full"), fused),
            chunk_pairs_window=per(delta("prefill_pairs_window"), fused),
            chunk_keys_full=per(delta("prefill_keys_full"), fused),
            kv_pool_gb_full=per(stats1.get("kv_pool_bytes_full"), 1e9),
            kv_pool_gb_window=per(stats1.get("kv_pool_bytes_window"), 1e9))
        return result
