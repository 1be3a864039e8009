"""The one traffic generator: a data file of parameters -> the work of a run.

A traffic mix is a file ``traffic/<name>.json``; a later PR adds a mix by
adding a file. What a file may say:

``arrivals.process``
    ``steps``   training: no requests, a stream of token batches.
    ``poisson`` open loop: requests fall due at exponential gaps of mean
                ``burst / rate_per_s``, ``burst`` (default 1) at a time.
    ``closed``  closed loop: ``clients`` callers (a number, or ``"slots"`` for
                as many as the engine has slots), each sending its next
                request when its last one completes.
``arrivals.preroll_s``
    seconds of the same traffic run before the window opens, unmeasured and
    counted as set-up, so that the window starts on a busy system.
``prompt_tokens`` / ``output_tokens``
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}``.
``max_total_tokens``
    prompt + output never exceed it (the output is cut to fit).
``sizes_seed`` / ``pool``
    The SIZES (lengths, gaps) are drawn from ``sizes_seed``, not from the
    run's seed, so the seed does not change the amount of work. An open loop's
    whole schedule (which request falls due when) is the file's: the run's seed
    gives the token contents (and the weights) only, because even another ORDER
    of the same requests and gaps moved a 95th-percentile TTFT by 18 % between
    seeds on the chip (PERF.md section 6, PR 23), ten times what two runs of
    one seed differ by. A closed loop's clients draw the same pairs in the
    seed's own order. ``pool`` is how many size pairs a closed loop cycles
    through.
"""

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: Optional[float]      # seconds from the window's start; None in a closed loop
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


def draw(spec: dict, rs: np.random.RandomState, n: int) -> np.ndarray:
    """``n`` whole numbers from the distribution ``spec``."""
    dist = spec["dist"]
    if dist == "uniform":
        return rs.randint(int(spec["min"]), int(spec["max"]) + 1, n).astype(np.int64)
    if dist == "lognormal":
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * rs.standard_normal(n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown distribution {dist!r}")


def _sizes(traffic: dict, n: int):
    """``n`` (prompt, output) pairs from the file's own ``sizes_seed``."""
    rs = np.random.RandomState(int(traffic.get("sizes_seed", 0)))
    prompts = draw(traffic["prompt_tokens"], rs, n)
    outputs = draw(traffic["output_tokens"], rs, n)
    cap = int(traffic["max_total_tokens"])
    outputs = np.minimum(outputs, cap - prompts)
    if (outputs < 1).any():
        raise ValueError("a prompt leaves no room for output under max_total_tokens")
    return prompts, outputs


def _requests(prompts, outputs, dues, vocab, rs) -> List[Request]:
    return [Request(i, None if dues is None else float(dues[i]),
                    rs.randint(0, vocab, int(p)).astype(np.int32), int(o))
            for i, (p, o) in enumerate(zip(prompts, outputs))]


def open_loop(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """Requests due in [-preroll_s, seconds), in order of their due time."""
    arr = traffic["arrivals"]
    burst = int(arr.get("burst", 1))
    span = float(arr.get("preroll_s", 0.0)) + seconds
    sizes_rs = np.random.RandomState(int(traffic.get("sizes_seed", 0)) + 2)
    n_events = int(np.ceil(span * arr["rate_per_s"] / burst))  # a fixed count: the rate IS the load
    gaps = sizes_rs.exponential(1.0, n_events)
    gaps *= span / gaps.sum()                                   # ... spread over exactly the span
    prompts, outputs = _sizes(traffic, n_events * burst)
    starts = np.cumsum(gaps) - gaps[0] - float(arr.get("preroll_s", 0.0))
    dues = np.repeat(starts, burst)
    return _requests(prompts, outputs, dues, vocab,
                     np.random.RandomState(seed % (2 ** 32)))


def closed_loop(traffic: dict, seed: int, vocab: int) -> List[Request]:
    """The cycle of requests the clients draw from, in this seed's order."""
    n = int(traffic.get("pool", 256))
    prompts, outputs = _sizes(traffic, n)
    rs = np.random.RandomState(seed % (2 ** 32))
    order = rs.permutation(n)
    return _requests(prompts[order], outputs[order], None, vocab, rs)


def token_batches(seed: int, rows: int, seq: int, vocab: int):
    """Training: an endless stream of fresh (rows, seq) int32 batches."""
    rs = np.random.RandomState(seed % (2 ** 32))
    while True:
        yield {"input_ids": rs.randint(0, vocab, (rows, seq)).astype(np.int32)}
