"""What the serving tests under ``tests/unit/inference`` share: the toy models
they serve, seeded prompts, and the loops that drive a
``ContinuousBatchingEngine`` to its end. A model and its parameters are built
once a process and keyed by what defines them (the configuration and the
seed), so a file that only reads them does not trace ``init`` again; nothing
here may be written to in place."""

import functools

import jax
import numpy as np

from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

# the token-stream files' model: two layers, a 128-token vocabulary
SMALL = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                          max_seq_len=128, dtype="float32")


@functools.cache
def built(cfg, seed=0):
    """``(model, params)`` of ``cfg``, initialised from ``PRNGKey(seed)``."""
    model = TransformerModel(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def prompt(n, vocab, seed=0):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).astype(np.int32)


def prompts(ns, seed=0, vocab=SMALL.vocab_size):
    """One prompt a length of ``ns``, drawn in turn from ONE seeded stream."""
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (n,)).astype(np.int32) for n in ns]


def drain(cb, rids):
    """Step ``cb`` until it holds no work; the finished arrays of ``rids``."""
    while cb.has_work():
        cb.step()
    done = cb.finished()
    return [np.asarray(done[r]) for r in rids]


def serve(cb, submissions, max_ticks=400):
    """Drive ``cb`` over [(tick, prompt, max_new)]; returns the finished
    arrays in submission order. Asserts the step()-stream/finished()
    contract on the way: a tick may emit several tokens a request, and their
    concatenation must equal the final array's generated part."""
    streams, results = {}, {}
    pending = list(submissions)  # list order = submission order per tick
    rid_of = {}
    tick = 0
    while pending or cb.has_work():
        assert tick < max_ticks, "scheduler did not drain"
        for item in [s for s in pending if s[0] <= tick]:
            rid_of[id(item)] = cb.submit(item[1], max_new_tokens=item[2])
        pending = [s for s in pending if s[0] > tick]
        for rid, toks in cb.step().items():
            streams.setdefault(rid, []).extend(toks)
        results.update(cb.finished())
        tick += 1
    for item in submissions:
        rid = rid_of[id(item)]
        np.testing.assert_array_equal(
            np.asarray(streams[rid], np.int32), results[rid][len(item[1]):])
    return [results[rid_of[id(s)]] for s in submissions]
