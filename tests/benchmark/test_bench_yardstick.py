"""The benchmark's yardstick, piece by piece: the trace reductions on a
recorded chip trace and on hand-made intervals, the traffic generator, the
cost functions against hand counts, the float32 reference's own controls."""

import json
import os

import numpy as np
import pytest

from benchmark import costs, trafficgen
from benchmark.reduce import reductions as R
from benchmark.reduce import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "benchmark", "reduce", "recorded_1chip_toy_train.json.gz")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


# -- the reducer -------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return xplane.load(RECORDED)


def test_recorded_trace_has_one_tpu_plane_with_ops_and_modules(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    dev = recorded.devices["/device:TPU:0"]
    assert len(dev.ops) == 4332 and len(dev.modules) == 42


def test_busy_is_the_union_not_the_sum(recorded):
    dev = recorded.devices["/device:TPU:0"]
    busy = R.busy_s(recorded)
    summed = sum(d for _, _, d in dev.ops) / 1e9
    span = (dev.ops[-1][1] + dev.ops[-1][2] - dev.ops[0][1]) / 1e9
    # a while op encloses its body's ops: the sum counts them twice, the union once
    assert busy < summed
    assert 0 < busy <= span
    assert busy == pytest.approx(0.0232, abs=0.001)  # 3 toy optimizer steps of ~7.7 ms


def test_module_time_per_call(recorded):
    # jit_micro_fn ran 6 times (3 steps x 2 accumulation) at ~3.49 ms, jit_apply_fn 3 times
    assert R.module_ms_per_call(recorded, r"^jit_micro_fn") == pytest.approx(3.49, abs=0.02)
    # the family's summed time: what a per-tick or per-step reading divides by a host count
    assert R.module_ms(recorded, r"^jit_micro_fn") == pytest.approx(6 * 3.49, abs=0.1)
    assert R.module_ms(recorded, r"^jit_(micro|apply)_fn") > R.module_ms(recorded, r"^jit_micro_fn")
    assert R.module_ms(recorded, r"^no_such_program") is None
    assert R.module_ms_per_call(recorded, r"^no_such_program") is None


def test_op_sums_leave_containers_out(recorded):
    flash = R.op_ms(recorded, r"^custom-call:tpu_custom_call ")
    # 4 Mosaic calls a layer (fwd, remat fwd, dq, dkv) x 2 layers x 6 micro-steps
    calls = [e for e in recorded.devices["/device:TPU:0"].ops
             if e[0].startswith("custom-call:tpu_custom_call ")]
    assert len(calls) == 48
    assert flash == pytest.approx(sum(e[2] for e in calls) / 1e6)
    assert R.op_ms(recorded, r"^while ") is None  # containers never enter a sum
    names = [n for n, _ in R.top_ops(recorded, 10)]
    assert len(names) == 10 and not any(n.startswith("while ") for n in names)


def test_window_clips_events(recorded):
    dev = recorded.devices["/device:TPU:0"]
    first = dev.modules[3]  # the first jit_micro_fn
    window = (first[1], first[1] + first[2])
    assert R.module_ms(recorded, r"^jit_micro_fn", window) == pytest.approx(first[2] / 1e6)
    assert R.busy_s(recorded, window) <= first[2] / 1e9
    # an op that straddles the window's edge counts for the part inside: busy never passes it
    straddle = xplane.Trace(devices={"d": xplane.DeviceTrace(ops=[("fusion a", 0, 100), ("fusion b", 150, 100)])})
    assert R.busy_s(straddle, (50, 200)) == pytest.approx(100e-9)
    assert dict(R.idle_gaps(straddle, window=(50, 200))) == {"(no span)": pytest.approx(50e-9)}


def test_op_name_reduces_an_hlo_instruction():
    text = ('%closed_call.11 = (bf16[2,4,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[2,4,1024,1]) '
            'custom-call(bf16[2,4,1024,64]{3,2,1,0} %bitcast.408), custom_call_target="tpu_custom_call"')
    assert xplane.op_name(text) == "custom-call:tpu_custom_call closed_call.11"
    assert xplane.op_name("%all-gather.3 = bf16[8,4]{1,0} all-gather(bf16[2,4]{1,0} %p), dimensions={0}") \
        == "all-gather all-gather.3"
    assert xplane.op_name("%while.7 = (s32[]{:T(128)}, bf16[2]{0}) while((s32[], bf16[2]) %t), body=%b") \
        == "while while.7"
    assert xplane.op_name("not an instruction") == "not an instruction"


def hand_made():
    ops = [("fusion a", 0, 100), ("all-gather g", 50, 100), ("fusion b", 120, 10),
           ("while w", 0, 400), ("all-reduce r", 300, 50), ("fusion c", 500, 100)]
    host = [("bench:window", 0, 1000), ("bench:train_batch", 400, 90), ("bench:loss_read", 600, 300)]
    return xplane.Trace(devices={"/device:TPU:0": xplane.DeviceTrace(ops=ops)}, host_spans=host)


def test_interval_algebra():
    assert R.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert R.covered_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert R.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == [(0, 2), (4, 8), (22, 29)]


def test_exposed_collective_share_on_hand_made_intervals():
    total_ms, exposed = R.collective_ms(hand_made())
    # collectives cover [50,150) and [300,350): 150 ns. Other ops (the while is a container)
    # cover [0,100), [120,130), [500,600): of the collective time, [100,120), [130,150) and
    # [300,350) are exposed = 90 ns
    assert total_ms == pytest.approx(150 / 1e6)
    assert exposed == pytest.approx(90 / 150)
    assert R.collective_ms(xplane.Trace(devices={"d": xplane.DeviceTrace(ops=[("fusion a", 0, 5)])})) \
        == (None, None)


def test_idle_gaps_are_charged_to_the_host_span_that_covers_them():
    trace = hand_made()
    trace.devices["/device:TPU:0"].ops = [e for e in trace.devices["/device:TPU:0"].ops
                                          if not e[0].startswith("while")]
    gaps = dict(R.idle_gaps(trace, window=(0, 1000)))
    # busy: [0,150), [300,350), [500,600); idle: [150,300) none, [350,500) train_batch 90 of it,
    # [600,1000) loss_read 300 of it; the enclosing bench:window span is never charged
    assert "bench:window" not in gaps
    assert gaps["(no span)"] == pytest.approx(150e-9)
    assert gaps["bench:train_batch"] == pytest.approx(150e-9)
    assert gaps["bench:loss_read"] == pytest.approx(400e-9)
    assert R.span_window(trace) == (0, 1000)


# -- traffic -----------------------------------------------------------------

CHAT = load("benchmark", "traffic", "chat_open_loop.json")
BATCH = load("benchmark", "traffic", "batch_closed_loop.json")


def test_open_loop_is_a_function_of_its_seed():
    a = trafficgen.open_loop(CHAT, 5, 40.0, 50257)
    b = trafficgen.open_loop(CHAT, 5, 40.0, 50257)
    c = trafficgen.open_loop(CHAT, 2 ** 31 + 7, 40.0, 50257)
    assert [(r.due_s, r.max_new_tokens) for r in a] == [(r.due_s, r.max_new_tokens) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))


def test_the_seed_does_not_change_the_amount_of_work():
    # open loop: the whole schedule is the file's, whatever the seed
    a = trafficgen.open_loop(CHAT, 1, 40.0, 50257)
    b = trafficgen.open_loop(CHAT, 2, 40.0, 50257)
    schedule = lambda reqs: [(r.due_s, r.prompt.size, r.max_new_tokens) for r in reqs]
    assert schedule(a) == schedule(b)
    # closed loop: the same pairs, drawn in the seed's own order
    sizes = lambda reqs: [(r.prompt.size, r.max_new_tokens) for r in reqs]
    c, d = trafficgen.closed_loop(BATCH, 1, 50257), trafficgen.closed_loop(BATCH, 2, 50257)
    assert sizes(c) != sizes(d) and sorted(sizes(c)) == sorted(sizes(d))


def test_open_loop_meets_its_rate_and_length_distributions():
    reqs = trafficgen.open_loop(CHAT, 3, 400.0, 50257)
    arr = CHAT["arrivals"]
    span = 400.0 + arr["preroll_s"]
    assert len(reqs) == int(np.ceil(span * arr["rate_per_s"]))
    dues = np.array([r.due_s for r in reqs])
    assert dues[0] == pytest.approx(-arr["preroll_s"]) and dues[-1] < 400.0
    assert (np.diff(dues) >= 0).all()
    prompts = np.array([r.prompt.size for r in reqs])
    outs = np.array([r.max_new_tokens for r in reqs])
    assert prompts.min() >= 16 and prompts.max() <= 512 and outs.min() >= 8 and outs.max() <= 192
    assert 100 <= np.median(prompts) <= 160 and 38 <= np.median(outs) <= 60
    assert (prompts + outs <= CHAT["max_total_tokens"]).all()
    gaps = np.diff(dues)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)  # exponential gaps
    assert all(0 <= t < 50257 for r in reqs[:20] for t in r.prompt)


def test_closed_loop_pool_and_bursts():
    pool = trafficgen.closed_loop(BATCH, 9, 50257)
    assert len(pool) == BATCH["pool"] and all(r.due_s is None for r in pool)
    assert all(128 <= r.prompt.size <= 768 and 64 <= r.max_new_tokens <= 256
               and r.prompt.size + r.max_new_tokens <= 1024 for r in pool)
    assert sorted(r.prompt.size for r in pool) == sorted(
        r.prompt.size for r in trafficgen.closed_loop(BATCH, 10, 50257))
    bursty = dict(CHAT, arrivals=dict(CHAT["arrivals"], burst=16))
    dues = [r.due_s for r in trafficgen.open_loop(bursty, 1, 100.0, 50257)]
    assert len(dues) % 16 == 0 and len(set(dues)) == len(dues) // 16


def test_token_batches_are_fresh_and_seeded():
    a, b = trafficgen.token_batches(4, 2, 8, 100), trafficgen.token_batches(4, 2, 8, 100)
    first, second = next(a)["input_ids"], next(a)["input_ids"]
    assert first.shape == (2, 8) and first.dtype == np.int32 and (first != second).any()
    assert (first == next(b)["input_ids"]).all()


# -- costs -------------------------------------------------------------------

@pytest.mark.parametrize("name,params,flops_per_token", [
    # hand count: L*(4*D*D + 8*D*D) matmul weights + V*D tied head, times 6; attention
    # 6*2*(S/2)*D per layer at S=1024
    ("gpt2-medium", 354_823_168, 6 * (24 * 12 * 1024 ** 2 + 50257 * 1024) + 24 * 6 * 1024 * 1024),
    ("gpt2-xl", 1_557_611_200, 6 * (48 * 12 * 1600 ** 2 + 50257 * 1600) + 48 * 6 * 1024 * 1600),
])
def test_required_flops_and_parameter_counts(name, params, flops_per_token):
    config = load("benchmark", "configs", name + ".json")
    assert costs.total_params(config) == params
    assert costs.train_flops_per_token(config, 1024) == flops_per_token
    # the program's own count charges the full S x S attention: ours is lower by the masked half
    L, D = config["model"]["n_layer"], config["model"]["n_embd"]
    assert costs.train_flops_per_token(config, 1024) < 6 * params + 12 * L * D * 1024


def test_kv_and_weight_bytes():
    medium, xl = (load("benchmark", "configs", n + ".json") for n in ("gpt2-medium", "gpt2-xl"))
    assert costs.kv_bytes_per_position(medium) == 98_304
    assert costs.kv_bytes_per_position(xl) == 307_200
    assert costs.weight_bytes(xl) == 2 * 1_557_611_200
    tick = costs.decode_tick(xl, {}, {"mean_live_rows": 16, "mean_live_kv_tokens": 8000})
    assert tick["bytes"] == costs.weight_bytes(xl) + 8000 * 307_200
    with pytest.raises(NotImplementedError):
        costs.dense_params({"model": dict(medium["model"], moe_num_experts=8)})


@pytest.mark.parametrize("remat,matmuls,operands", [(True, 2 + 2 + 3 + 4, 4 + 4 + 5 + 6),
                                                    (False, 2 + 3 + 4, 4 + 5 + 6)])
def test_flash_cost_counts_the_causal_half_of_every_call(remat, matmuls, operands):
    medium = load("benchmark", "configs", "gpt2-medium.json")
    cell = load("benchmark", "cells", "train-gpt2-medium-1chip.json")
    cell["train"]["remat"] = remat  # the second forward call is made only under remat
    cost = costs.flash_train_micro_step(medium, cell, {})
    # matmuls of 2 * B*H*(S*S/2)*hd, 24 layers
    assert cost["flops"] == 24 * matmuls * 2 * 8 * 16 * (1024 * 1024 / 2) * 64
    assert cost["bytes"] == 24 * operands * 8 * 16 * 1024 * 64 * 2


def test_a_family_of_programs_is_read_per_host_count_not_per_run(recorded):
    from benchmark import readers

    ctx = readers.Context(obs={"micro_steps": 6, "none": 0}, config={}, cell={}, peaks=None,
                          chips=1, trace=recorded)
    per = lambda key: readers.evaluate(
        {"reduction": "module_ms_per", "pattern": "^jit_micro_fn", "per": key}, ctx)
    assert per("micro_steps") == pytest.approx(3.49, abs=0.02)
    assert per("none") is None and per("missing") is None  # nothing to divide by: left out


def test_peaks_table_has_its_source_and_no_default():
    peaks = load("benchmark", "peaks.json")
    assert peaks["TPU v5 lite"] == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                                    "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9}
    assert "Google Cloud" in peaks["_source"] and "cpu" not in peaks
