"""The benchmark's own arithmetic, on the CPU and without the program: the
trace reduction on a small recorded trace, the operation counts against a
hand count, the traffic generator, the metric arithmetic, the manifest."""
import itertools
import json
import os
import re
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import check, flops, stats, traffic  # noqa: E402
from benchmark.trace import ProgramText, Trace, op_short_name  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")


# -- the trace reduction ----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    """Three calls of a toy conv + pool + matmul training step, traced on a
    v5e (my chip run, PR 25): 42 device operations, 14 a call."""
    return Trace.from_file(os.path.join(DATA, "toy_conv_step.xplane.pb"))


def test_recorded_trace_busy_union_and_idle_share(recorded):
    ops = recorded.device_ops["/device:TPU:0"]
    assert len(ops) == 42
    # no two operations of one chip overlap here: the union is the sum
    assert recorded.busy_s() == pytest.approx(
        sum(d for _, _, d in ops) / 1e9, rel=1e-9)
    assert recorded.busy_s() == pytest.approx(1.420501e-3, rel=1e-6)
    span = (max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)) / 1e9
    idle_share = 1.0 - recorded.busy_s() / span
    assert idle_share == pytest.approx(0.93585, abs=1e-4)


def test_recorded_trace_sums_by_operation_and_category(recorded):
    seconds = recorded.op_seconds()
    assert seconds["select_and_scatter.9"] == pytest.approx(601.049e-6)
    assert seconds["maximum_convert_fusion"] == pytest.approx(245.376e-6)
    assert sum(seconds.values()) == pytest.approx(recorded.busy_s())
    by_kind = {}
    for name, s in seconds.items():
        kind = ("POOL-BWD" if name.startswith("select_and_scatter")
                else "COPY" if name.startswith("copy") else "OTHER")
        by_kind[kind] = by_kind.get(kind, 0.0) + s
    assert by_kind["POOL-BWD"] == pytest.approx(601.049e-6)
    assert by_kind["COPY"] > 154e-6


def test_recorded_trace_names_idle_gaps_by_host_span(recorded):
    gaps = dict(recorded.idle_gaps())
    # the host slept between the calls, outside its annotated span
    assert gaps["unattributed"] == pytest.approx(20.72e-3, rel=1e-2)
    spans = [s for s in recorded.host_spans if s[0] == "hostspan"]
    assert len(spans) == 3
    start, dur = spans[0][1], spans[0][2]
    assert recorded._cover(start + 1, start + dur - 1) == "hostspan"


def test_busy_union_merges_overlaps():
    t = Trace({"/device:TPU:0": [("a", 0, 100), ("b", 50, 100),
                                 ("c", 300, 50)]}, [])
    assert t.busy_intervals("/device:TPU:0") == [[0, 150], [300, 350]]
    assert t.busy_s() == pytest.approx(200e-9)
    assert t.idle_gaps() == [("unattributed", pytest.approx(150e-9))]


HLO = '''HloModule jit_step, is_scheduled=true

FileNames
1 "/x/bigdl_tpu/nn/pooling.py"
2 "/x/bigdl_tpu/nn/conv.py"
3 "/x/bigdl_tpu/optim/local_optimizer.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=7 end_line=7 column=4 end_column=46}
2 {file_name_id=2 function_name_id=1 line=6 end_line=6 column=11 end_column=92}
3 {file_name_id=3 function_name_id=1 line=5 end_line=5 column=12 end_column=58}

StackFrames
1 {file_location_id=3 parent_frame_id=1}
2 {file_location_id=1 parent_frame_id=2}
3 {file_location_id=2 parent_frame_id=2}


%fused_computation.1 (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8]{1,0} parameter(0)
  %convolution.3 = f32[8,8]{1,0} convolution(%p, %p), window={size=1}, dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp(conv))/conv_general_dilated" stack_frame_id=3}
  ROOT %add.1 = f32[8,8]{1,0} add(%convolution.3, %p), metadata={op_name="jit(step)/add" stack_frame_id=3}
}

ENTRY %main.1 (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %reduce-window.2 = f32[8,8]{1,0} reduce-window(%x, %x), window={size=1x1}, to_apply=%fused_computation.1, metadata={op_name="jit(step)/jvp(pool)/reduce_window_max" stack_frame_id=2}
  %fusion.7 = f32[8,8]{1,0} fusion(%reduce-window.2), kind=kOutput, calls=%fused_computation.1
  ROOT %copy.4 = f32[8,8]{1,0} copy(%fusion.7)
}
'''


def test_program_text_category_and_source_files():
    program = ProgramText(HLO)
    assert program.entry == "main.1"
    assert program.category("reduce-window.2") == "POOL-FWD"
    assert program.source_files("reduce-window.2")[0] == "pooling.py"
    # a fusion is what it holds: a transposed convolution, traced from
    # conv.py under the optimizer's frame
    assert program.category("fusion.7") == "CONV-BWD"
    assert program.source_files("fusion.7") == ["conv.py",
                                                "local_optimizer.py"]
    assert program.category("copy.4") == "LAYOUT"
    assert program.category("not-there") == "UNKNOWN"
    assert op_short_name("%fusion.7 = f32[8,8]{1,0} fusion(...)") == \
        "fusion.7"


# -- operations and bytes ---------------------------------------------------

@pytest.fixture(scope="module")
def inception_cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "inception_v1.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def lm_cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "transformer_big_lm.json")) as f:
        return json.load(f)


def test_inception_3a_flops_against_a_hand_count(inception_cfg):
    table = {n: f for n, f, _ in flops.inception_conv_table(inception_cfg)}
    hw = 28 * 28                    # 3a runs at 28 x 28 on 192 channels
    by_hand = {"inception_3a/1x1": 2 * 64 * 192 * hw,
               "inception_3a/3x3_reduce": 2 * 96 * 192 * hw,
               "inception_3a/3x3": 2 * 128 * 96 * 9 * hw,
               "inception_3a/5x5_reduce": 2 * 16 * 192 * hw,
               "inception_3a/5x5": 2 * 32 * 16 * 25 * hw,
               "inception_3a/pool_proj": 2 * 32 * 192 * hw}
    for name, want in by_hand.items():
        assert table[name] == want
    assert sum(by_hand.values()) == 256_098_304   # the paper: 128M ops
    assert table["conv1/7x7_s2"] == 2 * 64 * 3 * 49 * 112 * 112
    assert table["inception_5b/1x1"] == 2 * 384 * 832 * 49


def test_inception_whole_model_flops(inception_cfg):
    forward = sum(f for _, f, _ in flops.inception_conv_table(inception_cfg))
    # GoogLeNet: about 1.5 G multiply-adds a forward pass (the paper's
    # table 1 sums to 1.5 G "ops")
    assert 2.9e9 < forward < 3.3e9
    train = flops.inception_train_flops_per_record(inception_cfg)
    conv1 = 2 * 64 * 3 * 49 * 112 * 112
    assert train == pytest.approx(3 * forward - conv1)
    assert flops.inception_train_flops_per_record(
        inception_cfg, convs_only=True) == pytest.approx(
            train - 3 * 2 * 1000 * 1024)


def test_transformer_layer_flops_and_bytes_against_a_hand_count(lm_cfg):
    d, h, v, layers = 1024, 4096, 32768, 6
    context = 700
    per_layer = (2 * 4 * d * d          # q, k, v, o projections
                 + 2 * 2 * d * h        # feed-forward
                 + 2 * 2 * context * d)  # q.k and p.v over the context
    assert flops.lm_position_flops(lm_cfg, context) == \
        layers * per_layer + 2 * d * v
    params = (v * d + d + layers * (4 * (d * d + d) + 2 * d * h + h + d
                                    + 4 * d) + 2 * d + d * v + v)
    assert flops.lm_param_count(lm_cfg) == params
    assert 109e6 < params < 144e6
    assert flops.lm_kv_bytes_per_token(lm_cfg) == 2 * d * 4 * layers == 49152
    assert flops.lm_step_weight_bytes(lm_cfg) == (params - v * d) * 4
    # a request of 300 prompt tokens and 100 outputs runs positions 0..398
    by_position = sum(flops.lm_position_flops(lm_cfg, p + 1)
                      for p in range(399))
    assert flops.lm_span_flops(lm_cfg, 0, 399) == pytest.approx(by_position)
    assert flops.lm_span_kv_bytes(lm_cfg, 0, 399) == pytest.approx(
        49152 * sum(p + 1 for p in range(399)))


# -- traffic ----------------------------------------------------------------

DECODE_MIX = {"pool": 32, "prompt_len": [256, 1024], "output_len": [64, 256]}


def test_decode_traffic_same_seed_same_requests_other_seed_others():
    take = lambda seed, n=32: list(itertools.islice(
        traffic.decode_requests(DECODE_MIX, seed, 32768), n))
    a, b, c = take(3000000017), take(3000000017), take(5)
    assert a == b
    assert a != c
    # past the pool the lengths come round again, the prompts never do
    twice = take(3000000017, 64)
    assert [len(r["prompt"]) for r in twice[32:]] == \
        [len(r["prompt"]) for r in twice[:32]]
    assert len({tuple(r["prompt"]) for r in twice}) == 64
    # every seed gets the same sizes, in another order
    sizes = lambda reqs: sorted(len(r["prompt"]) for r in reqs)
    assert sizes(a) == sizes(c)
    assert sorted(r["n_words"] for r in a) == sorted(r["n_words"] for r in c)
    assert min(sizes(a)) == 256 and max(sizes(a)) == 1024
    assert all(0 <= t < 32768 for r in a for t in r["prompt"])
    assert all(len(r["prompt"]) + r["n_words"] - 1 <= 1280 for r in a)


def test_image_traffic_is_seeded():
    mix = {"records": 6, "class_offset": 0.1}
    x1, y1 = traffic.image_records(mix, 11, (3, 8, 8), 10)
    x2, y2 = traffic.image_records(mix, 11, (3, 8, 8), 10)
    x3, _ = traffic.image_records(mix, 12, (3, 8, 8), 10)
    assert (x1 == x2).all() and (y1 == y2).all()
    assert not (x1 == x3).all()
    assert x1.dtype.name == "float32" and x1.shape == (6, 3, 8, 8)
    assert y1.min() >= 1 and y1.max() <= 10
    assert len({float(v) for v in x1[:, 0, 0, 0]}) == 6


# -- metric arithmetic ------------------------------------------------------

def _request(submit, prompt_len, chunk_times, per_chunk=8):
    return {"submit": submit, "prompt_len": prompt_len,
            "chunks": [(t, per_chunk) for t in chunk_times],
            "done": chunk_times[-1] if chunk_times else None}


def test_rates_and_tails_over_a_timeline_with_a_stall():
    # ten requests stream 8 tokens every 0.1 s; one stalls for 2 s
    # between its second and third chunk
    steady = [_request(0.0, 100, [1.0 + 0.1 * i for i in range(10)])
              for _ in range(9)]
    stalled = _request(0.0, 100, [1.0, 1.1] + [3.1 + 0.1 * i
                                               for i in range(8)])
    requests = steady + [stalled]
    t_open, t_close = 0.5, 4.5
    # the rate is all tokens over all the time of the window, stall and all
    assert stats.tokens_in_window(requests, t_open, t_close) == 800
    assert stats.rate(800, t_open, t_close) == pytest.approx(200.0)
    # a window that closes inside the stall counts only what arrived
    assert stats.tokens_in_window(requests, t_open, 2.0) == 9 * 80 + 16
    ttfts = stats.ttfts_ms(requests, t_open, t_close)
    assert ttfts == [pytest.approx(1000.0)] * 10
    # per request: (last - first) / (tokens - 1); the stall shows in the tail
    tpots = stats.tpots_ms(requests, t_open, t_close)
    assert sorted(tpots)[0] == pytest.approx(900.0 / 79)
    assert max(tpots) == pytest.approx(2800.0 / 79)
    assert stats.percentile(tpots, 95) > stats.percentile(tpots, 50)
    # a request whose first token came before the window has no ttft in it
    assert stats.ttfts_ms(requests, 1.05, t_close) == []
    # one that completes after the window has no tpot in it
    assert len(stats.tpots_ms(requests, t_open, 3.0)) == 9


def test_percentile_interpolates():
    assert stats.percentile([], 95) is None
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert stats.spread([10, 10, 11, 11, 12, 12]) == pytest.approx(
        (12 - 10) / 11, rel=0.3)


def test_positions_done_follows_the_stream():
    r = _request(10.0, 100, [12.0, 13.0], per_chunk=8)
    assert stats.positions_done(r, 9.0) == 0.0
    assert stats.positions_done(r, 11.0) == pytest.approx(107 / 2)
    assert stats.positions_done(r, 12.0) == pytest.approx(107.0)
    assert stats.positions_done(r, 12.5) == pytest.approx(111.0)
    assert stats.positions_done(r, 99.0) == pytest.approx(115.0)
    assert stats.position_spans([r], 11.0, 12.5) == [
        (pytest.approx(53.5), pytest.approx(111.0))]


# -- the comparison ---------------------------------------------------------

def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "tiny": 3e-6}
    gap, leaf = check.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)
    # a leaf that has not moved reads about 1
    gap, leaf = check.worst_leaf_gap({"a": 1.0, "b": 0.0, "tiny": 1e-6}, ref)
    assert leaf == "b" and gap == pytest.approx(1.0)
    assert check.negligible_gradient_leaves(ref) == {"tiny"}
    ok, table = check.verdict([("x", 0.1, 0.2), ("y", float("nan"), 1.0)])
    assert not ok and table["x"] == {"value": 0.1, "limit": 0.2}
    assert check.verdict([("x", 0.1, 0.2)])[0]


def test_tree_relative_error_is_the_norm_of_the_difference():
    ref = {"a": {"weight": [3.0, 0.0], "bias": [4.0]}}
    assert check.tree_relative_error(ref, ref) == 0.0
    # a state left unchanged: the program's change is nought, the error 1
    still = {"a": {"weight": [0.0, 0.0], "bias": [0.0]}}
    assert check.tree_relative_error(still, ref) == pytest.approx(1.0)
    off = {"a": {"weight": [3.0, 0.5], "bias": [4.0]}}
    assert check.tree_relative_error(off, ref) == pytest.approx(0.1)


def test_slice_rates_count_steps_in_each_ten_seconds():
    from benchmark.runners.train import _slice_rates
    # a step every 0.2 s, but none between 12 s and 15 s: a stall
    ticks, n = [], 100
    for i in range(151):
        t = 1000.0 + 0.2 * i
        if not 12.0 < t - 1000.0 < 15.0:
            n += 1
        ticks.append((t, n))
    rates = _slice_rates(ticks, 1000.0, 30.0, batch=256)
    assert len(rates) == 3
    assert rates[0] == pytest.approx(50 * 256 / 10.0, rel=0.03)
    assert rates[1] == pytest.approx(36 * 256 / 10.0, rel=0.05)
    assert rates[2] == pytest.approx(50 * 256 / 10.0, rel=0.03)
    assert _slice_rates(ticks, 1000.0, 1.0, batch=256) == []


def test_memory_watch_reports_the_peaks_and_stops_its_thread():
    import time

    from benchmark.harness import MemoryWatch
    watch = MemoryWatch(period=0.01)
    watch.start()
    time.sleep(0.05)
    got = watch.stop()
    assert not watch._thread.is_alive()
    assert got["memory_reads"] >= 1
    assert got["memory_peak_bytes"] >= max(got["peak_bytes_in_use"],
                                           got["peak_bytes_reserved"])


# -- the manifest -----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_names_files_that_exist_and_plain_names():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for path in m["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    configs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert os.path.isfile(os.path.join(ROOT, held["reference"]))
        assert all(NAME.match(k) for k in c["reduced"])
    cells = set()
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = os.path.join(ROOT, "benchmark", "workloads",
                            w["name"] + ".json")
        with open(cell) as f:
            held = json.load(f)
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "runners", held["runner"] + ".py"))
        cells.add(w["name"])
    e2e = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in e2e
    for e in m["end_to_end"]:
        assert NAME.match(e["name"]) and 0 < e["bound"] <= 0.1
        assert set(e.get("workloads", [])) <= cells
    for p in m["per_layer"]:
        assert NAME.match(p["name"]) and p["moves"] in e2e
        assert set(p["workloads"]) <= cells
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           p["name"] + ".py"))
        if p["name"].endswith("_roofline") or "mfu" in p["name"]:
            assert p["unit"] == "%"
    for cell in cells:
        assert any(cell in p["workloads"] for p in m["per_layer"])
