"""The readers of the trainer's own spans and scopes (PR 26), on hand-built
observations and without the program, then through a rehearsal of the cell
at toy sizes on the CPU.  A program that lacks the spans or the scopes, as
the parent of that PR does, makes each reader return None."""
import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import harness, spanread  # noqa: E402
from benchmark.trace import ProgramText, Trace  # noqa: E402

CELL = "inception_v1.train_b256"
SPAN_METRICS = ["train.feed_wait_ms", "train.feed_produce_ms",
                "train.feed_stage_max_ms", "train.feed_h2d_ms",
                "train.loop_host_ms", "train.loop_unnamed_ms"]
IDLE_METRICS = ["train.idle_under_feed_pct", "train.idle_under_h2d_pct",
                "train.idle_under_loop_pct", "train.idle_unnamed_pct"]
SCOPE_METRICS = ["train.fwd_ms", "train.bwd_ms", "train.lrn_ms",
                 "train.pool_ms"]
NEW = SPAN_METRICS + IDLE_METRICS + SCOPE_METRICS


def read(name, obs):
    return harness.load_reader(name)(obs)


# -- the manifest -----------------------------------------------------------

def test_manifest_lists_each_new_metric_once_with_a_reader():
    per_layer = harness.load_manifest()["per_layer"]
    names = [m["name"] for m in per_layer]
    assert len(names) == len(set(names))
    layers = {m["name"]: m["layer"] for m in per_layer}
    for name in NEW:
        entry = next(m for m in per_layer if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_records_per_s"
        assert callable(harness.load_reader(name))
    assert {layers[n] for n in SPAN_METRICS[:4]} == {"dataset"}
    assert layers["train.loop_unnamed_ms"] == "optim"
    assert layers["train.idle_unnamed_pct"] == "device"
    assert layers["train.fwd_ms"] == "model step"
    assert layers["train.lrn_ms"] == "kernels"


# -- span totals ------------------------------------------------------------

# ten steps of 186 ms: the loop waits 126 of them, works 58, 2 are unnamed
SPANS = {"data-load": (1.26, 10), "dispatch": (0.20, 10),
         "host-wait": (0.04, 1), "flush": (0.25, 1), "bookkeep": (0.09, 20),
         "h2d": (0.38, 10), "h2d/prefetch": (0.38, 10), "loop": (1.86, 10),
         "data-load/fetch": (1.80, 10),
         "data-load/fetch/source:LocalArrayDataSet": (0.10, 10),
         "data-load/fetch/stage/0:SampleToBatch": (1.65, 10),
         "data-load/fetch/stage/1:Stage": (0.02, 10)}


def test_span_readers_on_a_hand_built_window(capsys):
    obs = {"spans": SPANS, "steps": 10}
    assert read("train.feed_wait_ms", obs) == pytest.approx(126.0)
    assert read("train.feed_produce_ms", obs) == pytest.approx(180.0)
    assert read("train.feed_h2d_ms", obs) == pytest.approx(38.0)
    assert read("train.feed_stage_max_ms", obs) == pytest.approx(165.0)
    err = capsys.readouterr().err
    assert err.index("stage/0:SampleToBatch") < err.index("source:")
    assert read("train.loop_host_ms", obs) == pytest.approx(58.0)
    # the transfer thread's wall is in ``h2d`` but blocks nothing
    assert read("train.loop_unnamed_ms", obs) == pytest.approx(2.0)


def test_inline_transfers_count_as_named_loop_time():
    spans = dict(SPANS, h2d=(0.40, 10))       # 2 ms a step on the loop
    assert read("train.loop_unnamed_ms", {"spans": spans, "steps": 10}) \
        == pytest.approx(0.0, abs=1e-9)
    del spans["h2d/prefetch"]                  # prefetch off: all inline
    spans["loop"] = (2.26, 10)
    assert read("train.loop_unnamed_ms", {"spans": spans, "steps": 10}) \
        == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_returns_none_without_its_source(name):
    assert read(name, {}) is None
    # the parent's spans: no flush, bookkeep, loop, stage or h2d/prefetch
    parent = {k: SPANS[k] for k in ("data-load", "dispatch", "host-wait",
                                    "h2d", "data-load/fetch")}
    value = read(name, {"spans": parent, "steps": 10, "trace": None,
                        "program_text": None})
    if name == "train.feed_wait_ms":
        assert value == pytest.approx(126.0)
    elif name == "train.feed_produce_ms":
        assert value == pytest.approx(180.0)
    else:
        assert value is None


# -- the device's idle time by the host's state -----------------------------

def _trace():
    """1,000 ns of trace: the device runs [100, 200) and [600, 700)."""
    ops = [("%fusion.7 = f32[8] fusion(%x)", 100, 100),
           ("%fusion.7 = f32[8] fusion(%x)", 600, 100)]
    host = [("$python", 0, 1000, "main"),
            ("data-load", 200, 350, "main"), ("dispatch", 550, 50, "main"),
            ("bookkeep", 700, 20, "main"), ("flush", 720, 40, "main"),
            ("data-load/fetch", 150, 350, "producer"),
            ("h2d/prefetch", 500, 45, "transfer")]
    return Trace({"/device:TPU:0": ops}, host)


def test_idle_partition_sums_to_the_idle_share():
    obs = {"trace": _trace(), "traced_s": 1000e-9}
    idle = read("train.device_idle_pct", obs)
    assert idle == pytest.approx(80.0)
    feed = read("train.idle_under_feed_pct", obs)
    h2d = read("train.idle_under_h2d_pct", obs)
    loop = read("train.idle_under_loop_pct", obs)
    unnamed = read("train.idle_unnamed_pct", obs)
    # the loop waits [200, 550): the transfer covers [500, 545) of it, the
    # producer's draw [200, 500); the loop works [550, 600) and [700, 760)
    assert feed == pytest.approx(30.0)
    assert h2d == pytest.approx(4.5)
    assert loop == pytest.approx(11.0)
    assert unnamed == pytest.approx(34.5)
    assert feed + h2d + loop + unnamed == pytest.approx(idle)


def test_idle_partition_needs_the_feed_threads_spans():
    trace = _trace()
    trace.host_spans = [s for s in trace.host_spans
                        if s[0] not in ("data-load/fetch", "h2d/prefetch")]
    obs = {"trace": trace, "traced_s": 1000e-9}
    assert all(read(name, obs) is None for name in IDLE_METRICS)
    assert read("train.device_idle_pct", obs) == pytest.approx(80.0)


def test_interval_arithmetic():
    a = spanread.merged([(5, 9), (0, 3), (2, 4), (7, 7)])
    assert a == [(0, 4), (5, 9)]
    assert spanread.intersect(a, [(3, 6), (8, 12)]) == [(3, 4), (5, 6),
                                                        (8, 9)]
    assert spanread.subtract(a, [(1, 2), (3, 6)]) == [(0, 1), (2, 3),
                                                      (6, 9)]
    assert spanread.subtract([(0, 10)], []) == [(0, 10)]
    assert spanread.total(a) == 8


# -- the step's operations by scope -----------------------------------------

STEP = "jit(train_step)"
HLO = f'''HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p: f32[8,8]) -> f32[8,8] {{
  %p = f32[8,8]{{1,0}} parameter(0)
  %convolution.3 = f32[8,8]{{1,0}} convolution(%p, %p), window={{size=1}}, dim_labels=bf_io->bf, metadata={{op_name="{STEP}/transpose(jvp(Concat))/Sequential/SpatialConvolution/conv_general_dilated"}}
  %maximum.1 = f32[8,8]{{1,0}} maximum(%convolution.3, %p), metadata={{op_name="{STEP}/transpose(jvp(Concat))/Sequential/ReLU/max"}}
  ROOT %add.1 = f32[8,8]{{1,0}} add(%maximum.1, %p), metadata={{op_name="{STEP}/transpose(jvp(Concat))/Sequential/ReLU/add_any"}}
}}

%fused_computation.2 (p: f32[8,8]) -> f32[8,8] {{
  %p.2 = f32[8,8]{{1,0}} parameter(0)
  %sqrt.1 = f32[8,8]{{1,0}} sqrt(%p.2), metadata={{op_name="{STEP}/jvp(SpatialCrossMapLRN)/sqrt"}}
  ROOT %divide.1 = f32[8,8]{{1,0}} divide(%p.2, %sqrt.1), metadata={{op_name="{STEP}/jvp(SpatialCrossMapLRN)/div"}}
}}

ENTRY %main.1 (x: f32[8,8]) -> f32[8,8] {{
  %x = f32[8,8]{{1,0}} parameter(0)
  %reduce-window.2 = f32[8,8]{{1,0}} reduce-window(%x, %x), window={{size=1x1}}, to_apply=%add, metadata={{op_name="{STEP}/jvp(SpatialMaxPooling)/reduce_window_max"}}
  %fusion.7 = f32[8,8]{{1,0}} fusion(%reduce-window.2), kind=kOutput, calls=%fused_computation.1
  %fusion.8 = f32[8,8]{{1,0}} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.2
  %multiply.9 = f32[8,8]{{1,0}} multiply(%fusion.8, %x), metadata={{op_name="{STEP}/transpose(jvp(SpatialCrossMapLRN))/mul"}}
  %multiply.5 = f32[8,8]{{1,0}} multiply(%multiply.9, %x), metadata={{op_name="{STEP}/optim-update/mul"}}
  %add.9 = f32[8,8]{{1,0}} add(%multiply.5, %x), metadata={{op_name="{STEP}/obs-taps/add"}}
  ROOT %copy.4 = f32[8,8]{{1,0}} copy(%add.9)
}}
'''
# device microseconds of one step
OP_US = {"fusion.7": 3000, "reduce-window.2": 2000, "fusion.8": 1000,
         "multiply.9": 1500, "multiply.5": 500, "add.9": 250, "copy.4": 250}


def _scoped_obs(text=HLO):
    at, ops = 0, []
    for name, us in OP_US.items():
        ops.append((f"%{name} = f32[8,8] op(%x)", at, us * 1000))
        at += us * 1000
    return {"trace": Trace({"/device:TPU:0": ops}, []), "steps": 1,
            "program_text": ProgramText(text)}


def test_scope_of_an_op_name():
    assert spanread.scope_of(
        f"{STEP}/jvp(Concat)/Sequential/ReLU/jit(relu)/max") == \
        ("ReLU", "fwd")
    assert spanread.scope_of(
        f"{STEP}/transpose(jvp(SpatialMaxPooling))/select_and_scatter") == \
        ("SpatialMaxPooling", "bwd")
    assert spanread.scope_of(f"{STEP}/optim-update/mul") == \
        ("optim-update", None)
    assert spanread.scope_of(f"{STEP}/jvp()/reduce_sum") == (None, "fwd")
    assert spanread.scope_of("") == (None, None)


def test_scope_readers_on_a_hand_built_step(capsys):
    obs = _scoped_obs()
    # the fusion that holds the convolution is the convolution's, though
    # two of its three parts are the ReLU's
    assert read("train.bwd_ms", obs) == pytest.approx(3.0 + 1.5)
    assert read("train.fwd_ms", obs) == pytest.approx(2.0 + 1.0)
    assert read("train.lrn_ms", obs) == pytest.approx(1.0 + 1.5)
    assert read("train.pool_ms", obs) == pytest.approx(2.0)
    table = spanread.scope_seconds(obs)
    assert table[("SpatialConvolution", "bwd", "CONV-BWD")] == \
        pytest.approx(3e-3)
    assert table[("unscoped", None, "LAYOUT")] == pytest.approx(0.25e-3)
    err = capsys.readouterr().err
    assert "scope optim-update - 0.500" in err
    assert "other SpatialCrossMapLRN 2.500" in err     # once: memoised
    assert err.count("ELTWISE/OTHER ms/step by scope") == 1


def test_scope_readers_return_none_for_a_program_without_scopes():
    import re
    plain = re.sub(r"(jvp\()[A-Za-z]*(\)+)/(?:[A-Z]\w*/)*", r"\1\2/", HLO)
    assert "SpatialConvolution" not in plain and "jvp()" in plain
    obs = _scoped_obs(plain)
    assert all(read(name, obs) is None for name in SCOPE_METRICS)
    # an operation the text does not hold is booked as unknown, not lost
    obs = _scoped_obs()
    obs["trace"].device_ops["/device:TPU:0"].append(
        ("%fusion.99 = f32[8] fusion(%x)", 10 ** 8, 1000))
    assert spanread.scope_seconds(obs)[("unknown", None, "UNKNOWN")] == \
        pytest.approx(1e-6)


# -- the cell, rehearsed ----------------------------------------------------

TOY = {"batch": 8, "records": 32, "classes": 10, "reference_block": 4,
       "check": {"loss_gap": 1e-4, "first_grad_gap": 0.15,
                 "change3_gap": 0.15, "change3_error": 0.11}}


@pytest.fixture(scope="module")
def traced_rehearsal():
    return harness.run_cell(CELL, 26, 1.0, True, sizes=TOY)


def test_rehearsal_reports_every_span_read_metric(traced_rehearsal):
    r = traced_rehearsal
    assert r["correct"] is True, r["check"]
    for name in SPAN_METRICS:
        assert r["metrics"][name]["value"] >= 0, name
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # the accepted metric adds the overlapped transfer to the wait
    assert m["train.input_wait_ms"] > m["train.feed_wait_ms"]
    assert m["train.feed_stage_max_ms"] <= m["train.feed_produce_ms"]
    assert m["train.loop_host_ms"] >= m["train.dispatch_ms"]
    assert json.loads(json.dumps(r["metrics"])) == r["metrics"]


def test_rehearsal_on_the_cpu_reports_no_device_share(traced_rehearsal):
    # no TPU plane in the trace: nothing that is a share of the device
    assert not set(traced_rehearsal["metrics"]) & set(IDLE_METRICS
                                                      + SCOPE_METRICS)
