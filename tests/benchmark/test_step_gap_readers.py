"""The readers of the loop's step timeline (PR 37): ``loop.*`` on hand-built
span totals, ``step.gap_*`` on a small hand-made trace with every number
worked out by hand.  A program without ``dispatch/call``, as the parent of
that PR, makes each reader return None, and so does a run without an
accelerator: the traced rehearsals of the four cells (``test_span_readers``,
``test_lm_rehearsal``, ``test_mla_rehearsal``, ``test_lfm2_rehearsal``) run
every reader here on the CPU and report none of these names."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import harness, spanread_steps  # noqa: E402
from benchmark.trace import ProgramText, Trace  # noqa: E402

CELLS = ["inception_v1.train_b256", "trinity_mini.train_s8k",
         "kanana_2_30b_a3b.train_s8k", "lfm2_24b_a2b.train_s8k"]
# name: (unit, better, source, layer)
ENTRIES = {
    "loop.call_ms": ("ms/step", "lower", "program_span", "optim"),
    "loop.prepare_ms": ("ms/step", "lower", "program_span", "optim"),
    "loop.between_calls_ms": ("ms/step", "lower", "program_span", "optim"),
    "loop.in_flight_steps": ("steps", "higher", "program_counter", "optim"),
    "loop.device_empty_pct": ("%", "lower", "program_counter", "optim"),
    "step.gap_ms": ("ms/step", "lower", "device_trace", "device"),
    "step.gap_host_late_ms": ("ms/step", "lower", "device_trace", "optim"),
    "step.gap_in_call_ms": ("ms/step", "lower", "device_trace", "device"),
}
LOOP = [n for n in ENTRIES if n.startswith("loop.")]


def read(name, obs):
    return harness.load_reader(name)(obs)


# -- the manifest -----------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_manifest_entry_is_found_by_name_with_its_cells_and_reader(name):
    per_layer = harness.load_manifest()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == name]
    unit, better, source, layer = ENTRIES[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "train_records_per_s", "workloads": CELLS}
    assert callable(harness.load_reader(name))
    # the layer is one the accepted entries name
    assert layer in {m["layer"] for m in per_layer
                     if m["name"] not in ENTRIES}


# -- the span totals --------------------------------------------------------

# a window of 20 steps: (seconds, bookings)
SPANS = {"data-load": (0.02, 20), "dispatch": (9.0, 20),
         "dispatch/prepare": (0.24, 20), "dispatch/call": (8.7, 20),
         "dispatch/in-flight": (17.0, 20), "dispatch/device-empty": (0.0, 3),
         "host-wait": (0.9, 2), "flush": (0.04, 2), "bookkeep": (0.02, 40),
         "loop": (10.0, 20)}


PEAKS = {"bf16_flops": 197e12}     # the harness gives them for a TPU only


def test_loop_readers_on_a_hand_built_window():
    obs = {"spans": SPANS, "steps": 20, "peaks": PEAKS}
    assert read("loop.call_ms", obs) == pytest.approx(435.0)
    assert read("loop.prepare_ms", obs) == pytest.approx(12.0)
    assert read("loop.between_calls_ms", obs) == pytest.approx(65.0)
    assert read("loop.in_flight_steps", obs) == pytest.approx(0.85)
    assert read("loop.device_empty_pct", obs) == pytest.approx(15.0)
    # no dispatch found the device empty: the path was never booked
    some = {k: v for k, v in SPANS.items() if k != "dispatch/device-empty"}
    assert read("loop.device_empty_pct", dict(obs, spans=some)) == 0.0


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_every_reader_returns_none_on_the_parents_program(name):
    parent = {k: v for k, v in SPANS.items()
              if not k.startswith("dispatch/")}
    assert read(name, {"spans": parent, "steps": 20, "trace": _trace(),
                       "program_text": None, "traced_s": 1e-3,
                       "peaks": PEAKS}) is None
    assert read(name, {"spans": {}, "steps": 0}) is None
    assert read(name, {}) is None


# -- the gaps between the steps on the device -------------------------------

HLO = '''HloModule jit_train_step, is_scheduled=true

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused
}

ENTRY %main.1 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused
  %while.2 = f32[8]{0} while(%fusion.1), condition=%cond, body=%body
  ROOT %copy.3 = f32[8]{0} copy(%while.2)
}
'''
US = 1000        # the trace's clock counts nanoseconds


def _step(at):
    """One step on the device, 450 us from ``at``: a fusion, a loop whose
    body's operations lie inside it, a copy."""
    op = lambda name, start, dur: (f"%{name} = f32[8] op(%x)",
                                   (at + start) * US, dur * US)
    return [op("fusion.1", 0, 100), op("while.2", 100, 300),
            op("fusion.9", 150, 100), op("fusion.9", 260, 130),
            op("copy.3", 400, 50)]


def _trace(calls=True):
    """Four steps on the device, at 1000, 1500, 2150 and 2900 us.

    - A ends at 1450; its call came before the trace began.
    - B starts at 1500: its call opened at 1300, so all 50 us of the gap
      lie in the call.
    - C starts at 2150, 200 us after B ended (1950): the loop flushed, drew
      a batch, made its key (two device operations in the gap, one of them
      under a name of the step's loop body) and called at 2100: 150 us
      late, 50 in the call.
    - D starts at 2900, 300 us after C ended (2600), though its call opened
      at 2300: the runtime held it in ``ExecutePrepare``."""
    ops = _step(1000) + _step(1500) + _step(2150) + _step(2900) + [
        ("%xor.5 = u32[2] xor(%a, %b)", 2020 * US, 10 * US),
        ("%fusion.9 = u32[2] fusion(%a)", 2060 * US, 10 * US)]
    main = [("$python", 0, 4000), ("dispatch", 1290, 235),
            ("dispatch/prepare", 1292, 7), ("dispatch/call", 1300, 220),
            ("bookkeep", 1525, 15), ("host-wait", 1540, 420),
            ("flush", 1960, 30), ("data-load", 1992, 6),
            ("dispatch", 1999, 206), ("dispatch/prepare", 2000, 90),
            ("dispatch/call", 2100, 100), ("bookkeep", 2205, 15),
            ("dispatch", 2290, 665), ("dispatch/prepare", 2292, 7),
            ("dispatch/call", 2300, 650),
            ("PjitFunction(train_step)", 2310, 630),
            ("ExecutePrepare", 2320, 570), ("bookkeep", 2955, 15)]
    if not calls:
        main = [e for e in main if not e[0].startswith("dispatch/")]
    host = [(name, start * US, dur * US, "main")
            for name, start, dur in main]
    # the feed's threads, under the same line name as the loop's: a draw,
    # and a transfer that lies inside the last call
    host.append(("data-load/fetch", 1900 * US, 600 * US, "main"))
    host.append(("h2d/prefetch", 2400 * US, 500 * US, "main"))
    return Trace({"/device:TPU:0": ops}, host)


def _obs(**kw):
    return {"trace": _trace(**kw), "program_text": ProgramText(HLO),
            "steps": 3, "traced_s": 4000e-6}


def test_steps_on_the_device_and_what_lies_between_them():
    steps, between = spanread_steps.device_steps(
        _trace().device_ops["/device:TPU:0"], ProgramText(HLO))
    assert steps == [(1000 * US, 1450 * US), (1500 * US, 1950 * US),
                     (2150 * US, 2600 * US), (2900 * US, 3350 * US)]
    # by position, not by name: one carries a name of the step's text
    assert between == [("xor.5", 2020 * US, 2030 * US),
                       ("fusion.9", 2060 * US, 2070 * US)]


def test_gap_readers_on_a_hand_made_trace(capsys):
    obs = _obs()
    gap = read("step.gap_ms", obs)
    late = read("step.gap_host_late_ms", obs)
    in_call = read("step.gap_in_call_ms", obs)
    # gaps of 50, 200 and 300 us, of which 0, 150 and 0 before the call
    assert gap == pytest.approx(0.550 / 3, abs=1e-6)
    assert late == pytest.approx(0.050)
    assert in_call == pytest.approx(0.400 / 3, abs=1e-6)
    assert round(late + in_call, 6) == gap
    g = spanread_steps.step_gaps(obs)
    assert g["gaps"] == 3
    assert g["gap_s"] == pytest.approx(550e-6)
    assert g["foreign_s"] == pytest.approx(20e-6)
    err = capsys.readouterr().err
    assert "4 steps on the device, 3 paired" in err
    assert "pairing is off" not in err
    assert "xor.5 1" in err and "fusion.9 1" in err
    # the largest gap first: the fourth step's, all of it in the call
    assert "largest gaps (step on the device, ms, late, in the call): " \
        "4 0.300 0.000 0.300, 3 0.200 0.150 0.050, 2 0.050" in err


def test_the_late_part_by_span_and_the_in_call_part_by_event():
    calls, events = spanread_steps._host_events(_trace())
    assert calls == [(1300 * US, 1520 * US), (2100 * US, 2200 * US),
                     (2300 * US, 2950 * US)]
    # (gap, late, the gap's start, the step, its call): as ``step_gaps``
    # makes them
    rows = [(50 * US, 0, 1450 * US, 2, calls[0]),
            (200 * US, 150 * US, 1950 * US, 3, calls[1]),
            (300 * US, 0, 2600 * US, 4, calls[2])]
    # the late 150 us, [1950, 2100): the end of the wait 10, the flush 30,
    # the draw 6, the key 90, ``dispatch`` outside its halves 1 + 10,
    # between the spans 2 + 1
    assert spanread_steps.late_by_span(rows, events) == {
        "host-wait": 10 * US, "flush": 30 * US, "data-load": 6 * US,
        "dispatch/prepare": 90 * US, "dispatch": 11 * US,
        "under no span": 3 * US}
    # the innermost of the runtime's events inside the call that covers
    # half of the part or more; not the transfer's span, shorter though
    assert spanread_steps.in_call_by_event(rows, events) == {
        "dispatch/call": 100 * US, "ExecutePrepare": 300 * US}
    # a launch that lands 60 us after its call returned
    late_launch = [(100 * US, 0, 3000 * US, 5, (2900 * US, 3040 * US))]
    assert spanread_steps.in_call_by_event(late_launch, events) == {
        "dispatch/call": 40 * US, "the call had returned": 60 * US}


def test_a_head_step_with_its_call_counts_from_the_second_step():
    obs = _obs()
    obs["trace"].host_spans.append(
        ("dispatch/call", 900 * US, 150 * US, "main"))
    g = spanread_steps.step_gaps(obs)
    assert g["gaps"] == 3 and g["late_ms"] == pytest.approx(0.050)


def test_gap_readers_need_the_calls_the_text_and_a_device_trace():
    assert spanread_steps.step_gaps(_obs(calls=False)) is None
    assert spanread_steps.step_gaps(dict(_obs(), program_text=None)) is None
    no_device = _obs()
    no_device["trace"] = Trace({}, no_device["trace"].host_spans)
    assert spanread_steps.step_gaps(no_device) is None
    # one step on the device has no predecessor: no gap to split
    one = _obs()
    one["trace"].device_ops["/device:TPU:0"] = _step(2900)
    assert spanread_steps.step_gaps(one) is None


@pytest.mark.parametrize("name", LOOP)
def test_loop_readers_are_left_out_of_a_run_without_an_accelerator(name):
    assert read(name, {"spans": SPANS, "steps": 20, "peaks": None}) is None
    assert read(name, {"spans": SPANS, "steps": 20}) is None
