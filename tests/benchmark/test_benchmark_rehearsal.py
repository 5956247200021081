"""Both runners on the CPU at toy sizes (the on-chip-measurement guide's
first rehearsal): the whole of a run but the look for a chip.  A sound run
comes out correct; the control (the reference computed a precision lower)
and each fault planted under the timed path come out not correct.  No
number of these runs is a device metric: the command itself fails without
a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

TRAIN = "inception_v1.train_b256"
# limits of the toy size, from its own readings on the CPU (seeds 5, 6, 11,
# 14, 15: loss 8e-6..3.6e-5, first gradient 0.017..0.034, change 0.020..0.048,
# the change's error 0.041..0.055 against the fp8 control's 0.191..0.226)
TOY_TRAIN_LIMITS = {"loss_gap": 1e-4, "first_grad_gap": 0.15,
                    "change3_gap": 0.15, "change3_error": 0.11}
TOY_TRAIN = {"batch": 8, "records": 32, "classes": 10, "reference_block": 4,
             "check": TOY_TRAIN_LIMITS}
# The serving runner has no cell in BENCHMARK.json yet (PERF.md, Open
# question 0).  The rehearsal brings one as a later PR would, by entries and
# a cell of its own and no edit of the harness: the configuration's file at
# toy widths, a closed-loop mix, the four decode readers.
DECODE = "toy_lm.closed_loop"
TOY_LM = {"vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
          "ffn_hidden": 64}
DECODE_LIMIT = 0.05
DECODE_CELL = {
    "runner": "decode",
    "traffic": {"kind": "closed_loop", "clients": 4, "n_pos": 32, "pool": 8,
                "prompt_len": [4, 16], "output_len": [4, 8]},
    "trace_seconds": 1,
    "check": {"sample": 16, "served_logit_gap": DECODE_LIMIT}}
DECODE_END_TO_END = {"decode_tokens_per_s": "tokens/s", "ttft_p95_ms": "ms",
                     "tpot_p95_ms": "ms"}
DECODE_PER_LAYER = {"decode.slot_occupancy_pct": "scheduler",
                    "decode.step_mfu": "model step",
                    "decode.step_hbm_roofline": "kernels",
                    "decode.device_idle_pct": "device"}


def decode_manifest():
    m = harness.load_manifest()
    m["configs"].append({
        "name": "toy_lm", "source": "benchmark/configs/transformer_big_lm.json"
        " at toy widths", "file": "benchmark/configs/transformer_big_lm.json",
        "reduced": [], "why": "rehearsal"})
    m["workloads"].append({"name": DECODE, "config": "toy_lm",
                           "traffic": "closed_loop", "chips": 1,
                           "why": "rehearsal"})
    m["end_to_end"] += [
        {"name": k, "unit": unit, "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": [DECODE]}
        for k, unit in DECODE_END_TO_END.items()]
    m["per_layer"] += [
        {"name": k, "unit": "%", "better": "higher", "source": "device_trace",
         "layer": layer, "moves": "decode_tokens_per_s",
         "workloads": [DECODE]} for k, layer in DECODE_PER_LAYER.items()]
    return m


def run_decode(seed, traced):
    return harness.run_cell(DECODE, seed, 1.0, traced, sizes=TOY_LM,
                            manifest=decode_manifest(), cell=DECODE_CELL)


def numbers(result):
    return {k: v["value"] for k, v in result["check"].items()}


# -- training ---------------------------------------------------------------

@pytest.fixture(scope="module")
def sound_train():
    return harness.run_cell(TRAIN, 11, 1.0, False, sizes=TOY_TRAIN)


def test_train_rehearsal_is_correct(sound_train):
    r = sound_train
    assert r["correct"] is True, r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_records_per_s", "setup_s"}
    assert r["metrics"]["train_records_per_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu"
    assert r["device"]["memory_peak_bytes"] >= 0
    assert len(r["detail"]["records_per_s_by_slice"]) == 0   # a 1 s window
    assert numbers(r)["compiles_in_window"] == 0
    assert list(r)[-1] == "check"          # the numbers compared come last
    # a bf16-compute step follows the float32 reference closely
    assert numbers(r)["loss_gap"] < 1e-3


def test_train_step_that_leaves_its_state_unchanged_is_not_correct(
        monkeypatch):
    from bigdl_tpu.optim import optim_method
    monkeypatch.setattr(
        optim_method.SGD, "update",
        lambda self, grads, opt_state, params, hyper: (params, opt_state))
    r = harness.run_cell(TRAIN, 12, 0.5, False, sizes=TOY_TRAIN)
    assert r["correct"] is False
    # a leaf that has not moved reads 1 by the worst-leaf measure
    assert numbers(r)["change3_gap"] == pytest.approx(1.0, abs=1e-3)


def test_train_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from bigdl_tpu.nn import criterion
    whole = criterion.ClassNLLCriterion.apply_loss

    def half(self, input, target):
        n = input.shape[0] // 2
        return whole(self, input[:n], target[:n])

    monkeypatch.setattr(criterion.ClassNLLCriterion, "apply_loss", half)
    r = harness.run_cell(TRAIN, 13, 0.5, False, sizes=TOY_TRAIN)
    assert r["correct"] is False
    assert numbers(r)["first_grad_gap"] > TOY_TRAIN_LIMITS["first_grad_gap"]


@pytest.mark.parametrize("what", ["control", "half_batch"])
def test_train_reference_variants_read_above_a_limit(what):
    from benchmark.runners import train
    cell = harness.load_json("benchmark", "workloads", TRAIN + ".json")
    config = harness.load_json("benchmark", "configs", "inception_v1.json")
    from benchmark import check
    correct, table = check.verdict(
        train.variant_numbers(cell, config, 14, what, sizes=TOY_TRAIN))
    assert correct is False, table
    # a lower precision is the change's error's to catch: the gaps of norms
    # are second order in unbiased rounding
    assert table["change3_error"]["value"] > TOY_TRAIN_LIMITS["change3_error"]


# -- decode -----------------------------------------------------------------

def test_decode_rehearsal_is_correct_and_traced_run_has_no_device_metric():
    r = run_decode(21, True)
    assert r["correct"] is True, r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert numbers(r)["stream_mismatches"] == 0
    assert numbers(r)["prefix_hits_in_window"] == 0
    assert numbers(r)["served_logit_gap"] <= DECODE_LIMIT
    # no TPU plane in the trace: nothing that is a share of the device
    assert "busy_s" not in r["device"]
    assert not any("mfu" in k or "roofline" in k or "idle" in k
                   for k in r["metrics"])
    assert "decode.slot_occupancy_pct" in r["metrics"]


def test_decode_untraced_run_reports_the_end_to_end_metrics():
    r = run_decode(22, False)
    assert r["correct"] is True, r["check"]
    assert set(r["metrics"]) == set(DECODE_END_TO_END) | {"setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    # the control, read on the same prompts and tokens, lies further off
    assert r["detail"]["control_served_logit_gap"] > \
        numbers(r)["served_logit_gap"]


def test_decode_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    import jax.numpy as jnp

    from bigdl_tpu.models import transformer
    sound = transformer._lm_forward_one

    def altered(*args, **kwargs):
        logp, caches = sound(*args, **kwargs)
        return jnp.roll(logp, 1, axis=-1), caches

    monkeypatch.setattr(transformer, "_lm_forward_one", altered)
    r = run_decode(23, False)
    assert r["correct"] is False
    assert numbers(r)["served_logit_gap"] > DECODE_LIMIT


def test_decode_prompt_sent_twice_is_not_correct(monkeypatch):
    """A mix that repeats a prompt is served from the prefix cache: the run
    says so instead of reporting the cache's work as the step's."""
    import itertools

    from benchmark import traffic
    fresh = traffic.decode_requests

    def repeating(mix, seed, vocab):
        return itertools.cycle(list(itertools.islice(
            fresh(dict(mix, prompt_len=[24, 24]), seed, vocab), 2)))

    monkeypatch.setattr(traffic, "decode_requests", repeating)
    r = run_decode(24, False)
    assert numbers(r)["prefix_hits_in_window"] > 0
    assert r["correct"] is False


# -- the command ------------------------------------------------------------

def test_without_a_tpu_the_command_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not on a TPU" in out.stderr


def test_alone_with_its_paths_the_command_fails(tmp_path):
    manifest = harness.load_manifest()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        manifest["command"] + ["--workload", TRAIN, "--seed", "1",
                               "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**env, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert not out.stdout.strip().startswith("{")
    assert json.dumps({"correct": True})[1:-1] not in out.stdout
