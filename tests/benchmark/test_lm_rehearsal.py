"""The language-model cell on the CPU at toy sizes (the on-chip-measurement
guide's first rehearsal): the whole of a run of ``trinity_mini.train_s8k``
but the look for a chip.  A sound run comes out correct; the control (the
reference a precision lower) and each planted fault come out not correct;
the ``lm_train.*`` readers are checked on a small synthetic trace, and
``flops_lm``'s counts against brute-force counts of the mask and of a
routing table.  No number of these runs is a device metric."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import check, flops_lm, harness, spanread_lm  # noqa: E402
from benchmark.runners import train_lm                        # noqa: E402
from benchmark.trace import ProgramText, Trace                # noqa: E402

CELL = "trinity_mini.train_s8k"
# limits of the toy size, from its own readings on the CPU (sound run, seed
# 11: loss 1.8e-4, first gradient 0.041, change 0.012, the change's error
# 0.022; the fp8 control over seeds 5, 6, 11: loss 6.3e-4..1.8e-3, the
# change's error 0.128..0.226; tokens dropped over a capacity: 0.187..0.210)
TOY_LIMITS = {"loss_gap": 5e-4, "first_grad_gap": 0.25, "change3_gap": 0.2,
              "change3_error": 0.08}
TOY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
       "router_experts": 16, "num_experts_per_tok": 4,
       "experts_held": [0, 1], "sliding_window": 8, "vocab_size": 50,
       "seq_len": 32, "records": 16, "sequences_per_step": 2,
       "reference_query_chunk": 8, "check": TOY_LIMITS}


def numbers(result):
    return {k: v["value"] for k, v in result["check"].items()}


@pytest.fixture(scope="module")
def sound():
    return harness.run_cell(CELL, 11, 1.0, False, sizes=TOY)


def test_lm_rehearsal_is_correct(sound):
    r = sound
    assert r["correct"] is True, r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_records_per_s", "setup_s"}
    assert r["metrics"]["train_records_per_s"]["value"] > 0
    assert numbers(r)["compiles_in_window"] == 0
    assert list(r)[-1] == "check"
    # the loss falls over the window: the model is learning the mix
    first, last = r["detail"]["window_losses"]
    assert last < first


def test_lm_traced_rehearsal_reads_the_host_side_metrics():
    r = harness.run_cell(CELL, 12, 1.0, True, sizes=TOY)
    assert r["correct"] is True, r["check"]
    # no device trace on the CPU: the device metrics are left out, the
    # counters' and the spans' are read
    assert "lm_train.expert_load_max_over_mean" in r["metrics"]
    assert r["metrics"]["lm_train.expert_load_max_over_mean"]["value"] >= 1
    assert "lm_train.feed_wait_ms" in r["metrics"]
    assert "lm_train.attn_core_roofline" not in r["metrics"]
    assert "lm_train.step_mfu" not in r["metrics"]      # no peak for a CPU


@pytest.mark.parametrize("what", ["control", "half_batch", "full_window",
                                  "capacity"])
def test_lm_control_and_faults_are_not_correct(what):
    """The reference put in the program's place: computed with fp8
    operands, with half of every batch left out, with the window layers
    seeing every earlier key, with tokens dropped over a capacity."""
    cell = harness.load_json("benchmark", "workloads", CELL + ".json")
    config = harness.load_json("benchmark/configs/trinity_mini.json")
    correct, table = check.verdict(
        train_lm.variant_numbers(cell, config, 6, what, sizes=TOY))
    assert correct is False, table


def test_lm_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    from bigdl_tpu.optim import optim_method
    monkeypatch.setattr(
        optim_method.SGD, "update",
        lambda self, grads, opt_state, params, hyper: (params, opt_state))
    r = harness.run_cell(CELL, 13, 0.5, False, sizes=TOY)
    assert r["correct"] is False
    assert numbers(r)["change3_gap"] == pytest.approx(1.0, abs=1e-3)


def test_token_records_are_seeded_and_zipf():
    mix = {"records": 64, "seq_len": 256, "zipf_exponent": 1.0}
    a = train_lm.token_records(mix, 2 ** 31 + 12345, 1000)
    b = train_lm.token_records(mix, 2 ** 31 + 12345, 1000)
    c = train_lm.token_records(mix, 7, 1000)
    assert a.shape == (64, 257) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() >= 1 and a.max() <= 1000
    counts = np.sort(np.bincount(a.astype(int).ravel(), minlength=1001))[::-1]
    # rank 1 holds about 1 / H(1000) = 13% of the tokens, rank 2 half that
    assert 0.10 < counts[0] / a.size < 0.17
    assert 0.3 < counts[1] / counts[0] < 0.8


# -- flops_lm against brute force ---------------------------------------------

@pytest.mark.parametrize("t,window", [(1, None), (9, None), (9, 4), (9, 9),
                                      (9, 20), (64, 8), (8192, 2048)])
def test_visible_pairs_match_a_count_of_the_mask(t, window):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    assert flops_lm.visible_pairs(t, window) == int(seen.sum())


def test_flops_match_a_brute_force_count():
    cfg = dict(harness.load_json("benchmark/configs/trinity_mini.json"),
               **{k: v for k, v in TOY.items() if k != "check"})
    t, seqs = 32, 2
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    # a routing table: 3 tokens out of 4 pick held expert 0, every other
    # token also picks held expert 1
    tokens = seqs * t
    table = np.zeros((tokens, cfg["router_experts"]), bool)
    table[np.arange(tokens) % 4 != 0, 0] = True
    table[::2, 1] = True
    held = int(table[:, cfg["experts_held"]].sum())
    forward = 0.0
    for kind in cfg["layer_types"]:
        forward += tokens * 2 * d * (2 * hq * hd + 2 * hk * hd)  # q g k v
        forward += tokens * 2 * hq * hd * d                      # o
        w = cfg["sliding_window"] if kind == "sliding_attention" else None
        pairs = sum(1 for i in range(t) for j in range(t)
                    if j <= i and (w is None or i - j < w))
        forward += seqs * 2 * 2 * hq * hd * pairs
    forward += tokens * 2 * 3 * d * cfg["intermediate_size"]     # 1 dense
    for _ in range(4):                                           # 4 sparse
        forward += tokens * 2 * d * cfg["router_experts"]
        forward += tokens * 2 * 3 * d * cfg["moe_intermediate_size"]
        forward += held * 2 * 3 * d * cfg["moe_intermediate_size"]
    forward += tokens * 2 * d * cfg["vocab_size"]
    got = flops_lm.train_flops_per_step(cfg, seqs, t, [held] * 4)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert flops_lm.expected_assignments(cfg, tokens) == tokens * 4 * 2 / 16
    # at the published sizes the issue's count: 738 MFLOP a token forward
    full = harness.load_json("benchmark/configs/trinity_mini.json")
    per_token = flops_lm.train_flops_per_step(full, 2, 8192) / 3 / 16384
    assert per_token == pytest.approx(738e6, rel=0.01)


# -- the readers on a small synthetic trace ------------------------------------

HLO = """HloModule jit_train_step

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %dot.1 = f32[4]{0} dot(%p, %p), metadata={op_name="jit(train_step)/jvp(Recompute)/Sequential/GatedGroupedQueryAttention/WindowAttentionCore/while/body/dot_general"}
  ROOT %exp.1 = f32[4]{0} exponential(%dot.1), metadata={op_name="jit(train_step)/jvp(Recompute)/Sequential/GatedGroupedQueryAttention/WindowAttentionCore/while/body/exp"}
}

%body_full (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %dot.2 = f32[4]{0} dot(%p, %p), metadata={op_name="jit(train_step)/transpose(jvp(Recompute))/Sequential/GatedGroupedQueryAttention/FullAttentionCore/while/body/dot_general"}
}

%chunks (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %gather.1 = f32[4]{0} gather(%p, %p), metadata={op_name="jit(train_step)/jvp(Recompute)/Sequential/DroplessMoE/MoeRoute/while/body/gather"}
  ROOT %ragged-dot-none.3 = f32[4]{0} custom-call(%gather.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.1 = f32[4]{0} while(%a), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp(Recompute)/Sequential/GatedGroupedQueryAttention/WindowAttentionCore/while"}
  %while.2 = f32[4]{0} while(%while.1), condition=%cond, body=%body_full, metadata={op_name="jit(train_step)/transpose(jvp(Recompute))/Sequential/GatedGroupedQueryAttention/FullAttentionCore/while"}
  %while.3 = f32[4]{0} while(%while.2), condition=%cond, body=%chunks, metadata={op_name="jit(train_step)/jvp(Recompute)/Sequential/DroplessMoE/MoeRoute/while"}
  %dot.9 = f32[4]{0} dot(%while.3, %a), metadata={op_name="jit(train_step)/jvp(LmHead)/dot_general"}
  %gather.9 = f32[4]{0} gather(%dot.9, %a), metadata={op_name="jit(train_step)/jvp(TimeDistributedCriterion)/vmap(ClassNLLCriterion)/gather"}
  ROOT %add.9 = f32[4]{0} add(%gather.9, %a), metadata={op_name="jit(train_step)/optim-update/add"}
}
"""
MS = 1_000_000       # nanoseconds


def fixture_obs():
    ev = lambda name, start_ms, dur_ms: (
        f"%{name} = f32[4]{{0}} x()", int(start_ms * MS), int(dur_ms * MS))
    events = [
        ev("while.1", 0, 10),                   # window core: 2 body rounds
        ev("dot.1", 0, 3), ev("exp.1", 3, 1), ev("dot.1", 5, 3),
        ev("exp.1", 8, 1),                      # the loop's own time: 2
        ev("while.2", 10, 8), ev("dot.2", 10, 8),
        ev("while.3", 20, 6), ev("gather.1", 20, 1),
        ev("ragged-dot-none.3", 21, 4),         # the loop's own time: 1
        ev("dot.9", 30, 5), ev("gather.9", 35, 1), ev("add.9", 36, 2),
    ]
    cfg = dict(harness.load_json("benchmark/configs/trinity_mini.json"),
               **{k: v for k, v in TOY.items() if k != "check"})
    cfg["layer_types"] = ["sliding_attention", "full_attention"]
    cfg["num_hidden_layers"], cfg["num_dense_layers"] = 2, 0
    return {"trace": Trace({"/device:TPU:0": events}, []),
            "program_text": ProgramText(HLO), "steps": 2, "batch": 2,
            "seq_len": 32, "config": cfg, "traced_s": 0.05, "wall_s": 0.05,
            "peaks": {"flops_per_s": 1e9},
            "expert_counters": {"assignments_held": [[30.0, 10.0],
                                                     [34.0, 14.0]],
                                "expert_max": [[20.0, 5.0], [20.0, 7.0]]}}


def test_own_time_takes_the_nested_operations_out():
    own = spanread_lm.self_seconds(fixture_obs()["trace"].device_ops[
        "/device:TPU:0"])
    assert own["while.1"] == pytest.approx(2e-3)
    assert own["dot.1"] == pytest.approx(6e-3)
    assert own["while.3"] == pytest.approx(1e-3)
    assert own["ragged-dot-none.3"] == pytest.approx(4e-3)
    assert sum(own.values()) == pytest.approx(32e-3)    # the busy time


def test_lm_readers_on_the_fixture():
    obs = fixture_obs()
    read = lambda name: harness.load_reader(name)(obs)
    assert spanread_lm.scope_seconds(obs) == pytest.approx({
        "WindowAttentionCore": 10e-3, "FullAttentionCore": 8e-3,
        "MoeRoute": 2e-3, "MoeExperts": 4e-3, "LmHead": 5e-3,
        "ClassNLLCriterion": 1e-3, "optim-update": 2e-3})
    assert read("lm_train.attn_window_ms") == pytest.approx(5.0)
    assert read("lm_train.attn_full_ms") == pytest.approx(4.0)
    assert read("lm_train.moe_route_ms") == pytest.approx(1.0)
    assert read("lm_train.head_ms") == pytest.approx(3.0)
    assert read("lm_train.device_idle_pct") == pytest.approx(100 * 0.36)
    cfg = obs["config"]
    core = 2 * flops_lm.attention_core_train(cfg, 32)
    assert read("lm_train.attn_core_roofline") == pytest.approx(
        100 * core / 1e9 / 9e-3)
    experts = sum(flops_lm.expert_products_train(cfg, a) for a in (32, 12))
    assert read("lm_train.moe_experts_roofline") == pytest.approx(
        100 * experts / 1e9 / 2e-3)
    # 2 experts held: max over mean = max * 2 / held
    assert read("lm_train.expert_load_max_over_mean") == pytest.approx(
        np.mean([40 / 30, 10 / 10, 40 / 34, 14 / 14]))
    step = flops_lm.train_flops_per_step(cfg, 2, 32, [32.0, 12.0])
    assert read("lm_train.step_mfu") == pytest.approx(
        100 * step * 2 / 0.05 / 1e9)


def test_lm_readers_return_nothing_for_a_program_without_the_scopes():
    obs = fixture_obs()
    obs["program_text"] = ProgramText(HLO.replace(
        "WindowAttentionCore", "x").replace("FullAttentionCore", "x")
        .replace("MoeRoute", "x").replace("LmHead", "Linear")
        .replace("ragged-dot-none", "fusion"))
    obs["trace"] = Trace({"/device:TPU:0": [
        (n.replace("ragged-dot-none", "fusion"), s, d)
        for n, s, d in obs["trace"].device_ops["/device:TPU:0"]]}, [])
    obs["expert_counters"] = {"assignments_held": [], "expert_max": []}
    for name in ("lm_train.attn_window_ms", "lm_train.attn_full_ms",
                 "lm_train.moe_route_ms", "lm_train.head_ms",
                 "lm_train.attn_core_roofline",
                 "lm_train.moe_experts_roofline",
                 "lm_train.expert_load_max_over_mean"):
        assert harness.load_reader(name)(obs) is None, name
