"""The short-convolution cell on the CPU at toy sizes (the
on-chip-measurement guide's first rehearsal): the whole of a run of
``lfm2_24b_a2b.train_s8k`` but the look for a chip.  A sound run comes out
correct; the control (the reference a precision lower) and each planted
fault come out not correct; the ``sconv_train.*`` readers are checked on a
small synthetic trace, ``flops_lfm2``'s counts against brute-force counts,
and the configuration's file against the published numbers.  No number of
these runs is a device metric."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import (check, fault_readings, flops_lfm2,   # noqa: E402
                       harness, spanread_lm)
from benchmark.runners import train_lm                         # noqa: E402
from benchmark.trace import ProgramText, Trace                 # noqa: E402

CELL = "lfm2_24b_a2b.train_s8k"
CONFIG = "benchmark/configs/lfm2_24b_a2b.json"
METRICS = ["sconv_train.step_mfu", "sconv_train.short_conv_ms",
           "sconv_train.short_conv_core_ms",
           "sconv_train.short_conv_roofline", "sconv_train.attn_core_ms",
           "sconv_train.attn_core_roofline", "sconv_train.moe_route_ms",
           "sconv_train.moe_experts_roofline",
           "sconv_train.expert_load_max_over_mean", "sconv_train.head_ms",
           "sconv_train.feed_wait_ms", "sconv_train.device_idle_pct"]
FAULTS = ["taps_reversed", "conv_forward", "gates_swapped", "x_first",
          "no_qk_norm", "no_rotary", "head_untied", "top_k_less"]
# the toy draws its matrices at 0.1, not 0.02: at 64 wide a 0.02 draw leaves
# the short convolution's output (two projections' product, times the taps,
# through the output projection) at 1e-4 of the residual stream, and no
# fault planted in it moves a number (change3_error 0.003 sound or not).
# Limits of the toy size, from its own readings on the CPU (sound runs, seeds
# 5, 11, 12, 13, 2147483725: loss 1.7e-4..1.9e-3, first gradient
# 0.003..0.028, change 0.003..0.028, the change's error 0.010..0.033; the
# fp8 control over seeds 5, 6, 11: the change's error 0.139..0.192; the
# eight faults: the change's error 0.090 (one expert fewer) .. 1.49 (the
# head untied), the four planted in the convolution 0.84..1.13; tokens
# dropped over a capacity 0.067..0.143; half a batch 0.72..)
TOY_LIMITS = {"loss_gap": 4e-3, "first_grad_gap": 0.08, "change3_gap": 0.07,
              "change3_error": 0.06}
TOY = {"hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "router_experts": 16, "experts_held": [0, 1, 2], "vocab_size": 50,
       "layer_types": ["conv", "full_attention", "conv", "conv"],
       "num_hidden_layers": 4, "seq_len": 32, "records": 16,
       "sequences_per_step": 2, "reference_query_chunk": 8,
       "assumed": {"initializer_std": 0.1}, "check": TOY_LIMITS}


def numbers(result):
    return {k: v["value"] for k, v in result["check"].items()}


def toy_config():
    return dict(harness.load_json(CONFIG),
                **{k: v for k, v in TOY.items() if k != "check"})


@pytest.fixture(scope="module")
def sound():
    return harness.run_cell(CELL, 11, 1.0, False, sizes=TOY)


def test_lfm2_rehearsal_is_correct(sound):
    r = sound
    assert r["correct"] is True, r["check"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_records_per_s", "setup_s"}
    assert r["metrics"]["train_records_per_s"]["value"] > 0
    assert numbers(r)["compiles_in_window"] == 0
    assert list(r)[-1] == "check"
    first, last = r["detail"]["window_losses"]
    assert last < first                 # the model is learning the mix
    # one reading an expert layer, first and last of the window
    assert [len(row) for row in r["detail"]["assignments_held"]] == [3, 3]


def test_lfm2_traced_rehearsal_reads_the_host_side_metrics(monkeypatch,
                                                           tmp_path):
    # a trace directory of this test's own: the other files' traced
    # rehearsals, in other workers, empty the harness's before they start
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    r = harness.run_cell(CELL, 12, 1.0, True, sizes=TOY)
    assert r["correct"] is True, r["check"]
    # no device trace on the CPU: the device metrics are left out, the
    # counters' and the spans' are read, and no other cell's name
    assert set(r["metrics"]) == {"sconv_train.expert_load_max_over_mean",
                                 "sconv_train.feed_wait_ms"}
    assert r["metrics"]["sconv_train.expert_load_max_over_mean"]["value"] >= 1


@pytest.mark.parametrize("what", ["control", "half_batch", "capacity"])
def test_lfm2_control_and_the_runners_faults_are_not_correct(what):
    """The reference put in the program's place: computed with fp8
    operands, with half of every batch left out, with tokens dropped over a
    capacity."""
    cell = harness.load_json("benchmark", "workloads", CELL + ".json")
    correct, table = check.verdict(train_lm.variant_numbers(
        cell, harness.load_json(CONFIG), 5, what, sizes=TOY))
    assert correct is False, table


@pytest.fixture(scope="module")
def faulty():
    cell = harness.load_json("benchmark", "workloads", CELL + ".json")
    return fault_readings.fault_numbers(
        cell, harness.load_json(CONFIG), 5, FAULTS + ["route_eps"],
        sizes=TOY)


@pytest.mark.parametrize("fault", FAULTS)
def test_lfm2_each_planted_fault_is_not_correct(faulty, fault):
    """The reference's own faults (taps reversed, the convolution looking
    forward, the projection split in another order, the head norms or the
    rotary left out, the head untied, an expert fewer), through
    ``benchmark.fault_readings`` as on the chip."""
    correct, table = check.verdict(faulty[fault])
    assert correct is False, table
    if fault in ("taps_reversed", "conv_forward", "gates_swapped",
                 "x_first", "head_untied"):
        assert table["change3_error"]["value"] > 0.5


def test_lfm2_the_routing_constant_is_under_the_checks_reach(faulty):
    """1e-20 for 1e-6 under the chosen scores' sum moves a weight by 5e-7 of
    itself where the scores are near a half: three steps differ by float32
    rounding, and the check cannot tell (``tests/test_lfm2_moe.py`` pins the
    constant where the scores are small)."""
    correct, table = check.verdict(faulty["route_eps"])
    assert correct is True
    assert table["change3_error"]["value"] < 1e-4


def test_lfm2_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    from bigdl_tpu.optim import optim_method
    monkeypatch.setattr(
        optim_method.SGD, "update",
        lambda self, grads, opt_state, params, hyper: (params, opt_state))
    r = harness.run_cell(CELL, 13, 0.5, False, sizes=TOY)
    assert r["correct"] is False
    assert numbers(r)["change3_gap"] == pytest.approx(1.0, abs=1e-3)


# -- the manifest and the configuration's file ---------------------------------

# the published config.json's numbers (the model-configs catalog's row)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_manifest_has_the_cell_its_metrics_and_the_published_widths():
    m = harness.load_manifest()
    entry, config_entry = harness.find_cell(m, CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "train_records_per_s")
    assert CELL in rate["workloads"]
    ours = [p for p in m["per_layer"] if CELL in p.get("workloads", ())]
    assert [p["name"] for p in ours] == METRICS
    for p in ours:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "train_records_per_s"
        harness.load_reader(p["name"])
        for family in ("lm_train.", "mla_train."):
            twin = next((q for q in m["per_layer"] if q["name"]
                         == p["name"].replace("sconv_train.", family)), None)
            if twin is not None:
                keys = ("unit", "better", "source", "layer")
                assert {k: p[k] for k in keys} == {k: twin[k] for k in keys}
    cfg = harness.load_json(config_entry["file"])
    reduced = config_entry["reduced"]
    assert reduced == ["num_hidden_layers", "num_dense_layers",
                       "layer_types", "num_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert key in cfg and cfg[key] == value, key
    # the cut: one leading dense conv layer, then two whole periods
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:10]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 9
    assert cfg["num_dense_layers"] == 1
    assert cfg["router_experts"] == PUBLISHED["num_experts"]
    assert cfg["experts_held"] == list(range(cfg["num_experts"])) \
        == list(range(8))
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert {"tied_embedding", "head_dim", "from_modeling_lfm2_moe"} \
        <= set(cfg["assumed"])
    # 832,651,520 parameters at 12 bytes, from the reference's own shapes
    # and from the built model's (at the published widths; on the host as
    # shapes only)
    from benchmark.program import load_reference
    shapes = load_reference(cfg).param_shapes(cfg)
    count = lambda leaf: sum(int(np.prod(s)) for s in leaf.values())
    assert sum(count(leaf) for leaf in shapes.values()) \
        == cfg["deployment"]["parameters"] == 832_651_520
    assert count(shapes["layer0/conv"]) == 16_783_360
    assert count(shapes["layer1/attn"]) == 10_485_888
    assert count(shapes["layer0/ffn"]) == 72_351_744
    assert count(shapes["layer1/moe"]) == 75_497_472 + 131_072
    assert "head" not in shapes


def test_the_built_model_holds_the_configurations_count():
    """The program's builder at the published widths, its parameters as
    shapes (``jax.eval_shape``: nothing is drawn): the same leaves in the
    same order as the reference's, 832,651,520 in all, the table once."""
    import jax

    from benchmark.program import build_model, leaf_dicts, load_reference
    cfg = harness.load_json(CONFIG)
    tree = jax.eval_shape(lambda: build_model(cfg).params())
    leaves = [{k: tuple(v.shape) for k, v in leaf.items()}
              for leaf in leaf_dicts(tree)]
    want = [dict(leaf) for leaf in
            load_reference(cfg).param_shapes(cfg).values()]
    assert leaves == want
    assert sum(int(np.prod(s)) for leaf in leaves for s in leaf.values()) \
        == cfg["deployment"]["parameters"]


# -- flops_lfm2 against brute force ---------------------------------------------

def test_flops_match_a_brute_force_count():
    cfg = toy_config()
    t, seqs = 32, 2
    d, heads, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
    hd, taps = d // heads, cfg["conv_L_cache"]
    tokens = seqs * t
    # a routing table: 3 tokens out of 4 pick held expert 0, every other
    # token also picks held expert 2
    table = np.zeros((tokens, cfg["router_experts"]), bool)
    table[np.arange(tokens) % 4 != 0, 0] = True
    table[::2, 2] = True
    held = int(table[:, cfg["experts_held"]].sum())
    pairs = sum(1 for i in range(t) for j in range(t) if j <= i)
    forward = conv = 0.0
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            conv += tokens * 2 * d * 3 * d                     # W_in
            conv += tokens * d * 2                             # B * x~, C * c
            conv += tokens * d * taps * 2                      # the taps
            conv += tokens * 2 * d * d                         # W_out
        else:
            forward += tokens * 2 * d * heads * hd * 2         # q, o
            forward += tokens * 2 * d * kv * hd * 2            # k, v
            forward += seqs * heads * pairs * 2 * hd * 2       # q . k, p v
        if i < cfg["num_dense_layers"]:
            forward += tokens * 2 * 3 * d * cfg["intermediate_size"]
        else:
            forward += tokens * 2 * d * cfg["router_experts"]
            forward += held * 2 * 3 * d * cfg["moe_intermediate_size"]
    forward += conv + tokens * 2 * d * cfg["vocab_size"]       # tied head
    got = flops_lfm2.train_flops_per_step(cfg, seqs, t, [held] * 3)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert flops_lfm2.short_conv_train(cfg, tokens) == pytest.approx(
        3 * conv, rel=1e-12)
    assert flops_lfm2.expected_assignments(cfg, tokens) == tokens * 4 * 3 / 16
    # at the published sizes the issue's counts: a conv operator 33.6 MFLOP
    # a token, the dense SwiGLU 145, an attention layer's projections 21
    # and its core 33.5 a token at 8k, the head 33.6, an expert 9.4 an
    # assignment (x 3 products x 2); ~29 TFLOP a step
    full = harness.load_json(CONFIG)
    assert flops_lfm2.short_conv_forward_per_token(full) == pytest.approx(
        33.6e6, rel=0.005)
    assert flops_lfm2.attention_projections_forward_per_token(full) \
        == pytest.approx(21.0e6, rel=0.005)
    assert flops_lfm2.attention_core_forward(full, 8192) / 8192 \
        == pytest.approx(33.5e6, rel=0.005)
    assert flops_lfm2.expected_assignments(full, 16384) == 8192
    step = flops_lfm2.train_flops_per_step(full, 2, 8192)
    assert step == pytest.approx(29.4e12, rel=0.02)
    assert flops_lfm2.short_conv_train(full, 16384) / step \
        == pytest.approx(0.39, abs=0.02)


# -- the readers on a small synthetic trace ------------------------------------

_LAYER = "jit(train_step)/jvp(Sequential)/Recompute/Sequential"
_CONV = f"{_LAYER}/ShortConv"
_ATTN = f"{_LAYER}/GroupedQueryAttention"
HLO = f"""HloModule jit_train_step

%body (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  %dot.1 = f32[4]{{0}} dot(%p, %p), metadata={{op_name="{_ATTN}/FullAttentionCore/while/body/dot_general"}}
  ROOT %exp.1 = f32[4]{{0}} exponential(%dot.1), metadata={{op_name="{_ATTN}/FullAttentionCore/while/body/exp"}}
}}

%chunks (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  %gather.1 = f32[4]{{0}} gather(%p, %p), metadata={{op_name="{_LAYER}/DroplessMoE/MoeRoute/while/body/gather"}}
  ROOT %ragged-dot-none.3 = f32[4]{{0}} custom-call(%gather.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
}}

ENTRY %main (a: f32[4]) -> f32[4] {{
  %a = f32[4]{{0}} parameter(0)
  %dot.5 = f32[4]{{0}} dot(%a, %a), metadata={{op_name="{_CONV}/dot_general"}}
  %mul.5 = f32[4]{{0}} multiply(%dot.5, %a), metadata={{op_name="{_CONV}/ShortConvCore/mul"}}
  %pad.5 = f32[4]{{0}} pad(%mul.5, %a), metadata={{op_name="jit(train_step)/transpose(jvp(Sequential))/Recompute/Sequential/ShortConv/ShortConvCore/pad"}}
  %dot.6 = f32[4]{{0}} dot(%pad.5, %a), metadata={{op_name="{_CONV}/dot_general"}}
  %dot.7 = f32[4]{{0}} dot(%dot.6, %a), metadata={{op_name="{_ATTN}/dot_general"}}
  %while.1 = f32[4]{{0}} while(%dot.7), condition=%cond, body=%body, metadata={{op_name="{_ATTN}/FullAttentionCore/while"}}
  %while.3 = f32[4]{{0}} while(%while.1), condition=%cond, body=%chunks, metadata={{op_name="{_LAYER}/DroplessMoE/MoeRoute/while"}}
  %dot.9 = f32[4]{{0}} dot(%while.3, %a), metadata={{op_name="jit(train_step)/jvp(LmHead)/dot_general"}}
  %gather.9 = f32[4]{{0}} gather(%dot.9, %a), metadata={{op_name="jit(train_step)/jvp(TimeDistributedCriterion)/vmap(ClassNLLCriterion)/gather"}}
  ROOT %add.9 = f32[4]{{0}} add(%gather.9, %a), metadata={{op_name="jit(train_step)/optim-update/add"}}
}}
"""
MS = 1_000_000       # nanoseconds


def fixture_obs():
    ev = lambda name, start_ms, dur_ms: (
        f"%{name} = f32[4]{{0}} x()", int(start_ms * MS), int(dur_ms * MS))
    events = [
        ev("dot.5", 0, 2), ev("mul.5", 2, 1), ev("pad.5", 3, 2),
        ev("dot.6", 5, 1), ev("dot.7", 6, 1),
        ev("while.1", 7, 10),                   # the core: 2 body rounds
        ev("dot.1", 7, 3), ev("exp.1", 10, 1), ev("dot.1", 12, 3),
        ev("exp.1", 15, 1),                     # the loop's own time: 2
        ev("while.3", 20, 6), ev("gather.1", 20, 1),
        ev("ragged-dot-none.3", 21, 4),         # the loop's own time: 1
        ev("dot.9", 30, 5), ev("gather.9", 35, 1), ev("add.9", 36, 2),
    ]
    cfg = toy_config()
    cfg["layer_types"] = ["conv", "full_attention", "full_attention"]
    cfg["num_dense_layers"] = 1
    return {"trace": Trace({"/device:TPU:0": events}, []),
            "program_text": ProgramText(HLO), "steps": 2, "batch": 2,
            "seq_len": 32, "config": cfg, "traced_s": 0.05, "wall_s": 0.05,
            "peaks": {"flops_per_s": 1e9},
            "expert_counters": {"assignments_held": [[30.0, 10.0],
                                                     [34.0, 14.0]],
                                "expert_max": [[20.0, 5.0], [20.0, 7.0]]}}


def test_sconv_readers_on_the_fixture():
    obs = fixture_obs()
    read = lambda name: harness.load_reader(name)(obs)
    assert spanread_lm.scope_seconds(obs) == pytest.approx({
        "ShortConv": 3e-3, "ShortConvCore": 3e-3,
        "GroupedQueryAttention": 1e-3, "FullAttentionCore": 10e-3,
        "MoeRoute": 2e-3, "MoeExperts": 4e-3, "LmHead": 5e-3,
        "ClassNLLCriterion": 1e-3, "optim-update": 2e-3})
    assert read("sconv_train.short_conv_ms") == pytest.approx(3.0)
    assert read("sconv_train.short_conv_core_ms") == pytest.approx(1.5)
    assert read("sconv_train.attn_core_ms") == pytest.approx(2.5)  # 2 layers
    assert read("sconv_train.moe_route_ms") == pytest.approx(1.0)
    assert read("sconv_train.head_ms") == pytest.approx(3.0)
    assert read("sconv_train.device_idle_pct") == pytest.approx(100 * 0.38)
    cfg = obs["config"]
    conv = flops_lfm2.short_conv_train(cfg, 2 * 32)
    assert read("sconv_train.short_conv_roofline") == pytest.approx(
        100 * conv / 1e9 / 3e-3)
    core = 2 * flops_lfm2.attention_core_train(cfg, 32)
    assert read("sconv_train.attn_core_roofline") == pytest.approx(
        100 * core / 1e9 / 5e-3)
    experts = sum(flops_lfm2.expert_products_train(cfg, a) for a in (32, 12))
    assert read("sconv_train.moe_experts_roofline") == pytest.approx(
        100 * experts / 1e9 / 2e-3)
    # 3 experts held: max over mean = max * 3 / held
    assert read("sconv_train.expert_load_max_over_mean") == pytest.approx(
        np.mean([60 / 30, 15 / 10, 60 / 34, 21 / 14]))
    step = flops_lfm2.train_flops_per_step(cfg, 2, 32, [32.0, 12.0])
    assert read("sconv_train.step_mfu") == pytest.approx(
        100 * step * 2 / 0.05 / 1e9)


def test_sconv_readers_return_nothing_for_a_program_without_the_scopes():
    """The parent's program on this benchmark has no ``ShortConv`` scope
    (an afmoe or deepseek_v3 step has the cores', the routes' and the
    head's): the three readers of the new scopes read nothing there; and a
    program with none of this model's scopes reads nothing at all."""
    obs = fixture_obs()
    obs["program_text"] = ProgramText(
        HLO.replace("ShortConvCore/", "").replace("ShortConv", "Linear"))
    for name in ("sconv_train.short_conv_ms",
                 "sconv_train.short_conv_core_ms",
                 "sconv_train.short_conv_roofline"):
        assert harness.load_reader(name)(obs) is None, name
    obs = fixture_obs()
    obs["program_text"] = ProgramText(
        HLO.replace("ShortConvCore", "x").replace("ShortConv", "x")
        .replace("FullAttentionCore", "x").replace("MoeRoute", "x")
        .replace("LmHead", "Linear").replace("ragged-dot-none", "fusion"))
    obs["trace"] = Trace({"/device:TPU:0": [
        (n.replace("ragged-dot-none", "fusion"), s, d)
        for n, s, d in obs["trace"].device_ops["/device:TPU:0"]]}, [])
    obs["expert_counters"] = {"assignments_held": [], "expert_max": []}
    for name in METRICS:
        if name not in ("sconv_train.step_mfu", "sconv_train.feed_wait_ms",
                        "sconv_train.device_idle_pct"):
            assert harness.load_reader(name)(obs) is None, name
    for name in METRICS:                # and none raises without a trace
        assert harness.load_reader(name)({"config": obs["config"]}) is None
