"""The latent-attention cell on the CPU at toy sizes (the on-chip-measurement
guide's first rehearsal): the whole of a run of
``kanana_2_30b_a3b.train_s8k`` but the look for a chip.  A sound run comes
out correct; the control (the reference a precision lower) and each planted
fault come out not correct; the ``mla_train.*`` readers are checked on a
small synthetic trace, ``flops_mla``'s counts against brute-force counts,
and the configuration's file against the published numbers.  No number of
these runs is a device metric."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmark import check, flops_mla, harness, spanread_lm  # noqa: E402
from benchmark.runners import train_lm                         # noqa: E402
from benchmark.trace import ProgramText, Trace                 # noqa: E402

CELL = "kanana_2_30b_a3b.train_s8k"
CONFIG = "benchmark/configs/kanana_2_30b_a3b.json"
METRICS = ["mla_train.step_mfu", "mla_train.attn_core_roofline",
           "mla_train.attn_core_ms", "mla_train.latent_proj_ms",
           "mla_train.moe_route_ms", "mla_train.moe_experts_roofline",
           "mla_train.head_ms", "mla_train.expert_load_max_over_mean",
           "mla_train.feed_wait_ms", "mla_train.device_idle_pct"]
# limits of the toy size, from its own readings on the CPU (sound runs,
# seeds 5, 11, 12: loss 2.0e-5..3.4e-5, first gradient 0.002..0.031, change
# 0.0027..0.0142, the change's error 0.0035..0.0059; the fp8 control over
# seeds 5, 6, 11: loss 3.3e-4..7.7e-4, the change's error 0.059..0.065;
# tokens dropped over a capacity, seeds 5, 6: change 0.102..0.160 (seed 11
# routes so evenly that none is over); half a batch: loss 1.2e-2.., change
# 0.24..)
TOY_LIMITS = {"loss_gap": 2e-4, "first_grad_gap": 0.25, "change3_gap": 0.05,
              "change3_error": 0.02}
TOY = {"hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 24,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 12,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "router_experts": 16, "experts_held": [0, 1, 2], "vocab_size": 50,
       "num_hidden_layers": 3, "seq_len": 32, "records": 16,
       "sequences_per_step": 2, "reference_query_chunk": 8,
       "check": TOY_LIMITS}


def numbers(result):
    return {k: v["value"] for k, v in result["check"].items()}


def toy_config():
    return dict(harness.load_json(CONFIG),
                **{k: v for k, v in TOY.items() if k != "check"})


@pytest.fixture(scope="module")
def sound():
    return harness.run_cell(CELL, 11, 1.0, False, sizes=TOY)


def test_mla_rehearsal_is_correct(sound):
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
    assert [len(row) for row in r["detail"]["assignments_held"]] == [2, 2]


def test_mla_traced_rehearsal_reads_the_host_side_metrics(monkeypatch,
                                                          tmp_path):
    # a trace directory of this test's own: the other files' traced
    # rehearsals, in other workers, empty the harness's before they start
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    r = harness.run_cell(CELL, 12, 1.0, True, sizes=TOY)
    assert r["correct"] is True, r["check"]
    # no device trace on the CPU: the device metrics are left out, the
    # counters' and the spans' are read, and no lm_train.* or train.* name
    assert set(r["metrics"]) == {"mla_train.expert_load_max_over_mean",
                                 "mla_train.feed_wait_ms"}
    assert r["metrics"]["mla_train.expert_load_max_over_mean"]["value"] >= 1


@pytest.mark.parametrize("what", ["control", "half_batch", "capacity"])
def test_mla_control_and_faults_are_not_correct(what):
    """The reference put in the program's place: computed with fp8
    operands, with half of every batch left out, with tokens dropped over a
    capacity."""
    cell = harness.load_json("benchmark", "workloads", CELL + ".json")
    correct, table = check.verdict(train_lm.variant_numbers(
        cell, harness.load_json(CONFIG), 6, what, sizes=TOY))
    assert correct is False, table


def test_mla_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
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
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256}


def test_manifest_has_the_cell_its_metrics_and_the_published_widths():
    m = harness.load_manifest()
    entry, config_entry = harness.find_cell(m, CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    rate = next(e for e in m["end_to_end"]
                if e["name"] == "train_records_per_s")
    assert CELL in rate["workloads"]
    ours = [p for p in m["per_layer"] if CELL in p.get("workloads", ())]
    assert [p["name"] for p in ours] == METRICS
    assert m["per_layer"][-len(METRICS):] == ours      # at the end
    for p in ours:
        assert p["workloads"] == [CELL]
        assert p["moves"] == "train_records_per_s"
        harness.load_reader(p["name"])
        twin = next((q for q in m["per_layer"] if q["name"]
                     == p["name"].replace("mla_train.", "lm_train.")), None)
        if twin is not None:
            assert {k: p[k] for k in ("unit", "better", "source", "layer")} \
                == {k: twin[k] for k in ("unit", "better", "source", "layer")}
    cfg = harness.load_json(config_entry["file"])
    reduced = config_entry["reduced"]
    assert reduced == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert key in cfg and cfg[key] == value, key
    assert cfg["router_experts"] == PUBLISHED["n_routed_experts"]
    assert cfg["experts_held"] == list(range(cfg["n_routed_experts"]))
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # 575,955,456 parameters at 12 bytes, from the reference's own shapes
    from benchmark.program import load_reference
    shapes = load_reference(cfg).param_shapes(cfg)
    count = sum(int(np.prod(s)) for leaf in shapes.values()
                for s in leaf.values())
    assert count == cfg["deployment"]["parameters"] == 575_955_456
    attention = shapes["layer1/attn"]
    assert sum(int(np.prod(s)) for s in attention.values()) == 26_345_984


# -- flops_mla against brute force ---------------------------------------------

def test_flops_match_a_brute_force_count():
    cfg = toy_config()
    t, seqs = 32, 2
    d, heads, r = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["kv_lora_rank"])
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    tokens = seqs * t
    # a routing table: 3 tokens out of 4 pick held expert 0, every other
    # token also picks held expert 2
    table = np.zeros((tokens, cfg["router_experts"]), bool)
    table[np.arange(tokens) % 4 != 0, 0] = True
    table[::2, 2] = True
    held = int(table[:, cfg["experts_held"]].sum())
    pairs = sum(1 for i in range(t) for j in range(t) if j <= i)
    forward = 0.0
    for _ in range(cfg["num_hidden_layers"]):
        forward += tokens * 2 * d * heads * (nope + rope)        # q
        forward += tokens * 2 * d * (r + rope)                   # down
        forward += tokens * 2 * r * heads * (nope + dv)          # up
        forward += tokens * 2 * heads * dv * d                   # o
        forward += seqs * heads * pairs * 2 * (nope + rope)      # q . k
        forward += seqs * heads * pairs * 2 * dv                 # p v
    forward += tokens * 2 * 3 * d * cfg["intermediate_size"]     # 1 dense
    for _ in range(cfg["num_hidden_layers"] - 1):                # 2 sparse
        forward += tokens * 2 * d * cfg["router_experts"]
        forward += tokens * 2 * 3 * d * 2 * cfg["moe_intermediate_size"]
        forward += held * 2 * 3 * d * cfg["moe_intermediate_size"]
    forward += tokens * 2 * d * cfg["vocab_size"]
    got = flops_mla.train_flops_per_step(cfg, seqs, t, [held] * 2)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert flops_mla.expected_assignments(cfg, tokens) == tokens * 6 * 3 / 16
    # at the published sizes the issue's counts: 482 MFLOP a token forward
    # outside the cores, 20.6 TFLOP of cores and 45.7 TFLOP a step
    full = harness.load_json(CONFIG)
    assert flops_mla.dense_forward_per_token(full) == pytest.approx(
        482e6, rel=0.005)
    assert 2 * flops_mla.attention_core_train(full, 8192) == pytest.approx(
        20.6e12, rel=0.005)
    assert flops_mla.train_flops_per_step(full, 2, 8192) == pytest.approx(
        45.7e12, rel=0.005)


# -- the readers on a small synthetic trace ------------------------------------

_LAYER = "jit(train_step)/jvp(Recompute)/Sequential/LatentAttention"
HLO = f"""HloModule jit_train_step

%body (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  %dot.1 = f32[4]{{0}} dot(%p, %p), metadata={{op_name="{_LAYER}/FullAttentionCore/while/body/dot_general"}}
  ROOT %exp.1 = f32[4]{{0}} exponential(%dot.1), metadata={{op_name="{_LAYER}/FullAttentionCore/while/body/exp"}}
}}

%chunks (p: f32[4]) -> f32[4] {{
  %p = f32[4]{{0}} parameter(0)
  %gather.1 = f32[4]{{0}} gather(%p, %p), metadata={{op_name="jit(train_step)/jvp(Recompute)/Sequential/DroplessMoE/MoeRoute/while/body/gather"}}
  ROOT %ragged-dot-none.3 = f32[4]{{0}} custom-call(%gather.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-none"}}
}}

ENTRY %main (a: f32[4]) -> f32[4] {{
  %a = f32[4]{{0}} parameter(0)
  %dot.5 = f32[4]{{0}} dot(%a, %a), metadata={{op_name="{_LAYER}/dot_general"}}
  %dot.6 = f32[4]{{0}} dot(%dot.5, %a), metadata={{op_name="{_LAYER}/LatentKV/dot_general"}}
  %mul.6 = f32[4]{{0}} multiply(%dot.6, %a), metadata={{op_name="jit(train_step)/transpose(jvp(Recompute))/Sequential/LatentAttention/LatentKV/mul"}}
  %while.1 = f32[4]{{0}} while(%mul.6), condition=%cond, body=%body, metadata={{op_name="{_LAYER}/FullAttentionCore/while"}}
  %while.3 = f32[4]{{0}} while(%while.1), condition=%cond, body=%chunks, metadata={{op_name="jit(train_step)/jvp(Recompute)/Sequential/DroplessMoE/MoeRoute/while"}}
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
        ev("dot.5", 0, 2), ev("dot.6", 2, 3), ev("mul.6", 5, 1),
        ev("while.1", 6, 10),                   # the core: 2 body rounds
        ev("dot.1", 6, 3), ev("exp.1", 9, 1), ev("dot.1", 11, 3),
        ev("exp.1", 14, 1),                     # the loop's own time: 2
        ev("while.3", 20, 6), ev("gather.1", 20, 1),
        ev("ragged-dot-none.3", 21, 4),         # the loop's own time: 1
        ev("dot.9", 30, 5), ev("gather.9", 35, 1), ev("add.9", 36, 2),
    ]
    cfg = toy_config()
    cfg["num_hidden_layers"], cfg["first_k_dense_replace"] = 2, 0
    return {"trace": Trace({"/device:TPU:0": events}, []),
            "program_text": ProgramText(HLO), "steps": 2, "batch": 2,
            "seq_len": 32, "config": cfg, "traced_s": 0.05, "wall_s": 0.05,
            "peaks": {"flops_per_s": 1e9},
            "expert_counters": {"assignments_held": [[30.0, 10.0],
                                                     [34.0, 14.0]],
                                "expert_max": [[20.0, 5.0], [20.0, 7.0]]}}


def test_mla_readers_on_the_fixture():
    obs = fixture_obs()
    read = lambda name: harness.load_reader(name)(obs)
    assert spanread_lm.scope_seconds(obs) == pytest.approx({
        "LatentAttention": 2e-3, "LatentKV": 4e-3, "FullAttentionCore": 10e-3,
        "MoeRoute": 2e-3, "MoeExperts": 4e-3, "LmHead": 5e-3,
        "ClassNLLCriterion": 1e-3, "optim-update": 2e-3})
    assert read("mla_train.latent_proj_ms") == pytest.approx(3.0)
    assert read("mla_train.attn_core_ms") == pytest.approx(2.5)   # 2 layers
    assert read("mla_train.moe_route_ms") == pytest.approx(1.0)
    assert read("mla_train.head_ms") == pytest.approx(3.0)
    assert read("mla_train.device_idle_pct") == pytest.approx(100 * 0.40)
    cfg = obs["config"]
    core = 2 * flops_mla.attention_core_train(cfg, 32)
    assert read("mla_train.attn_core_roofline") == pytest.approx(
        100 * core / 1e9 / 5e-3)
    experts = sum(flops_mla.expert_products_train(cfg, a) for a in (32, 12))
    assert read("mla_train.moe_experts_roofline") == pytest.approx(
        100 * experts / 1e9 / 2e-3)
    # 3 experts held: max over mean = max * 3 / held
    assert read("mla_train.expert_load_max_over_mean") == pytest.approx(
        np.mean([60 / 30, 15 / 10, 60 / 34, 21 / 14]))
    step = flops_mla.train_flops_per_step(cfg, 2, 32, [32.0, 12.0])
    assert read("mla_train.step_mfu") == pytest.approx(
        100 * step * 2 / 0.05 / 1e9)


def test_mla_readers_return_nothing_for_a_program_without_the_scopes():
    """The parent's program on this benchmark: no ``LatentAttention``
    scope (an afmoe step has the cores', the routes' and the head's, and
    the four new readers that read them are not asked there), and a
    program with none of them reads nothing at all."""
    obs = fixture_obs()
    obs["program_text"] = ProgramText(
        HLO.replace("LatentAttention", "GatedGroupedQueryAttention")
        .replace("LatentKV/", ""))
    assert harness.load_reader("mla_train.latent_proj_ms")(obs) is None
    obs = fixture_obs()
    obs["program_text"] = ProgramText(HLO.replace(
        "LatentAttention", "x").replace("LatentKV", "x")
        .replace("FullAttentionCore", "x").replace("MoeRoute", "x")
        .replace("LmHead", "Linear").replace("ragged-dot-none", "fusion"))
    obs["trace"] = Trace({"/device:TPU:0": [
        (n.replace("ragged-dot-none", "fusion"), s, d)
        for n, s, d in obs["trace"].device_ops["/device:TPU:0"]]}, [])
    obs["expert_counters"] = {"assignments_held": [], "expert_max": []}
    for name in METRICS:
        if name not in ("mla_train.step_mfu", "mla_train.feed_wait_ms",
                        "mla_train.device_idle_pct"):
            assert harness.load_reader(name)(obs) is None, name
    for name in METRICS:                # and none raises without a trace
        assert harness.load_reader(name)({"config": obs["config"]}) is None
