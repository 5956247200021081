"""The whole lfm2_moe step's share of the chip's peak: needed forward +
backward operations of the window's steps (``benchmark/flops_lfm2.py``: the
short convolutions' projections, gates and taps, the causal mask's exact
pairs, the counters' assignments, no recomputed work) over the window's wall
time and the bf16 peak."""
from benchmark import flops_lfm2, spanread_lm


def read(obs):
    if not obs.get("peaks") or not obs.get("steps") or "seq_len" not in obs:
        return None
    per_step = flops_lfm2.train_flops_per_step(
        obs["config"], obs["batch"], obs["seq_len"],
        spanread_lm.mean_assignments(obs))
    achieved = per_step * obs["steps"] / obs["wall_s"]
    return 100.0 * achieved / obs["peaks"]["flops_per_s"]
