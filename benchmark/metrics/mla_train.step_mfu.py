"""The whole latent-attention step's share of the chip's peak: needed
forward + backward operations of the window's steps
(``benchmark/flops_mla.py``: the causal mask's exact pairs at 192 + 128 a
head, the counters' assignments, no recomputed work) over the window's wall
time and the bf16 peak."""
from benchmark import flops_mla, spanread_lm


def read(obs):
    if not obs.get("peaks") or not obs.get("steps") or "seq_len" not in obs:
        return None
    per_step = flops_mla.train_flops_per_step(
        obs["config"], obs["batch"], obs["seq_len"],
        spanread_lm.mean_assignments(obs))
    achieved = per_step * obs["steps"] / obs["wall_s"]
    return 100.0 * achieved / obs["peaks"]["flops_per_s"]
