"""The attention cores' share of their roofline: the least time their needed
QK and PV operations, forward + backward (causal and window pairs counted
exactly), take at the bf16 peak, over the device time under
``WindowAttentionCore`` and ``FullAttentionCore``."""
from benchmark import flops_lm, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, spanread_lm.CORE_SCOPES.values())
    if not ms or not obs.get("peaks"):
        return None
    needed = obs["batch"] * flops_lm.attention_core_train(
        obs["config"], obs["seq_len"])
    return 100.0 * needed / obs["peaks"]["flops_per_s"] / (ms / 1e3)
