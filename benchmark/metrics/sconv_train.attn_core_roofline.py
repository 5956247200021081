"""The attention cores' share of their roofline: the least time their needed
QK + PV operations (32 heads of 64, forward + backward over exactly the
causal pairs, the ``full_attention`` layers) take at the bf16 peak, over the
device time under ``FullAttentionCore``."""
from benchmark import flops_lfm2, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.CORE_SCOPES["full"],))
    if not ms or not obs.get("peaks"):
        return None
    needed = obs["batch"] * flops_lfm2.attention_core_train(
        obs["config"], obs["seq_len"])
    return 100.0 * needed / obs["peaks"]["flops_per_s"] / (ms / 1e3)
