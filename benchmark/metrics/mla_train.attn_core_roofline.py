"""The latent attention's cores' share of their roofline: the least time
their needed QK (``qk_nope_head_dim`` + ``qk_rope_head_dim``) and PV
(``v_head_dim``) operations, forward + backward over exactly the causal
pairs, take at the bf16 peak, over the device time under
``FullAttentionCore``."""
from benchmark import flops_mla, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.CORE_SCOPES["full"],))
    if not ms or not obs.get("peaks"):
        return None
    needed = obs["batch"] * flops_mla.attention_core_train(
        obs["config"], obs["seq_len"])
    return 100.0 * needed / obs["peaks"]["flops_per_s"] / (ms / 1e3)
