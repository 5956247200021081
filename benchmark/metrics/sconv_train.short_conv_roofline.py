"""The gated short convolutions' share of their roofline: the least time
the operator's needed operations (both projections, the taps and the gates,
forward + backward, every conv layer) take at the bf16 peak, over the device
time under ``ShortConv`` + ``ShortConvCore``.  By needed counts operations
bind (a layer's activations and weights once are under 1 GB); the two
products are needed whatever implements the element work between them."""
from benchmark import flops_lfm2, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, ("ShortConv", "ShortConvCore"))
    if not ms or not obs.get("peaks"):
        return None
    needed = flops_lfm2.short_conv_train(
        obs["config"], obs["batch"] * obs["seq_len"])
    return 100.0 * needed / obs["peaks"]["flops_per_s"] / (ms / 1e3)
