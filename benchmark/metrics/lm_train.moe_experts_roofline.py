"""The routed experts' grouped products' share of their roofline: 3
products x 2 x hidden x expert width x the assignments held (the counters'
mean a step), forward + backward, at the bf16 peak, over the device time of
the grouped-product kernels (``MoeExperts``)."""
from benchmark import flops_lm, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.EXPERTS,))
    held = spanread_lm.mean_assignments(obs)
    if not ms or not held or not obs.get("peaks"):
        return None
    needed = sum(flops_lm.expert_products_train(obs["config"], a)
                 for a in held)
    return 100.0 * needed / obs["peaks"]["flops_per_s"] / (ms / 1e3)
