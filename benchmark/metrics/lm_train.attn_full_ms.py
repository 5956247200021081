"""Device milliseconds a step of one full layer's attention core: the time
under ``FullAttentionCore`` over the full layers."""
from benchmark import flops_lm, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.CORE_SCOPES["full"],))
    layers = sum(w is None for w in flops_lm.layer_windows(obs["config"]))
    return ms / layers if ms is not None and layers else None
