"""Device milliseconds a step of one window layer's attention core: the time
under ``WindowAttentionCore`` over the window layers."""
from benchmark import flops_lm, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.CORE_SCOPES["window"],))
    layers = sum(w is not None for w in flops_lm.layer_windows(obs["config"]))
    return ms / layers if ms is not None and layers else None
