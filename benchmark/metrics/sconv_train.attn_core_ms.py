"""Device milliseconds a step of one attention layer's core: the time under
``FullAttentionCore`` over the ``full_attention`` layers."""
from benchmark import flops_lfm2, spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.CORE_SCOPES["full"],))
    layers = flops_lfm2.layers_of(obs["config"], "full_attention")
    return None if ms is None or not layers else ms / layers
