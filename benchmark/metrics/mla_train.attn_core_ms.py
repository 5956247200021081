"""Device milliseconds a step of one layer's attention core: the time under
``FullAttentionCore`` over the layers (every layer's core is full
causal)."""
from benchmark import spanread_lm


def read(obs):
    ms = spanread_lm.scoped_ms(obs, (spanread_lm.CORE_SCOPES["full"],))
    return None if ms is None else ms / obs["config"]["num_hidden_layers"]
