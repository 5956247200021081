"""Device milliseconds a step under ``LatentAttention`` outside the core,
all layers: the module's own scope (the query and output projections) and
its inner ``LatentKV`` (the down-projection, the latent's norm, the
up-projection, the split, the rotary and the join of the shared key),
forward, the layer's recomputation and backward."""
from benchmark import spanread_lm


def read(obs):
    return spanread_lm.scoped_ms(obs, ("LatentAttention", "LatentKV"))
