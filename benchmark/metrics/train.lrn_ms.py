"""Device milliseconds per step under the scope ``SpatialCrossMapLRN``,
forward and backward."""
from benchmark import spanread


def read(obs):
    return spanread.scoped_ms(
        obs, lambda kind, direction: kind == spanread.LRN_SCOPE)
