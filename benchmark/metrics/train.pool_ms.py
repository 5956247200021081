"""Device milliseconds per step under the scopes ``SpatialMaxPooling``
and ``SpatialAveragePooling``, forward and backward."""
from benchmark import spanread


def read(obs):
    return spanread.scoped_ms(
        obs, lambda kind, direction: kind in spanread.POOL_SCOPES)
