"""Device milliseconds a step under ``MoeRoute``: router product, sigmoid,
top-k, the sort, the row gathers and scatter-adds, the chunk loop's own
time, forward and backward; the grouped products themselves are
``MoeExperts``."""
from benchmark import spanread_lm


def read(obs):
    return spanread_lm.scoped_ms(obs, ("MoeRoute",))
