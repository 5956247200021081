"""Device milliseconds a step of the head: ``LmHead`` (the product over the
vocabulary slice and the log-softmax) and the criterion's scope."""
from benchmark import spanread_lm


def read(obs):
    return spanread_lm.scoped_ms(obs, spanread_lm.HEAD_SCOPES)
