"""Host milliseconds a step between two calls: the counter ``loop`` (an
iteration's wall) less the ``dispatch/call`` span."""
from benchmark import spanread_steps


def read(obs):
    return spanread_steps.between_calls_ms(obs)
