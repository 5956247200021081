"""Device milliseconds per step of the backward pass: operations booked
to a module scope under ``transpose(``."""
from benchmark import spanread


def read(obs):
    return spanread.scoped_ms(obs, lambda kind, direction: direction == "bwd")
