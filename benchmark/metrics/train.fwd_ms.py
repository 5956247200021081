"""Device milliseconds per step of the forward pass: operations booked to
a module scope (``jax.named_scope`` of the module's class) under ``jvp(``
and not under ``transpose(``."""
from benchmark import spanread


def read(obs):
    return spanread.scoped_ms(obs, lambda kind, direction: direction == "fwd")
