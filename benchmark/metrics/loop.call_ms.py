"""Host milliseconds a step inside the loop's ``dispatch/call`` span: the
jitted call alone, which may block in the runtime (how long the call holds
the loop)."""
from benchmark import spanread_steps


def read(obs):
    return spanread_steps.span_ms(obs, spanread_steps.CALL)
