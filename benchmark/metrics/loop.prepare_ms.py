"""Host milliseconds a step inside the loop's ``dispatch/prepare`` span: the
learning rate, the key (two small device programs) and the rate's copy to
the device, which precede the call."""
from benchmark import spanread_steps


def read(obs):
    return spanread_steps.span_ms(obs, spanread_steps.PREPARE)
