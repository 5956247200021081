"""The part of ``step.gap_ms`` after the next step's ``dispatch/call``
began: the call was open, or had returned, and the device had not started."""
from benchmark import spanread_steps


def read(obs):
    gaps = spanread_steps.step_gaps(obs)
    return None if gaps is None else gaps["in_call_ms"]
