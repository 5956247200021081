"""Mean milliseconds between the last device operation of one step and the
first of the next, over the steps of the traced window whose
``dispatch/call`` the trace holds."""
from benchmark import spanread_steps


def read(obs):
    gaps = spanread_steps.step_gaps(obs)
    return None if gaps is None else gaps["gap_ms"]
