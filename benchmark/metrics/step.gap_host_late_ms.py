"""The part of ``step.gap_ms`` that lies before the next step's
``dispatch/call`` began: the loop had not called yet."""
from benchmark import spanread_steps


def read(obs):
    gaps = spanread_steps.step_gaps(obs)
    return None if gaps is None else gaps["late_ms"]
