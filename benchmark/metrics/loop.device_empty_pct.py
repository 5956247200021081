"""Share of the dispatches that found no step in flight (from there until the
launch lands the device has no work): bookings of ``dispatch/device-empty``
over bookings of ``dispatch/in-flight``."""
from benchmark import spanread_steps


def read(obs):
    return spanread_steps.device_empty_pct(obs)
