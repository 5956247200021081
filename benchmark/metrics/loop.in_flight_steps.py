"""Mean steps the device still held when the loop dispatched the next one:
the counter ``dispatch/in-flight`` (the counts summed) over its bookings."""
from benchmark import spanread_steps


def read(obs):
    return spanread_steps.in_flight_steps(obs)
