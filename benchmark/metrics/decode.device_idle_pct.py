"""Share of the traced window in which no operation ran on the device."""


def read(obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops:
        return None
    return 100.0 * (1.0 - trace.busy_s() / obs["traced_s"])
