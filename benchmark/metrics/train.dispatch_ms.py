"""Host milliseconds of the optimizer's loop per step: its ``dispatch``
span (key, learning rate, the jitted call) and its ``host-wait`` span (the
device -> host sync at the cadence boundary)."""


def read(obs):
    spans, steps = obs.get("spans"), obs.get("steps")
    if not spans or not steps:
        return None
    total = sum(spans.get(p, (0.0, 0))[0] for p in ("dispatch", "host-wait"))
    return total / steps * 1e3
