"""The decode steps' share of their roofline: the least time the window's
steps need to read the weights once a step and the live keys and values
once a position (memory bandwidth bounds a decode step), over the seconds
the device was busy in the traced window."""
from benchmark import flops, stats


def read(obs):
    trace = obs.get("trace")
    if trace is None or not trace.device_ops or not obs.get("peaks") \
            or not obs.get("steps"):
        return None
    spans = stats.position_spans(obs["requests"], obs["t_open"],
                                 obs["t_close"])
    cfg = obs["config"]
    needed = (obs["steps"] * flops.lm_step_weight_bytes(cfg)
              + sum(flops.lm_span_kv_bytes(cfg, a, b) for a, b in spans))
    least = needed / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / trace.busy_s()
