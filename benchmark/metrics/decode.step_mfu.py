"""The whole decode step's share of the chip's peak: operations of every
position the window's requests ran, each at its own context (from the
configuration's shapes), over the window and the bf16 peak."""
from benchmark import flops, stats


def read(obs):
    if not obs.get("peaks") or not obs.get("requests"):
        return None
    spans = stats.position_spans(obs["requests"], obs["t_open"],
                                 obs["t_close"])
    if not spans:
        return None
    work = sum(flops.lm_span_flops(obs["config"], a, b) for a, b in spans)
    seconds = obs["t_close"] - obs["t_open"]
    return 100.0 * work / seconds / obs["peaks"]["flops_per_s"]
