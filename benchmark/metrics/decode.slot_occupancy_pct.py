"""Share of the decoder's slot-steps that ran a request's position: the
positions the window's requests ran (read from the client's side) over
slots x the steps the decoder counted between window open and close."""
from benchmark import stats


def read(obs):
    if not obs.get("steps") or not obs.get("requests"):
        return None
    spans = stats.position_spans(obs["requests"], obs["t_open"],
                                 obs["t_close"])
    ran = sum(b - a for a, b in spans)
    return 100.0 * ran / (obs["slots"] * obs["steps"])
