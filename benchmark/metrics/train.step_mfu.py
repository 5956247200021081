"""The whole training step's share of the chip's peak: forward + backward
operations of one record, counted from the configuration's shapes, times
the records per second of the traced window, over the bf16 peak."""
from benchmark import flops


def read(obs):
    if not obs.get("peaks") or not obs.get("records"):
        return None
    per_record = flops.inception_train_flops_per_record(obs["config"])
    achieved = per_record * obs["records"] / obs["wall_s"]
    return 100.0 * achieved / obs["peaks"]["flops_per_s"]
