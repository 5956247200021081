"""The convolutions' share of their roofline: the least time their
forward + backward operations need at the bf16 peak (compute bounds them)
over the device time of the trace's operations that hold a convolution."""
from benchmark import flops


def read(obs):
    trace, program = obs.get("trace"), obs.get("program_text")
    if trace is None or program is None or not obs.get("peaks"):
        return None
    seconds = sum(s for name, s in trace.op_seconds().items()
                  if program.category(name) in ("CONV-FWD", "CONV-BWD"))
    if seconds <= 0:
        return None
    needed = flops.inception_train_flops_per_record(
        obs["config"], convs_only=True) * obs["records"]
    return 100.0 * needed / obs["peaks"]["flops_per_s"] / seconds
