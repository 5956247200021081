"""Device milliseconds per step of the operations traced from
``pooling.py`` or ``normalization.py`` (max/average pooling and the LRN
chain, forward and backward)."""

FILES = ("pooling.py", "normalization.py")


def read(obs):
    trace, program = obs.get("trace"), obs.get("program_text")
    if trace is None or program is None or not obs.get("steps"):
        return None
    seconds, found = 0.0, False
    for name, s in trace.op_seconds().items():
        if program.category(name) in ("CONV-FWD", "CONV-BWD"):
            continue
        files = program.source_files(name)
        if files and files[0] in FILES:
            seconds, found = seconds + s, True
    return seconds / obs["steps"] * 1e3 if found else None
