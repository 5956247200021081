"""Host milliseconds per step that the loop's thread waited for a batch:
the trainer's ``data-load`` span alone, the feed's time on the critical
path.  (``train.input_wait_ms`` adds to it the transfer thread's wall, which
overlaps the wait.)"""
from benchmark import spanread


def read(obs):
    return spanread.ms_per_step(obs, ("data-load",))
