"""Milliseconds the producer thread works on one batch: the wall of a draw
through the whole transformer chain, the ``data-load/fetch`` span."""
from benchmark import spanread


def read(obs):
    return spanread.ms_per_count(obs, spanread.FETCH)
