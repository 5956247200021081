"""``train.feed_wait_ms`` (the loop's ``data-load`` span, host ms a step)
under the LM cell's name: the accepted reader itself, not a copy of it."""
from benchmark.harness import load_reader

read = load_reader("train.feed_wait_ms")
