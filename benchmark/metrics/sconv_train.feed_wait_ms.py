"""``lm_train.feed_wait_ms`` (the loop's ``data-load`` span, host milliseconds a
step) under the short-convolution cell's name: the accepted reader itself, not
a copy of it."""
from benchmark.harness import load_reader

read = load_reader("lm_train.feed_wait_ms")
