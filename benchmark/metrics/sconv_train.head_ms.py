"""``lm_train.head_ms`` (device milliseconds a step of ``LmHead`` and the
criterion) under the short-convolution cell's name: the accepted reader
itself, not a copy of it."""
from benchmark.harness import load_reader

read = load_reader("lm_train.head_ms")
