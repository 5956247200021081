"""``lm_train.device_idle_pct`` (share of the traced window in which no operation
ran on the device) under the short-convolution cell's name: the accepted
reader itself, not a copy of it."""
from benchmark.harness import load_reader

read = load_reader("lm_train.device_idle_pct")
