"""``lm_train.moe_experts_roofline`` (the grouped products' share of their
roofline, from the configuration's ``hidden_size`` and
``moe_intermediate_size`` and the counters' assignments) under the
short-convolution cell's name: the accepted reader itself, not a copy of it."""
from benchmark.harness import load_reader

read = load_reader("lm_train.moe_experts_roofline")
