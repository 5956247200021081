"""``lm_train.expert_load_max_over_mean`` (the busiest held expert's tokens over
the mean held expert's, from ``DroplessMoE``'s counters) under the short-
convolution cell's name: the accepted reader itself, not a copy of it."""
from benchmark.harness import load_reader

read = load_reader("lm_train.expert_load_max_over_mean")
