"""``lm_train.moe_route_ms`` (device milliseconds a step under ``MoeRoute``)
under the short-convolution cell's name: the accepted reader itself, not a
copy of it."""
from benchmark.harness import load_reader

read = load_reader("lm_train.moe_route_ms")
