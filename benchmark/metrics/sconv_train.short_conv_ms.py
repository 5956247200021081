"""Device milliseconds a step of the gated short convolutions, all conv
layers: the module's own scope ``ShortConv`` (the input and output
projections) and its inner ``ShortConvCore`` (the split, the two gates and
the taps), forward, the layer's recomputation and backward."""
from benchmark import spanread_lm


def read(obs):
    return spanread_lm.scoped_ms(obs, ("ShortConv", "ShortConvCore"))
