"""Device milliseconds a step under ``ShortConvCore`` alone, all conv
layers: the element work between the short convolution's two projections
(an operation that fuses a projection with it is booked to the projection's
scope, ``ShortConv``)."""
from benchmark import spanread_lm


def read(obs):
    return spanread_lm.scoped_ms(obs, ("ShortConvCore",))
