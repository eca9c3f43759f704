"""Share of its roofline that ``predict`` reaches on the device, in %: the
least time the chip needs for the bytes the answer requires
(``bench.work``), over the device-busy time inside the ``predict`` spans
of the profiler trace."""
from bench.metrics._calls import roofline


def read(run):
    return roofline(run, "predict")
