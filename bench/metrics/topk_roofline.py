"""Share of its roofline that ``topk`` reaches on the device, in %: the
least time the chip needs for the bytes any implementation that scores
every reference row must read (``bench/ops/topk.py`` ``work_bytes``), over
the device-busy time inside the ``topk`` spans of the profiler trace."""
from bench.metrics._calls import roofline


def read(run):
    return roofline(run, "topk")
