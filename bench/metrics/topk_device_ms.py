"""Mean device-busy time inside each ``topk`` call, in ms, from the
profiler trace."""
from bench.metrics._calls import device_ms


def read(run):
    return device_ms(run, "topk")
