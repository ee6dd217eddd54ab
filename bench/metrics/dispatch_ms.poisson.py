"""Device-busy ms of the busiest chip per dispatch (open loop)."""
from harness.layers import dispatch_s


def read(facts):
    s = dispatch_s(facts)
    return None if s is None else s * 1e3
