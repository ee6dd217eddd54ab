"""Share of the scan's roofline: least time of one dispatch (bytes over
HBM bandwidth or operations over peak, whichever is larger) over the
measured device time per dispatch."""
from harness.layers import dispatch_s, scan_bound_s


def read(facts):
    s = dispatch_s(facts)
    return None if not s else 100.0 * scan_bound_s(facts) / s
