"""The open loop offers every seed the same arrivals, in its own order."""
import numpy as np

from harness import traffic

MIX = dict(loop="open", rate_qps=1249.3, arrival_seed=7, query_pool=8192)


def test_same_gaps_other_order():
    a = traffic.schedule(MIX, 20.0, 2**31 + 11)
    b = traffic.schedule(MIX, 20.0, 3_000_000_019)
    assert not np.array_equal(a, b)
    assert abs(len(a) - len(b)) < 0.01 * len(a)
    assert abs(len(a) / 20.0 - MIX["rate_qps"]) < 0.03 * MIX["rate_qps"]
    np.testing.assert_array_equal(a, traffic.schedule(MIX, 20.0, 2**31 + 11))
    assert a[0] > 0 and a[-1] <= 20.0 and (np.diff(a) > 0).all()
