"""Spread of the chips' own work: 100 x (max - min) / max over the chips
of device-busy time outside collectives (``busy_ns - collective_ns``).
A chip that runs per-batch work the others do not, or a shard that scans
more, leaves the rest waiting inside the all-gather, where busy time
alone counts the wait as work. None with fewer than two chips."""


def read(facts):
    own = [d["busy_ns"] - d["collective_ns"]
           for d in facts["trace"]["devices"]]
    if len(own) < 2 or max(own) <= 0:
        return None
    return 100.0 * (max(own) - min(own)) / max(own)
