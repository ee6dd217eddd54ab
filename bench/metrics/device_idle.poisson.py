"""Share of the traced window in which no operation ran, chips averaged."""
from harness.layers import idle_share


def read(facts):
    return idle_share(facts)
