"""Share of the traced window in which no operation ran on the chip."""
from chipbench.metrics._common import idle_share


def read(run):
    return idle_share(run)
