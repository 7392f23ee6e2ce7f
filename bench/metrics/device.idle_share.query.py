"""Share of the traced window in which no operation ran on the device (%),
in a cell whose end-to-end metric is the query rate."""
import _common


def read(run):
    return _common.idle_share(run)
