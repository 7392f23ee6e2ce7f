"""Share of the traced window in which no operation ran on the device (%),
in a cell whose end-to-end metric is the update rate."""
import _common


def read(run):
    return _common.idle_share(run)
