"""Device time of the insert search phase per traced update call (ms): the
operations under ``ann.insert.search`` (slot allocation, vector writes and
the new points' batched greedy search), see ``_program``."""
import _program


def read(run):
    return _program.phase_ms(run, _program.INSERT_SEARCH)
