"""Device time of the delete search phase per traced update call (ms): the
operations under ``ann.delete.search`` (the deleted points' batched greedy
search), see ``_program``."""
import _program


def read(run):
    return _program.phase_ms(run, _program.DELETE_SEARCH)
