"""Device time of the delete repair phase per traced update call (ms): the
operations under ``ann.delete.repair`` (the repair scan: in-neighbour
test, candidate selection, edge removal and appends, the next entry
point), see ``_program``."""
import _program


def read(run):
    return _program.phase_ms(run, _program.DELETE_REPAIR)
