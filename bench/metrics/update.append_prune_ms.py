"""Device time of the RobustPrunes inside edge appends per traced update
call (ms): the operations under ``ann.prune`` beneath ``ann.edges.append``,
in the insert link and delete repair phases together, see ``_program``."""
import _program


def read(run):
    return _program.per_call_ms(
        run, _program.nested(_program.EDGES_APPEND, _program.PRUNE),
        run.updates)
