"""Device time of the insert link phase per traced update call (ms): the
operations under ``ann.insert.link`` (the new rows' RobustPrunes and the
link scan with its reverse-edge appends), see ``_program``."""
import _program


def read(run):
    return _program.phase_ms(run, _program.INSERT_LINK)
