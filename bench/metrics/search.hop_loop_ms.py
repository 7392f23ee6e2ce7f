"""Device time of the search program's hop loop per traced query batch
(ms): the operations whose outermost scope is ``ann.search.hops`` (the
shared hop loop and its gathers; the searches inside update programs lie
under an update phase and are not counted), see ``_program``."""
import _program


def read(run):
    return _program.per_call_ms(
        run, lambda scopes: scopes[0] == _program.SEARCH_HOPS,
        run.searches)
