"""Device time of the vector-row reads per traced update call (ms): the
operations under ``ann.vectors.rows`` (``types.read_vectors``, the XLA
gathers of table rows: RobustPrune's candidates, the top-c candidate
matrices, the deleted points' own vectors, and at more than one table
row a vector the hop loops' gathers too) beneath an update phase, see
``_program``.  A program without the scope reads nothing."""
import _program

VECTOR_ROWS = "ann.vectors.rows"


def read(run):
    return _program.per_call_ms(
        run, lambda scopes: VECTOR_ROWS in scopes
        and _program.outermost_phase(scopes) != "", run.updates)
