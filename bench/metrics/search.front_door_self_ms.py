"""Median host time of a query call's front door less its dispatch (ms):
each ``ann.search`` host span less its ``ann.search.dispatch`` child, so the
bucket padding and the eager slot-to-id map, see ``_program``."""
import _program


def read(run):
    return _program.front_door_self_ms(run)
