"""Device time a step of the ops the scope table does not name (absent from
it, or ``unattributed``: compiler-made copies, loop control) — the health of
the instrument itself. With the four scope metrics it adds up to
``step_device_s``. Notes: its largest ops, and the idle time inside the span
by the scope of the op that follows each gap."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    by = _scopes.seconds_by_scope(rec)
    if by is None:
        return None
    _scopes.unscoped_note(rec)
    return by.get(_scopes.UNATTRIBUTED, 0.0)
