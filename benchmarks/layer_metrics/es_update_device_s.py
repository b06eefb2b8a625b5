"""Device time a step of the ES machinery itself: the ``es_noise`` scope
(noise sample, each member's perturbation) and the ``es_update`` scope
(fitness, update, health metrics); the split goes to the notes."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    _scopes.inner_note(rec, "es_noise")
    _scopes.inner_note(rec, "es_update")
    return _scopes.scope_seconds(rec, "es_noise", "es_update")
