"""What the two rooflines of attention over a cache share: the device time of
the ``attend`` scope under a layer's scope, against the least work the
configuration's flops file counts for it. None on a program that lacks the
scope (the parent of the PR that added it) or a flops file without the work."""
import importlib
from typing import Optional

from . import _lm, _scopes


def share(rec, layer_scope: str, work: str) -> Optional[float]:
    by = _scopes.seconds_by_scope(rec)
    found = [s for scope, s in (by or {}).items() if {layer_scope, "attend"} <= set(scope.split("/"))]
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    if not found or not hasattr(family, work):
        return None
    per_step, _ = _lm.sequences(rec)
    flops, bytes_ = getattr(family, work)(rec.config["model"], per_step)
    return _lm.roofline_share(rec, flops, bytes_, sum(found), f"{layer_scope}/attend ({per_step} sequences a step)")
