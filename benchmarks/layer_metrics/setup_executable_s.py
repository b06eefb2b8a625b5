"""The ``backend_compile`` span under ``compile``: ``lowered.compile()``, which is
a compile or a read of the persistent cache. The span's attrs say which, and the
notes repeat them with the key's first 12 characters."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step builder", "s", "program_span", "setup_s"


def read(rec):
    for s in rec.spans_named("backend_compile"):
        a = s.get("attrs", {})
        name, _, digest = str(a.get("cache_key") or "").rpartition("-")
        read_s = a.get("cache_read_s")
        rec.notes.append(f"backend_compile {s['dur_s']:.3f} s: cache {a.get('cache')}, "
                         f"cache_read_s {'none' if read_s is None else format(read_s, '.3f')}, "
                         f"key {name}-{digest[:12]}")
    return _scopes.span_seconds(rec, "backend_compile", parent="compile")
