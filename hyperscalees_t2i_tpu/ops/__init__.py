"""TPU ops: sampling primitives, Pallas kernels, distributed attention.

Re-exports are LAZY (PEP 562): ``ops.pallas_gate`` is stdlib-only at
import and is consumed by jax-free processes (the bench ladder parent,
tools/bench_report.py) — an eager ``from .ring_attention import ...`` here
would drag jax into them through the package init.
"""

_LAZY = {
    "filter_top_k": "sampling",
    "filter_top_p": "sampling",
    "sample_top_k_top_p": "sampling",
    "ring_attention": "ring_attention",
}

__all__ = list(_LAZY)


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(f".{_LAZY[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
