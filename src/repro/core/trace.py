"""Names for what the profiler shows of the solve path.

``scope`` names device work inside jitted code (``jax.named_scope``: it
changes only the HLO's ``op_name`` metadata, never the compiled code);
``span`` names host work (``jax.profiler.TraceAnnotation``: recorded in
the profiler's own trace, on the clock of its device operations, and a
cheap no-op while no profiler runs).  Every name starts with ``PREFIX``.
The profiler being on or off is the only switch.
"""
import jax

__all__ = ["PREFIX", "scope", "span"]

PREFIX = "ghost."


def scope(name: str):
    """Name the device operations traced inside the block."""
    return jax.named_scope(PREFIX + name)


def span(name: str, **attrs):
    """Name a stretch of host work; ``attrs`` become the span's
    attributes (more can be added with ``set_metadata`` inside it)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)
