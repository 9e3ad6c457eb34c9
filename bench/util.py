"""Small helpers shared by the drivers."""
from __future__ import annotations


def span(name: str):
    """A host span on the profiler's clock; the trace reduction
    attributes the device's idle gaps to these."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def check(ctx, name: str, value: float, limit: float) -> bool:
    """Record a compared number beside its limit; True when within."""
    ctx.checks[name] = (value, limit)
    return value <= limit


def peak_bytes(device) -> int:
    """A chip's peak HBM: the allocator's peak of live buffers plus its
    peak reservation for compiled programs' temporaries."""
    stats = device.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))
