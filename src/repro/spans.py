"""The names the program gives its layers in a ``jax.profiler`` trace.

Host spans are ``TraceAnnotation``s named ``PREFIX + <layer>``; they cost
about a microsecond while no profiler runs. Device scopes are
``jax.named_scope``s, which only set the ops' ``op_name`` metadata, so a
trace (or the HLO) says which layer of a jitted step each device op belongs
to. ``docs/ARCHITECTURE.md`` ("Profiling a job") says what each name
covers; the chip benchmark reads these names from here.

JAX is imported on first use, so that the host-only modules that open spans
(the sharding service, the data pipeline) still import without it.
"""
from __future__ import annotations

PREFIX = "repro/"

#: device scopes, one per layer of the train step; none holds a "/"
SCOPES = ("emb_relayout", "emb_fwd", "emb_bwd", "dense", "opt_update")


def span(name: str):
    """Host span ``repro/<name>`` (a ``TraceAnnotation``) around a call."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(PREFIX + name)


def scope(name: str):
    """Device scope ``name``, one of ``SCOPES``, for the ops traced in it."""
    import jax
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)
