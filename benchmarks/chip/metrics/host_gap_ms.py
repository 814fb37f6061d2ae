"""Device-idle milliseconds per engine step under the engine's own phase
spans (``engine.readback``, ``engine.inputs``, ...), from the trace: the
host's share of a tick (see ``trace_phases.reduce``)."""


def read(ctx):
    if not ctx.trace:
        return None
    return ctx.trace.get("host_gap_ms")
