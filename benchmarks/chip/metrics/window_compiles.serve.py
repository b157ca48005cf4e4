"""Compiles and retraces inside the traced window: JAX's jaxpr traces and
backend compiles (from ``jax.monitoring``) plus the retraces
``PlanFnCache.traces`` counted for the cell's rollout.  Should read 0;
read only in cells whose calls open ``gateway.window`` spans."""


def read(summary, ctx):
    if not summary.spans_named("gateway.window"):
        return None
    return float(ctx["compiles"])
