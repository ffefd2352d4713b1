"""Device time, in ms per decode step, of the operations of one program
(``params.module``) whose innermost ``jax.named_scope`` is one of
``params.scopes`` (names, or prefixes ending in ``.``): the sibling of
``scope_ms_per_step`` for scopes that ``lib/scopes.py``'s accepted list does
not hold (``moe.*``; ``lib/scope_paths.py``). A step is one of a run's
``slice_steps``. Returns nothing where the trace has no run of the program
or none of its operations carries a known scope."""

from ..lib import scope_paths


def read(ctx, params):
    if ctx.device is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    found = scope_paths.seconds_under(params["module"], ctx.trace_t0, ctx.trace_t1, params["scopes"])
    if found is None:
        return None
    seconds, runs = found
    return 1e3 * seconds / (runs * ctx.slice_steps)
