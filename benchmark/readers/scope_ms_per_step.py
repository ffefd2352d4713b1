"""Device time, in ms per decode step, of the operations of one program
(``params.module``) that the program put under given ``jax.named_scope``
names, over the program's runs whole inside the traced window; a step is
one of a run's ``slice_steps``.

``params.scopes`` lists scope names, or prefixes ending in ``.``
(``attn.`` is every ``attn.*``). With ``params.rest`` true the number is
what those scopes leave of the program's whole device time: other scopes
and operations that carry none. The four metrics that share this reader
therefore add up to the step. The map from operation to scope is
``lib/scopes.py``. Returns nothing where the trace has no run of the
program, or where none of its operations carries a known scope (an older
program), so a bare total is never reported as ``rest``.
"""

from ..lib import scopes


def read(ctx, params):
    if ctx.device is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    table = scopes.for_window(params["module"], ctx.trace_t0, ctx.trace_t1)
    if table is None or not table.runs or not table.scoped_seconds:
        return None
    wanted = tuple(params["scopes"])
    hit = sum(v for k, v in table.by_scope.items()
              if k is not None and any(k == w or (w.endswith(".") and k.startswith(w)) for w in wanted))
    seconds = table.total_seconds - hit if params.get("rest") else hit
    return 1e3 * seconds / (table.runs * ctx.slice_steps)
