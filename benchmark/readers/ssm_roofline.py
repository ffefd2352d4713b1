"""Share of the HBM roofline a part of the state-space layers reaches in a
decode step, in %. ``params.part``:

- ``state``: the recurrence. Numerator: the family's ``ssm_state_bytes(cfg,
  rows)``, the recurrent state (``S`` and the convolution's tail, every
  state-space layer) of the LIVE rows read and written once, ``rows`` the
  mean of the traced decode slices' ``sched.slice`` ``rows``; a row that is
  not live has no state anyone needs, so what the program streams for the
  rest of its row bucket is the gap, not the numerator.
- ``proj``: the mixers' two projections. Numerator: the family's
  ``ssm_proj_bytes(cfg)``, their int8 codes read once.

Both at the table's HBM rate, over the device time, per step, of the
decode-slice program's (``params.module``) operations under the scopes that
start with one of ``params.prefixes`` (``ssm.conv`` + ``ssm.update``;
``ssm.in_proj`` + ``ssm.out_proj``), found on the operation's ``op_name``
path as ``scope_prefix_ms_per_step`` finds them. Returns nothing where there
is no trace, the trace has no run of the program, no operation lies under
the scopes (a program without state-space layers), no slice was traced, or
the family does not count these bytes."""

from ..lib import spans as S
from ..lib.family import load as family_of
from . import scope_prefix_ms_per_step as by_prefix

SLICE = "sched.slice"
COUNTS = {"state": "ssm_state_bytes", "proj": "ssm_proj_bytes"}


def live_rows(t0, t1):
    """Mean ``rows`` of the decode slices whole inside ``[t0, t1]``, or nothing."""
    rows = [s.attrs["rows"] for s in S.finished(t0, t1) if s.name == SLICE and "rows" in s.attrs]
    return sum(rows) / len(rows) if rows else None


def read(ctx, params):
    if ctx.chip is None:
        return None
    count = getattr(family_of(ctx.cfg), COUNTS[params["part"]], None)
    ms = by_prefix.read(ctx, params)  # ms a step under the prefixes; None without a trace or a run
    if count is None or not ms:
        return None
    if params["part"] == "state":
        rows = live_rows(ctx.t0, ctx.t1)
        if not rows:
            return None
        need = count(ctx.cfg, rows)
    else:
        need = count(ctx.cfg)
    return 100.0 * need / float(ctx.chip["hbm_bytes_per_s"]) / (ms * 1e-3)
