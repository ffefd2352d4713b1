"""Share of the HBM roofline the decode step reaches, in %.

Both sides are taken run by run from the trace's runs of the decode-slice
program (``params.module``: a pattern on the program's name) inside the
traced part of the window. Denominator: those runs' device time.
Numerator: the least time the chip could take for them at the table's HBM
rate, each of a run's ``slice_steps`` steps moving the ``decode_step_bytes``
of the configuration's family (``lib/family.py``; the dense family's is
``lib/shapes.py``: int8 weights once, the live rows' real contexts of KV,
one token written per row, the logits). The rows of a run, their contexts
and the steps each still needed come from the consumers' log at the run's
middle (no token arrives while a slice runs). Memory-bound by a wide margin
at these batch sizes, so bytes bound it.
"""

from ..lib import family


def read(ctx, params):
    runs = ctx.program_runs(params["module"])
    if not runs or ctx.chip is None:
        return None
    step_bytes = family.load(ctx.cfg).decode_step_bytes
    device_s = need = 0.0
    for a, b, _ in runs:
        work = ctx.slice_work(ctx.host_time((a + b) / 2.0))
        # a row that needs n of the slice's steps is n / slice_steps of a row in each
        rows = sum(n for _, n in work) / ctx.slice_steps
        tokens = sum((context + n / 2.0) * n for context, n in work) / ctx.slice_steps
        need += ctx.slice_steps * step_bytes(ctx.cfg, rows, tokens)
        device_s += b - a
    return 100.0 * need / float(ctx.chip["hbm_bytes_per_s"]) / device_s
