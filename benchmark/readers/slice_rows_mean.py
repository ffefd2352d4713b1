"""Time-weighted mean of the rows that sat through each gap between two
decode slices, from the scheduler's ``slice_gap_sink``."""


def read(ctx, params):
    total = sum(gap for _, gap, _ in ctx.slices)
    if total <= 0:
        return None
    return sum(gap * rows for _, gap, rows in ctx.slices) / total
