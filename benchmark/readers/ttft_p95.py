"""95th percentile, in ms, of first stream event minus submit over all
requests whose first event came inside the whole measured window: the
end-to-end metric's own arithmetic, for a cell whose window holds too few
requests for a bound on it (``BENCHMARK.json`` then lists it per layer)."""

from ..lib.stats import percentile, window_metrics


def read(ctx, params):
    values = window_metrics(ctx.records, ctx.t0, ctx.window_t1)["ttft_ms"]["values"]
    return percentile(values, 95) if values else None
