"""Whole-step model FLOPs utilisation over the traced part of the window,
in %: the FLOPs that the programs the trace shows running there had to do,
over the traced span's length (idle time included) times the chip's bf16
peak. Activations are bf16 on the int8-weight path, so the bf16 peak is
the one that bounds it.

Time and runs come from the trace: the ``bench:window`` span, and the runs
inside it of the decode-slice program (``params.decode``) and of the join
chunk's prefill program (``params.prefill``). What a run had to do comes
from the consumers' log: a decode run is, for every row decoding while it
ran, the tokens of the slice the row still needed, each at the context it
had then; a prefill run is one joiner's prompt, the request whose first
token is the next to arrive after the run.
"""

from ..lib import family


def read(ctx, params):
    decode = ctx.program_runs(params["decode"])
    if not decode or ctx.chip is None or ctx.trace_t1 <= ctx.trace_t0:
        return None
    fam = family.load(ctx.cfg)
    flops = 0.0
    for a, b, _ in decode:
        for context, n in ctx.slice_work(ctx.host_time((a + b) / 2.0)):
            flops += n * fam.decode_token_flops(ctx.cfg, context + n / 2.0)
    # joiners in the order their first tokens came; each prefill run takes
    # the earliest one not yet taken whose first token came after the run
    joiners = sorted((r.events[0][0], r.prompt_tokens) for r in ctx.records if r.events)
    at = 0
    for _, b, _ in ctx.program_runs(params["prefill"]):
        while at < len(joiners) and joiners[at][0] < ctx.host_time(b):
            at += 1
        if at < len(joiners):
            flops += fam.prefill_flops(ctx.cfg, joiners[at][1])
            at += 1
    return 100.0 * flops / (ctx.trace_t1 - ctx.trace_t0) / float(ctx.chip["bf16_flops_per_s"])
