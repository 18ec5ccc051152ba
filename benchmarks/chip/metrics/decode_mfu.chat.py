"""The decode program's share of the chip's bf16 peak, in percent: the
FLOPs the traced decode steps need (``counts.decode_flops``: 2 per
matmul weight per active sequence, and the attention over each one's
length) over the decode program's device time in the trace times the
peak.  Layer: model step (``models/transformer.py`` decode program).
Moves ``itl_p95_ms``.  A traced slice with decode steps and no decode
program is an error: this metric bounds every kernel's roofline."""
import counts
import harness
import trace_reduce

PROGRAM = r"^jit__decode$"


def read(ctx):
    tr, steps = ctx.get("trace"), ctx.get("traced_decode")
    if not tr or not steps:
        return None
    t = trace_reduce.time_matching(tr["programs_s"], PROGRAM)
    if not t:
        # decode steps ran in the slice, so their program is in the trace
        # under another name: fail loudly rather than drop the metric
        raise harness.BenchError(
            f"traced decode steps but no program matching {PROGRAM!r} "
            f"(have {sorted(tr['programs_s'])})")
    need = counts.total(counts.decode_flops, ctx["config"], steps)
    return 100.0 * need / (t * ctx["peaks"]["bf16_flops_per_s"])
