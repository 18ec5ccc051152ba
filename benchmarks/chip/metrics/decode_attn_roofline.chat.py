"""The dense decode-attention kernel's share of its roofline, in percent:
the HBM bytes the algorithm needs for the traced decode steps (each
active sequence's keys and values up to its length, its query and its
output, every layer; ``counts.decode_attention_bytes``) over the chip's
HBM bandwidth, divided by the kernel's device time in the trace.  The
kernel is memory bound at one query per sequence, so bandwidth is the
roof.  Layer: kernels (``kernels/decode_attention/``).  Moves
``itl_p95_ms``."""
import counts
import trace_reduce

# the dense Pallas kernel's operations in the decode program
KERNEL = r"^jit__decode/decode_attention$"


def read(ctx):
    tr, steps = ctx.get("trace"), ctx.get("traced_decode")
    if not tr or not steps:
        return None
    t = trace_reduce.time_matching(tr["ops_s"], KERNEL)
    if not t:
        # the kernel is off the decode path: its roofline goes silent and
        # decode_mfu.chat still bounds the step
        return None
    need = counts.total(counts.decode_attention_bytes, ctx["config"], steps)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / t
