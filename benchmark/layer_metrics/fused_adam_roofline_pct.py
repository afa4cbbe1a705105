"""fused_adam's share of its roofline. The kernel is bound by memory: the
least time its calls could take is the bytes they move to and from HBM
over the chip's bandwidth. The bytes are read off each call's own operand
and result types in the trace: an array XLA has already put in on-chip
memory (layout `S(n)`) costs the call no HBM traffic — its copy runs on
the async line under other work — so a call wholly on chip adds time and
no bytes. With every array in HBM a call moves 28 bytes an element (f32
p, m, v, g read; p, m, v written)."""


def read(ctx):
    ops = ctx["trace"]["kernels"].get("fused_adam")
    if not ops or ctx["peaks"] is None:
        return None
    seconds = sum(op.dur_ns for op in ops) / 1e9
    moved = sum(ctx["trace_lib"].hbm_bytes(op) for op in ops)
    if seconds <= 0 or moved <= 0:
        return None
    return 100.0 * moved / ctx["peaks"]["bytes_per_s"] / seconds
