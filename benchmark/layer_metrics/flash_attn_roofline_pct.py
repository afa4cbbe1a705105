"""The three flash-attention kernels' share of their roofline. They are
bound by compute: attention FLOPs of the cell's shapes (forward
4*B*Sq*Sk*d_model per attention, the causal decoder self-attention at
half; backward twice that; the forward the backward pass recomputes is
not counted) over the chip's peak, over the kernels' summed device
time."""


def read(ctx):
    ops = ctx["trace"]["kernels"].get("flash_attention")
    if not ops or ctx["peaks"] is None:
        return None
    seconds = sum(op.dur_ns for op in ops) / 1e9 / ctx["trace"]["n_devices"]
    flops = ctx["family"].flops_per_step(
        ctx["sizes"], ctx["traffic"])["attention_step"] * ctx["steps"]
    if seconds <= 0:
        return None
    return 100.0 * flops / (ctx["chips"] * ctx["peaks"]["flops_per_s"]) \
        / seconds
