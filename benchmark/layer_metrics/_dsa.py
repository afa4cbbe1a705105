"""What the readers of a learned sparse attention share. The program
names its index kernels `sparse_index_scores` and `sparse_index_select`
(the family classifies each under its own name) and keeps a persistable
`sparse_attn_kept` counter that every step overwrites with the (query,
key) pairs each layer's selection kept; the family reads it after the
proof steps. A program without them gives these readers nothing to read:
they return None, never 0."""


def kernel_seconds_per_step(ctx, kernel):
    """Summed device time of the events the family classifies as
    `kernel`, over devices and steps, in seconds; None where the trace
    has none."""
    ops = ctx["trace"]["kernels"].get(kernel)
    if not ops or not ctx["steps"]:
        return None
    return sum(op.dur_ns for op in ops) / 1e9 \
        / ctx["trace"]["n_devices"] / ctx["steps"]


def kept_pairs(ctx):
    """The counter [layers] as the family read it, or None where the
    family reads none or it never counted."""
    read = getattr(ctx["family"], "kept_pairs", None)
    kept = read(ctx["sizes"]) if read is not None else None
    if kept is None or not kept.size or kept.sum() <= 0:
        return None
    return kept
