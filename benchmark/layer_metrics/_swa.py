"""What the readers of the sliding-window flash kernels share. The program
names the window layers' kernels `flash_attention_window_fwd` and
`flash_attention_window_bwd` (the split pair's dq kernel, where it runs,
`flash_attention_window_dq`; the family classifies all of them as
`flash_attention_window`, apart from the full layers' `flash_attention_*`)
and keeps a persistable `window_attn_pairs` counter that every step
overwrites with the (query, key) pairs each window layer's band admitted;
the family reads it after the proof steps. A program without them gives
these readers nothing to read: they return None, never 0.

The operations the window layers need are computed here, from the shapes
and the counter: forward 2 * H * pairs * (d + d) (q k^T and p v over the
admitted pairs, `pairs` already counting the batch), a step three
forwards, nothing recomputed counted."""
from . import _dsa


def kernel_seconds_per_step(ctx):
    """Summed device time of the window kernels' events over devices and
    steps, in seconds; None where the trace has none."""
    return _dsa.kernel_seconds_per_step(ctx, "flash_attention_window")


def admitted_pairs(ctx):
    """The counter [window layers] as the family read it, or None where
    the family reads none or it never counted."""
    read = getattr(ctx["family"], "window_pairs", None)
    pairs = read(ctx["sizes"]) if read is not None else None
    if pairs is None or not pairs.size or pairs.sum() <= 0:
        return None
    return pairs


def window_flops_per_step(sizes, pairs):
    """FLOPs the window layers' attention needs in one step, forward and
    backward, at the pairs counted (all window layers together)."""
    return 3 * 2.0 * sizes["num_attention_heads"] * float(pairs.sum()) \
        * 2 * sizes["head_dim"]
