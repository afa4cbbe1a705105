"""What the readers of the expert layer share. The program names its
three grouped-matmul kernels `moe_grouped_matmul_fwd` / `_dx` / `_dw`
(the family classifies them as `moe_grouped_matmul`) and keeps a
persistable `moe_expert_load` counter, which the family reads after the
proof steps. A program without them gives these readers nothing to
read: they return None, never 0."""


def gmm_seconds_per_step(ctx):
    """Summed device time of the grouped-matmul events over devices and
    steps, in seconds; None where the trace has none."""
    ops = ctx["trace"]["kernels"].get("moe_grouped_matmul")
    if not ops or not ctx["steps"]:
        return None
    return sum(op.dur_ns for op in ops) / 1e9 \
        / ctx["trace"]["n_devices"] / ctx["steps"]


def expert_load(ctx):
    """(the counter [MoE layers, experts held] as the family read it,
    tokens a layer it had seen), or None where there is none."""
    read = getattr(ctx["family"], "expert_load", None)
    load = read(ctx["sizes"]) if read is not None else None
    if load is None or not load.size or load.sum() <= 0:
        return None
    tokens = ctx["family"].PROOF_STEPS * ctx["traffic"]["batch"] \
        * ctx["traffic"]["seq_len"]
    return load, tokens
