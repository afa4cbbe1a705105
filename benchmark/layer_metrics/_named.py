"""What the readers of the program's own names share. The program names
its Pallas kernels (`name=` on each `pallas_call`), so a kernel event's
instruction text starts `%flash_attention_fwd.3 = ...`; it opens a
`pt.step` span per `Executor.run` with `pt.executor.*` / `pt.engine.*`
phase spans inside. A program without the names (the commits before
them) gives these readers nothing to read: they return None, never 0."""

PROGRAM_SPANS = "pt."


def head(op):
    """The instruction's own name without its numeric suffix:
    `%flash_attention_fwd.3 = ...` -> `flash_attention_fwd`."""
    text = op.name
    name = text[:text.find(" =")] if " =" in text else text
    name = name.lstrip("%")
    stem, dot, suffix = name.rpartition(".")
    return stem if dot and suffix.isdigit() else name


def named_kernel_events(ctx, family_kernel, name):
    """The events of `ctx["trace"]["kernels"][family_kernel]` whose
    instruction is called `name`; None when the trace has no such
    event."""
    ops = [op for op in ctx["trace"]["kernels"].get(family_kernel, ())
           if head(op) == name]
    return ops or None


def kernel_ms_per_step(ctx, family_kernel, name):
    """Summed device time of the named kernel's events, over devices
    and steps, in ms."""
    ops = named_kernel_events(ctx, family_kernel, name)
    if ops is None or not ctx["steps"]:
        return None
    return sum(op.dur_ns for op in ops) / 1e6 \
        / ctx["trace"]["n_devices"] / ctx["steps"]


def setup_span_seconds(name):
    """Sum of this process's set-up spans called `name`
    (`paddle_tpu.observability.tracing.setup_spans()`), in seconds;
    None where the program keeps no such list or holds no such span."""
    try:
        from paddle_tpu.observability import tracing
    except ImportError:
        return None
    spans = getattr(tracing, "setup_spans", None)
    if spans is None:
        return None
    durs = [s["dur_ms"] for s in spans() if s.get("name") == name]
    return sum(durs) / 1e3 if durs else None
