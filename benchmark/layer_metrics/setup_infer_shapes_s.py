"""Seconds this process spent inferring shapes while programs were
built: `Block.append_op` runs the appended op's lowering under
`jax.eval_shape` (the program's `tracing.build_totals()`, all op
types)."""
from . import _setup


def read(ctx):
    return _setup.infer_shapes_seconds()
