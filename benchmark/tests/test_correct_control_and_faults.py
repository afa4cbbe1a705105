"""`correct` has to come out false for the control and for every fault a
training cell can have, and true for the sound program — here at the
rehearsal's sizes on the CPU, through the harness's own `run_cell` (which
is what `run.py` drives once it has looked for the chip).

The control is the plain reference computed in float8 (the nearest
precision below the configuration's bfloat16), put in the program's
place. The faults are planted under the timed path, in the session the
window drives: a step that returns its state unchanged; half of the batch
left out, the mean taken over the rest. A one-chip training cell has no
exchange between chips and produces no token or answer. On the chip, at
the cells' own sizes, the same readings are taken by
benchmark/calibrate.py (PERF.md has them).
"""
import io
import json

import pytest

from benchmark import run as bench_run
from benchmark.lib import compare
from benchmark.lib.cells import Cell

CELLS = ["tbase_s128", "tbig_s128", "tbase_s4096"]


def _drive(cell_name, hook=None, seed=11):
    import time
    out, err = io.StringIO(), io.StringIO()
    res = bench_run.run_cell(Cell(cell_name), seed, 0.3, 0, True,
                             time.perf_counter(), session_hook=hook,
                             out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last == res
    assert list(last)[-1] == "compared"
    assert "CPU REHEARSAL" in out.getvalue().splitlines()[0]
    return res


def _freeze_state(sess):
    """A step that returns its state unchanged."""
    import jax.numpy as jnp
    real = sess.step
    names = [v.name for v in sess.main.list_vars() if v.persistable]

    def step(i):
        keep = {}
        for n in names:
            var = sess.scope.find_var(n)
            if var is not None and var.is_initialized():
                keep[n] = jnp.copy(sess.get(n))
        loss = real(i)
        for n, a in keep.items():
            sess.scope.find_var(n).set_value(a)
        return loss
    sess.step = step


def _drop_half_the_batch(sess):
    """Half of the batch left out, the mean taken over the rest."""
    half = sess.tr["batch"] // 2
    sess.pool = [{k: v[:half] for k, v in b.items()} for b in sess.pool]
    sess.items = [sess.family.items(b) for b in sess.pool]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell):
    res = _drive(cell)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS[:1])
@pytest.mark.parametrize("fault", [_freeze_state, _drop_half_the_batch])
def test_fault_is_not_correct(cell, fault):
    res = _drive(cell, hook=fault)
    assert res["correct"] is False
    over = [k for k, c in res["compared"].items()
            if c["value"] > c["limit"]]
    assert set(over) & set(compare.NUMBERS), res["compared"]
    if fault is _freeze_state:
        assert res["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", CELLS[:1])
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_is_not_correct(cell, seed):
    c = Cell(cell)
    fam = c.family
    sz, tr = fam.sizes(c.config, True), fam.traffic(c.traffic, True)
    pool = fam.make_pool(sz, tr, seed)
    ref = fam.run_reference(sz, tr, pool, seed, 3)
    ctl = fam.run_reference(sz, tr, pool, seed, 3, precision="fp8")
    values, _ = compare.gaps(ctl, ref)
    _, ok = compare.judge(values, c.limits_for(True))
    assert not ok, values
    same, _ = compare.gaps(ref, ref)
    assert compare.judge(same, c.limits_for(True))[1]
