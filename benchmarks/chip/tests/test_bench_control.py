"""The lower-precision controls at a test size: the reference put in the
program's place, computed in bfloat16 or with its selection's
contractions in one bfloat16 pass, must fail the cell's limits."""
import os
import sys
from pathlib import Path

# the benchmark's CPU tests: small shapes on the host CPU, the
# benchmark's own modules and the program on the path
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[3] / "src")]

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import check, harness, population
from fedbench.spec import Cell
from reference import models as refmodels
from reference.fleet import Reference


CELL = "shakespeare_lstm.stragglers30"


def control_verdict(cell_name, seed, dtype, precision, passes):
    """The cell's verdict on the reference in ``dtype`` at ``precision``,
    its FasterPAM's contractions in ``passes``, put in the program's
    place."""
    cell = Cell(cell_name)
    cfg = cell.config
    model, fleet = cfg.TINY["model"], dict(cfg.FLEET)
    pop = population.build(cfg, cell.traffic, cfg.TINY["population"], model,
                           fleet, seed)
    p0 = cfg.init_params(population.seed_key(seed), model)
    sizes = cfg.reference_sizes(model)

    def ref_of(dtype, precision):
        return Reference(refmodels.build(cfg.REFERENCE, dtype, **sizes),
                         fleet["lr"], fleet["epochs"], precision)

    control = check.reference_rounds(ref_of(dtype, precision), None, p0,
                                     pop, fleet, seed, None, (),
                                     select_passes=passes)
    prog = {"params": [harness.host(r["params"]) for r in control],
            "losses": [r["losses"] for r in control],
            "medoids": [r["medoids"] for r in control]}
    judge = check.judge_sample(pop.cohort, pop.budgets, pop.sizes, 8, seed)
    rr = check.reference_rounds(ref_of(jnp.float32, "highest"), None, p0,
                                pop, fleet, seed, prog["medoids"], judge)
    rows = check.verdict(check.compare(prog, rr, harness.host(p0)),
                         cell.limits)
    # the control ran to its end
    assert all(np.isfinite(v).all()
               for v in jax.tree.leaves(prog["params"][-1]))
    return rows


@pytest.mark.parametrize("cell_name", [CELL])
def test_bfloat16_control_is_not_correct(cell_name):
    rows = control_verdict(cell_name, 2**32 + 11, jnp.bfloat16, None, 0)
    assert rows and not all(ok for *_, ok in rows), rows


@pytest.mark.parametrize("seed", [5, 2**32 + 11, 2**33 + 17])
def test_selection_in_one_bfloat16_pass_is_not_correct(seed):
    """Only the selection's contractions lowered: the model is the
    float32 reference, and the judge still fails it."""
    rows = control_verdict(CELL, seed, jnp.float32, "highest", 1)
    failed = {name for name, *_, ok in rows if not ok}
    assert failed == {"select_ratio"}, rows
