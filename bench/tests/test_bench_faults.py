"""The comparison that decides ``correct`` rejects a broken timed path.

Each case plants one fault in the program underneath a whole run on host
CPU devices (the harness's look for a chip skipped) and sees ``correct``
come out false; the controls put the reference in the next lower
precision in the program's place.  The unbroken runs beside them pass.
"""
from __future__ import annotations

import pytest

from bench_subprocess import run

# Faults in the solver's segment runner (driver.make_sim_runner), and a step
# twice the configuration's CFL step (dg_solver.stable_dt).
STEPS_FAULTS = """
import jax.numpy as jnp
from repro.swe import driver
make = driver.make_sim_runner


def broken(kind):
    def make_sim_runner(sim, n_inner=10):
        run_ok = make(sim, n_inner)

        def run_bad(state, t):
            out = run_ok(state, t)
            if kind == "unchanged":
                return state
            if kind == "half":
                keep = jnp.arange(out.shape[1]) < out.shape[1] // 2
                return jnp.where(keep[None, :, None], out, state)
            if kind == "altered":
                return out.at[0, 7, 0].add(1e-2)
            raise ValueError(kind)
        return run_bad
    return make_sim_runner


from repro.swe import dg_solver
stable_dt = dg_solver.stable_dt


def long_step(*args, **kw):
    return 2.0 * stable_dt(*args, **kw)


res = {}
for kind in KINDS:
    if kind == "long_step":
        dg_solver.stable_dt = long_step
    else:
        driver.make_sim_runner = broken(kind)
    res[kind] = run(CELL)
    driver.make_sim_runner = make
    dg_solver.stable_dt = stable_dt
res["sound"] = run(CELL)
res["control"] = run(CELL, substitute="control")
emit(**res)
"""

# The exchange left out (collectives.multi_neighbor_exchange), which the
# solver's step calls through the module.
EXCHANGE_FAULTS = """
import jax.numpy as jnp
from repro.core import collectives
exchange = collectives.multi_neighbor_exchange


def broken(kind):
    def multi_neighbor_exchange(payloads, rounds, comm, cfg, **kw):
        got = exchange(payloads, rounds, comm, cfg, **kw)
        if kind == "left_out":
            return [jnp.zeros_like(g) for g in got]
        raise ValueError(kind)
    return multi_neighbor_exchange


res = {}
for kind in KINDS:
    collectives.multi_neighbor_exchange = broken(kind)
    res[kind] = run(CELL)
    collectives.multi_neighbor_exchange = exchange
res["sound"] = run(CELL)
res["control"] = run(CELL, substitute="control")
emit(**res)
"""

STEPS = ["unchanged", "half", "altered", "long_step"]
CASES = {
    ("swe1e5-1c", 1, "steps"): STEPS,
    ("swe1e6-1c", 1, "steps"): STEPS,
    ("swe1e5-4c", 4, "steps"): STEPS,
    ("swe1e5-4c", 4, "exchange"): ["left_out"],
}


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("jax_cache"))
    out = {}
    for (cell, devices, where), kinds in CASES.items():
        code = STEPS_FAULTS if where == "steps" else EXCHANGE_FAULTS
        head = f"CELL = {cell!r}\nKINDS = {kinds!r}\n"
        out[cell, where] = run(head + code, devices, cache)
    return out


FAULTS = [(cell, where, kind) for (cell, _, where), kinds in CASES.items()
          for kind in kinds]
SIDES = sorted({(cell, where) for cell, where, _ in FAULTS})


@pytest.mark.parametrize("cell,where,kind", FAULTS)
def test_fault_is_not_correct(outcomes, cell, where, kind):
    r = outcomes[cell, where][kind]
    assert r["correct"] is False, r["checks"]
    assert r["failed"] >= 1


@pytest.mark.parametrize("cell,where", SIDES)
def test_control_is_not_correct(outcomes, cell, where):
    r = outcomes[cell, where]["control"]
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("cell,where", SIDES)
def test_sound_run_beside_the_faults_is_correct(outcomes, cell, where):
    r = outcomes[cell, where]["sound"]
    assert r["correct"] is True, r["checks"]
