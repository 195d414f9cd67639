"""Each fault a cell can have, planted under the timed path, makes the
run come out not correct.

The faults: the integration returns its state unchanged; half of the
batch is left out (those systems come back at their initial state);
an answer is altered where it is produced.  The exchange between chips
is not among them: every cell runs on one chip.
"""
import functools

import jax.numpy as jnp
import pytest

from chipbench.tests.support import run_cell

FAULTS = ("unchanged", "half_batch", "altered")


def broken(y, y0, fault, live=None):
    """The answer ``y`` (systems on axis 0, the first ``live`` of them
    real) as the fault leaves it."""
    if fault == "unchanged":
        return jnp.broadcast_to(y0, y.shape).astype(y.dtype)
    if fault == "half_batch":
        live = y.shape[0] if live is None else live
        keep = jnp.arange(y.shape[0]) < live // 2
        return jnp.where(keep[:, None], y, y0).astype(y.dtype)
    return y.at[:, 0].multiply(1.01)


@pytest.mark.parametrize("fault", FAULTS)
def test_ensemble_fault_is_not_correct(tiny_root, capsys, monkeypatch,
                                       fault):
    from repro.core import ivp

    real = ivp.integrate

    @functools.wraps(real)
    def integrate(problem, t0, tf, method="bdf", **kw):
        sol = real(problem, t0, tf, method, **kw)
        return sol._replace(y=broken(sol.y, problem.y0, fault))

    monkeypatch.setattr(ivp, "integrate", integrate)
    res = run_cell(tiny_root, capsys, "robertson_mesh.bulk", seconds=0.3)
    assert res["correct"] is False
    assert res["checks"]["worst_err"]["value"] > \
        res["checks"]["worst_err"]["limit"]


@pytest.mark.parametrize("fault", FAULTS)
def test_service_fault_is_not_correct(tiny_root, capsys, monkeypatch,
                                      fault):
    from repro.serve.solver import SolverServer

    real = SolverServer._run_compiled

    def run_compiled(self, entry, sess, tfa, params):
        y, st, sess_out = real(self, entry, sess, tfa, params)
        live = int(jnp.sum(tfa > sess.t))      # padded lanes: tf == t
        return broken(y, sess.Z[0].T, fault, live), st, sess_out

    monkeypatch.setattr(SolverServer, "_run_compiled", run_compiled)
    res = run_cell(tiny_root, capsys, "robertson_service.poisson",
                   seconds=1.0)
    assert res["correct"] is False
