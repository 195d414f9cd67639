"""Quickstart: solve a stiff ODE through the unified front-end
(`IVP` + `integrate(method=...)` -> `Solution`), choosing the
integration method and linear solver without touching the problem.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

from repro.core.context import Context
from repro.core.ivp import IVP, integrate
from repro.core.linsol import DenseGJ


def ode_demo():
    print("=== stiff ODE via the unified front-end (CVODE analog) ===")

    def f(t, y):  # Robertson chemical kinetics
        return jnp.stack([
            -0.04 * y[0] + 1e4 * y[1] * y[2],
            0.04 * y[0] - 1e4 * y[1] * y[2] - 3e7 * y[1] ** 2,
            3e7 * y[1] ** 2])

    ctx = Context()  # ExecPolicy + MemoryHelper + run-wide counters
    prob = IVP(f=f, y0=jnp.asarray([1.0, 0.0, 0.0]))
    sol = integrate(prob, 0.0, 40.0, method="bdf", ctx=ctx,
                    opts=ctx.options(rtol=1e-6, atol=1e-10),
                    lin_solver=DenseGJ())
    st = sol.stats
    print(f"  y(40) = {[float(v) for v in sol.y]}")
    print(f"  steps={int(st.steps)} newton_iters={int(sol.nni)} "
          f"err_fails={int(st.netf)}  mass={float(jnp.sum(sol.y)):.9f}")
    print(f"  lin_solver={sol.lin_solver}  "
          f"workspace={sol.workspace_bytes}B")


if __name__ == "__main__":
    ode_demo()
