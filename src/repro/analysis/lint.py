"""sunlint — jaxpr-level static verification of the repo's invariants.

The paper's thesis is that the GPU-enabled infrastructure adds
*negligible overhead*; PRs 1-6 established the invariants that keep it
true (SoA hot loops with zero layout conversions, jnp/pallas kernel
contracts, donated carries, dtype discipline, one coherent op table).
This module checks them *statically*: it traces the integrators and the
dispatch ops to jaxprs and walks the equations, the way
byteprofile-analysis walks HLO to assign costs — except the output is a
verdict, not a cost.

Architecture
------------
* **Rules** live in :mod:`repro.analysis.rules` and register themselves
  via :func:`register`; each is a callable ``rule(ctx) -> [Violation]``.
* A :class:`LintContext` supplies what rules inspect — the op table,
  traced hot-loop jaxprs, contract signatures, purity targets — with
  lazy defaults built from the real repo.  Fixtures
  (``tests/fixtures/bad_kernels.py``) override individual fields to
  seed deliberate violations.
* **Suppression**: a violation is muted by a ``# sunlint:
  disable=<rule>`` comment on the offending source line (when the
  jaxpr equation carries source info) or by a ``rule|where`` entry in
  the committed ``.sunlint-baseline`` file (trailing ``*`` matches a
  ``where`` prefix; ``#`` starts a comment).

CLI::

    PYTHONPATH=src python -m repro.analysis.lint --check
    PYTHONPATH=src python -m repro.analysis.lint --list
    PYTHONPATH=src python -m repro.analysis.lint --rule hot-loop-layout
    PYTHONPATH=src python -m repro.analysis.lint --fixture hidden_transpose

Exit status 0 = no unsuppressed violations, 1 = at least one (or an
unknown rule/fixture name).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

REPO_ROOT = Path(__file__).resolve().parents[3]

#: primitives whose sub-jaxprs are implementation detail, never walked
OPAQUE_PRIMS = frozenset({"pallas_call", "custom_jvp_call",
                          "custom_vjp_call", "custom_lin"})

#: the primitive a ``jax.jit`` call site traces to
JIT_PRIM = "jit"


# ---------------------------------------------------------------------------
# Violations and the rule registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Violation:
    """One finding: which rule, where (a stable dotted location string),
    and what went wrong.  ``src`` is a best-effort (file, line) from the
    jaxpr equation's source info, used for comment suppression."""

    rule: str
    where: str
    message: str
    src: Optional[Tuple[str, int]] = None

    def key(self) -> str:
        return f"{self.rule}|{self.where}"


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    doc: str
    fn: Callable


RULES: Dict[str, Rule] = {}


def register(name: str, doc: str):
    """Decorator: add a ``fn(ctx) -> [Violation]`` to the registry."""
    def deco(fn):
        RULES[name] = Rule(name, doc, fn)
        return fn
    return deco


_rules_loaded = False


def load_rules():
    """Import the rules package (idempotent); registration happens at
    module import via :func:`register`."""
    global _rules_loaded
    if not _rules_loaded:
        importlib.import_module("repro.analysis.rules")
        _rules_loaded = True
    return RULES


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def subjaxprs(eqn):
    """Yield every sub-jaxpr stored in an equation's params (scan's
    ``jaxpr``, while's ``cond_jaxpr``/``body_jaxpr``, cond's
    ``branches`` list, jit's ``jaxpr``, ...)."""
    from jax.extend import core as jex_core
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jex_core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jex_core.Jaxpr):
                yield v


def is_opaque(eqn, opaque_names=frozenset()) -> bool:
    """True when the equation is a kernel boundary the walkers must not
    descend into: a Pallas call, a custom-derivative wrapper, or a
    ``jit`` of one of the named (jitted) kernel entry points."""
    name = eqn.primitive.name
    if name in OPAQUE_PRIMS:
        return True
    return name == JIT_PRIM and eqn.params.get("name") in opaque_names


def iter_eqns(jaxpr, opaque_names=frozenset()):
    """Every equation of ``jaxpr`` and its sub-jaxprs (depth first),
    stopping at opaque kernel boundaries."""
    for eqn in jaxpr.eqns:
        yield eqn
        if is_opaque(eqn, opaque_names):
            continue
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub, opaque_names)


def contains_loop(jaxpr, opaque_names=frozenset()) -> bool:
    return any(e.primitive.name in ("while", "scan")
               for e in iter_eqns(jaxpr, opaque_names))


def innermost_while_bodies(jaxpr, opaque_names=frozenset()):
    """Body jaxprs of every ``while`` that contains no further
    while/scan at any non-opaque depth — for the ensemble integrators
    these are exactly the Newton iteration loops (the adaptive step
    loop encloses them; the kernels' internal scans sit behind opaque
    jit boundaries on the pallas backend)."""
    out = []
    for eqn in iter_eqns(jaxpr, opaque_names):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params["body_jaxpr"].jaxpr
        if not contains_loop(body, opaque_names):
            out.append(body)
    return out


def eqn_src(eqn) -> Optional[Tuple[str, int]]:
    """Best-effort (file, line) for an equation, for clickable reports
    and ``# sunlint: disable=`` comment suppression."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return None
        return (frame.file_name, int(frame.start_line))
    except Exception:
        return None


def kernel_wrapper_names() -> frozenset:
    """Names of the jitted Pallas kernel entry points in
    :mod:`repro.kernels.ops` — their ``jit`` equations carry the
    function name, which is how the walkers treat kernel internals as
    opaque."""
    from repro.kernels import ops as kops
    return frozenset(attr for attr in dir(kops)
                     if type(getattr(kops, attr)).__name__
                     == "PjitFunction")


# ---------------------------------------------------------------------------
# Trace targets and the lint context
# ---------------------------------------------------------------------------


class TraceTarget:
    """A named deferred trace: ``thunk`` builds (and caches) the jaxpr
    on first use so rules share one trace per target."""

    def __init__(self, name: str, thunk: Callable):
        self.name = name
        self._thunk = thunk
        self._jaxpr = None

    def jaxpr(self):
        if self._jaxpr is None:
            self._jaxpr = self._thunk()
        return self._jaxpr


def _hot_policy():
    # the pallas(interpret) path: kernel internals sit behind opaque
    # jit boundaries, so the trace shows exactly the *integrator's*
    # layout behavior — what the PR 5 no-transpose guarantee is about.
    # (The jnp oracles inline einsum/transpose into the body by design.)
    from repro.core.policies import ExecPolicy
    return ExecPolicy(backend="pallas", interpret=True)


def default_hot_loop_targets() -> List[TraceTarget]:
    """The ensemble Newton hot loops, traced with native-SoA RHS forms
    (the conversion-free configuration the integrators guarantee)."""
    import jax

    def bdf():
        from repro.core import batched
        from repro.core.problems import (batched_robertson,
                                         batched_robertson_soa)
        f, jac, y0 = batched_robertson(8)
        f_soa, jac_soa = batched_robertson_soa(8)
        return jax.make_jaxpr(
            lambda y: batched.ensemble_bdf_integrate(
                f, jac, y, 0.0, 1e-3, policy=_hot_policy(),
                f_soa=f_soa, jac_soa=jac_soa)[0])(y0).jaxpr

    def dirk():
        from repro.core import batched
        from repro.core.butcher import DIRK_TABLES
        from repro.core.problems import (batched_robertson,
                                         batched_robertson_soa)
        f, jac, y0 = batched_robertson(8)
        f_soa, jac_soa = batched_robertson_soa(8)
        return jax.make_jaxpr(
            lambda y: batched.ensemble_dirk_integrate(
                f, jac, y, 0.0, 1e-3, DIRK_TABLES["sdirk2"],
                policy=_hot_policy(), f_soa=f_soa,
                jac_soa=jac_soa)[0])(y0).jaxpr

    def bdf_warm():
        # the warm-start re-entry path: a session whose lanes carry
        # nonzero h/order/history enters the loop through the copy-
        # before-donate branch — the donation-aliasing rule audits that
        # the caller's session leaves are never donated directly and
        # the exported session never aliases the donated carry
        import jax.numpy as jnp

        from repro.core import batched
        from repro.core.problems import (batched_robertson,
                                         batched_robertson_soa)
        f, jac, y0 = batched_robertson(8)
        f_soa, jac_soa = batched_robertson_soa(8)
        sess = batched.SolverSession.cold(y0, 0.0)._replace(
            h=jnp.full((8,), 1e-5), q=jnp.full((8,), 2, jnp.int32),
            steps=jnp.full((8,), 3, jnp.int32))
        return jax.make_jaxpr(
            lambda s: batched.ensemble_bdf_integrate(
                f, jac, None, None, 1e-3, policy=_hot_policy(),
                f_soa=f_soa, jac_soa=jac_soa, session=s,
                return_session=True)[0])(sess).jaxpr

    return [TraceTarget("ensemble_bdf", bdf),
            TraceTarget("ensemble_dirk", dirk),
            TraceTarget("ensemble_bdf_warm_restart", bdf_warm)]


def default_contract_sigs() -> Dict[str, list]:
    """The OpSig grid the kernel-contract rule checks per op: small and
    large instances of every OP_TABLE op (block sizes straddling the
    b<=8 single-tile / b>8 row-tiled kernel regimes)."""
    from repro.analysis.opcost import OpSig
    sigs: Dict[str, list] = {}

    def add(op, **kw):
        sigs.setdefault(op, []).append(OpSig(op, "float64", **kw))

    for n in (6, 300):
        for op in ("linear_sum", "axpy"):
            add(op, n=n, k=2)
        for op in ("linear_combination", "scale_add_multi",
                   "dot_prod_multi"):
            add(op, n=n, k=3)
        for op in ("dot", "wrms_norm", "wrms_ss"):
            add(op, n=n, k=1)
        add("wrms_norm_mask", n=n, k=1)
    for b, nsys in ((3, 8), (16, 40)):
        for op in ("block_solve_soa", "block_inverse_soa",
                   "blockdiag_spmv_soa"):
            add(op, n=b, nsys=nsys, b=b)
    for n, nsys in ((3, 8), (12, 40)):
        for op in ("newton_residual_soa", "masked_update_wrms_soa",
                   "wrms_soa"):
            add(op, n=n, nsys=nsys)
    add("lagrange_rescale_soa", n=3, nsys=8, k=6)
    for n in (4, 8):
        add("csr_spmv", n=n, nnz=3 * n - 2)
    for nblk, b, nsys in ((4, 3, 8),):
        add("bsr_spmv_soa", n=nblk * b, nsys=nsys, b=b,
            nnz=3 * nblk - 2)
        add("bsr_block_jacobi_inverse_soa", n=nblk * b, nsys=nsys, b=b,
            nnz=3 * nblk - 2)
    return sigs


def default_purity_targets() -> List[TraceTarget]:
    """Abstract (eval_shape) traces of every canonical
    ``IVP.integrate`` method string plus the sunmatrix/spsolve symbolic
    phases — the surfaces where a Python branch on a tracer or a
    non-hashable static pattern would leak a concrete value."""
    import jax
    import jax.numpy as jnp

    targets = []

    def _integrate_thunk(method):
        def thunk():
            import numpy as np
            from repro.core.ivp import IVP, integrate
            from repro.core.problems import batched_robertson
            if method.startswith("ensemble"):
                f, jac, y0 = batched_robertson(4)
                prob_kw = dict(f=f, jac=jac)
            else:
                f, jac, y0b = batched_robertson(1)
                y0 = np.asarray(y0b)[0]
                sf = lambda t, y: f(jnp.asarray(t)[None],
                                    y[None, :])[0]
                sjac = lambda t, y: jac(jnp.asarray(t)[None],
                                        y[None, :])[0]
                if method.startswith("imex"):
                    prob_kw = dict(fe=lambda t, y: jnp.zeros_like(y),
                                   fi=sf, jac=sjac)
                else:
                    prob_kw = dict(f=sf, jac=sjac)
            return jax.eval_shape(
                lambda y: integrate(
                    IVP(y0=y, **prob_kw), 0.0, 1e-3, method).y,
                jax.ShapeDtypeStruct(jnp.shape(y0), jnp.float64))
        return thunk

    from repro.core.ivp import METHOD_STRINGS
    for m in METHOD_STRINGS:
        targets.append(TraceTarget(f"integrate[{m}]",
                                   _integrate_thunk(m)))

    def spsolve_thunk():
        import numpy as np
        from repro.core import spsolve, sunmatrix
        A = np.array([[4.0, 1, 0, 0], [1, 4, 1, 0],
                      [0, 1, 4, 1], [0, 0, 1, 4]])
        indptr, indices = sunmatrix.csr_pattern_from_dense(A)
        plan = spsolve.symbolic_lu(indptr, indices)
        nnz = len(indices)
        return jax.eval_shape(
            lambda vals, rhs: spsolve.lu_solve(
                plan,
                spsolve.numeric_lu(
                    plan, spsolve.scatter_from_csr(plan, indptr,
                                                   indices, vals)),
                rhs),
            jax.ShapeDtypeStruct((nnz, 5), jnp.float64),
            jax.ShapeDtypeStruct((4, 5), jnp.float64))

    targets.append(TraceTarget("spsolve.symbolic_lu+solve",
                               spsolve_thunk))
    return targets


def default_telemetry_targets() -> List[Tuple[str, TraceTarget,
                                              TraceTarget]]:
    """(name, baseline, candidate) trace pairs for telemetry-purity.

    Baseline is the *raw* integrator call — the pre-observability trace
    with no :class:`~repro.observability.ObservabilityConfig` anywhere
    near it.  Candidate is the same integration routed through
    ``IVP.integrate`` with the default (disabled) observability config
    on the context.  The rule demands the adaptive step-loop bodies be
    primitive-identical: a disabled config must add ZERO equations to
    the jitted hot loop."""
    import jax
    import jax.numpy as jnp

    def _ensemble_setup():
        from repro.core.problems import (batched_robertson,
                                         batched_robertson_soa)
        f, jac, y0 = batched_robertson(8)
        f_soa, jac_soa = batched_robertson_soa(8)
        return f, jac, y0, f_soa, jac_soa

    def bdf_base():
        from repro.core import batched
        f, jac, y0, f_soa, jac_soa = _ensemble_setup()
        return jax.make_jaxpr(
            lambda y: batched.ensemble_bdf_integrate(
                f, jac, y, 0.0, 1e-3, f_soa=f_soa,
                jac_soa=jac_soa)[0])(y0).jaxpr

    def bdf_cand():
        from repro.core.context import Context
        from repro.core.ivp import IVP, integrate
        f, jac, y0, f_soa, jac_soa = _ensemble_setup()
        return jax.make_jaxpr(
            lambda y: integrate(
                IVP(y0=y, f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa),
                0.0, 1e-3, "ensemble_bdf", ctx=Context()).y)(y0).jaxpr

    def dirk_base():
        from repro.core import batched
        from repro.core.butcher import DIRK_TABLES
        f, jac, y0, f_soa, jac_soa = _ensemble_setup()
        return jax.make_jaxpr(
            lambda y: batched.ensemble_dirk_integrate(
                f, jac, y, 0.0, 1e-3, DIRK_TABLES["sdirk2"],
                f_soa=f_soa, jac_soa=jac_soa)[0])(y0).jaxpr

    def dirk_cand():
        from repro.core.context import Context
        from repro.core.ivp import IVP, integrate
        f, jac, y0, f_soa, jac_soa = _ensemble_setup()
        return jax.make_jaxpr(
            lambda y: integrate(
                IVP(y0=y, f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa),
                0.0, 1e-3, "ensemble_dirk:sdirk2",
                ctx=Context()).y)(y0).jaxpr

    def _scalar_setup():
        import numpy as np
        from repro.core.problems import batched_robertson
        f, jac, y0b = batched_robertson(1)
        y0 = np.asarray(y0b)[0]
        sf = lambda t, y: f(jnp.asarray(t)[None], y[None, :])[0]
        sjac = lambda t, y: jac(jnp.asarray(t)[None], y[None, :])[0]
        return sf, sjac, y0

    def scalar_base():
        from repro.core import cvode
        sf, _, y0 = _scalar_setup()
        return jax.make_jaxpr(
            lambda y: cvode.bdf_integrate(sf, y, 0.0, 1e-3)[0])(
                y0).jaxpr

    def scalar_cand():
        from repro.core.context import Context
        from repro.core.ivp import IVP, integrate
        sf, sjac, y0 = _scalar_setup()
        return jax.make_jaxpr(
            lambda y: integrate(
                IVP(y0=y, f=sf, jac=sjac), 0.0, 1e-3, "bdf",
                ctx=Context()).y)(y0).jaxpr

    return [
        ("ensemble_bdf", TraceTarget("ensemble_bdf[raw]", bdf_base),
         TraceTarget("ensemble_bdf[integrate,obs-off]", bdf_cand)),
        ("ensemble_dirk", TraceTarget("ensemble_dirk[raw]", dirk_base),
         TraceTarget("ensemble_dirk[integrate,obs-off]", dirk_cand)),
        ("bdf", TraceTarget("bdf[raw]", scalar_base),
         TraceTarget("bdf[integrate,obs-off]", scalar_cand)),
    ]


def default_telemetry_enabled_targets() -> List[TraceTarget]:
    """Traces with step telemetry switched ON, scanned for host
    callback primitives — the enabled path must record through the
    in-graph ring buffer, never ``io_callback`` and friends."""
    import jax

    def enabled():
        from repro.core.context import Context
        from repro.core.ivp import IVP, integrate
        from repro.core.problems import (batched_robertson,
                                         batched_robertson_soa)
        from repro.observability import ObservabilityConfig
        f, jac, y0 = batched_robertson(8)
        f_soa, jac_soa = batched_robertson_soa(8)
        ctx = Context(observability=ObservabilityConfig(
            telemetry=True, telemetry_capacity=16))

        def run(y):
            sol = integrate(
                IVP(y0=y, f=f, jac=jac, f_soa=f_soa, jac_soa=jac_soa),
                0.0, 1e-3, "ensemble_bdf", ctx=ctx)
            return sol.y, sol.telemetry
        return jax.make_jaxpr(run)(y0).jaxpr

    return [TraceTarget("ensemble_bdf[integrate,telemetry=16]",
                        enabled)]


class LintContext:
    """What the rules inspect.  Every field has a lazy default built
    from the real repo; fixtures override via the setters."""

    def __init__(self, repo_root: Optional[Path] = None):
        self.repo_root = Path(repo_root) if repo_root else REPO_ROOT
        self.baseline_path = self.repo_root / ".sunlint-baseline"
        #: allowed float-width conversions inside hot-loop bodies, as
        #: (src_dtype, dst_dtype) string pairs — the mixed-precision
        #: seam: a future f32 Newton mode allowlists its casts here.
        self.dtype_allowlist: set = set()
        self._op_table = None
        self._opaque_names = None
        self._hot_loop_targets = None
        self._donation_targets = None
        self._contract_sigs = None
        self._purity_targets = None
        self._telemetry_targets = None
        self._telemetry_enabled_targets = None

    @property
    def op_table(self) -> dict:
        if self._op_table is None:
            from repro.core import dispatch
            self._op_table = dict(dispatch.OP_TABLE)
        return self._op_table

    @op_table.setter
    def op_table(self, table):
        self._op_table = dict(table)

    @property
    def opaque_names(self) -> frozenset:
        if self._opaque_names is None:
            self._opaque_names = kernel_wrapper_names()
        return self._opaque_names

    @opaque_names.setter
    def opaque_names(self, names):
        self._opaque_names = frozenset(names)

    @property
    def hot_loop_targets(self) -> List[TraceTarget]:
        if self._hot_loop_targets is None:
            self._hot_loop_targets = default_hot_loop_targets()
        return self._hot_loop_targets

    @hot_loop_targets.setter
    def hot_loop_targets(self, targets):
        self._hot_loop_targets = list(targets)

    @property
    def donation_targets(self) -> List[TraceTarget]:
        # the hot-loop traces contain the _donated_loop jit; sharing
        # the TraceTarget objects shares the cached trace.
        if self._donation_targets is None:
            self._donation_targets = self.hot_loop_targets
        return self._donation_targets

    @donation_targets.setter
    def donation_targets(self, targets):
        self._donation_targets = list(targets)

    @property
    def contract_sigs(self) -> Dict[str, list]:
        if self._contract_sigs is None:
            self._contract_sigs = default_contract_sigs()
        return self._contract_sigs

    @contract_sigs.setter
    def contract_sigs(self, sigs):
        self._contract_sigs = dict(sigs)

    @property
    def purity_targets(self) -> List[TraceTarget]:
        if self._purity_targets is None:
            self._purity_targets = default_purity_targets()
        return self._purity_targets

    @purity_targets.setter
    def purity_targets(self, targets):
        self._purity_targets = list(targets)

    @property
    def telemetry_targets(self) -> List[Tuple[str, TraceTarget,
                                              TraceTarget]]:
        if self._telemetry_targets is None:
            self._telemetry_targets = default_telemetry_targets()
        return self._telemetry_targets

    @telemetry_targets.setter
    def telemetry_targets(self, targets):
        self._telemetry_targets = list(targets)

    @property
    def telemetry_enabled_targets(self) -> List[TraceTarget]:
        if self._telemetry_enabled_targets is None:
            self._telemetry_enabled_targets = \
                default_telemetry_enabled_targets()
        return self._telemetry_enabled_targets

    @telemetry_enabled_targets.setter
    def telemetry_enabled_targets(self, targets):
        self._telemetry_enabled_targets = list(targets)


# ---------------------------------------------------------------------------
# Suppression
# ---------------------------------------------------------------------------


def load_baseline(path: Path) -> List[str]:
    """``rule|where`` entries (trailing ``*`` = prefix match) from the
    committed baseline file; missing file = empty baseline."""
    if not path.is_file():
        return []
    out = []
    for line in path.read_text().splitlines():
        line = line.split("#", 1)[0].strip()
        if line:
            out.append(line)
    return out


_SRC_CACHE: Dict[str, List[str]] = {}


def _source_line(fname: str, lineno: int) -> str:
    lines = _SRC_CACHE.get(fname)
    if lines is None:
        try:
            lines = Path(fname).read_text().splitlines()
        except OSError:
            lines = []
        _SRC_CACHE[fname] = lines
    if 1 <= lineno <= len(lines):
        return lines[lineno - 1]
    return ""


def is_suppressed(v: Violation, baseline: Sequence[str]) -> bool:
    for entry in baseline:
        if entry.endswith("*"):
            if v.key().startswith(entry[:-1]):
                return True
        elif entry == v.key():
            return True
    if v.src is not None:
        fname, lineno = v.src
        line = _source_line(fname, lineno)
        if "# sunlint: disable=" in line:
            disabled = line.split("# sunlint: disable=", 1)[1]
            names = {s.strip() for s in disabled.split(",")}
            if v.rule in names or "all" in names:
                return True
    return False


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def run_rules(ctx: LintContext,
              names: Optional[Sequence[str]] = None) -> List[Violation]:
    """Run the named rules (default: all) and return raw violations
    (suppression NOT applied — the caller filters)."""
    load_rules()
    if names:
        unknown = sorted(set(names) - set(RULES))
        if unknown:
            raise KeyError(f"unknown rule(s) {unknown}; registered: "
                           f"{', '.join(sorted(RULES))}")
    out: List[Violation] = []
    for name in sorted(RULES):
        if names and name not in names:
            continue
        out.extend(RULES[name].fn(ctx))
    return out


def load_fixtures(repo_root: Optional[Path] = None) -> dict:
    """``{name: (expected_rule, setup_fn)}`` from
    tests/fixtures/bad_kernels.py, loaded by path (tests/ is not a
    package on sys.path)."""
    root = Path(repo_root) if repo_root else REPO_ROOT
    path = root / "tests" / "fixtures" / "bad_kernels.py"
    spec = importlib.util.spec_from_file_location("sunlint_bad_kernels",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.FIXTURES


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="sunlint: jaxpr-level static verification")
    ap.add_argument("--check", action="store_true",
                    help="run all rules over the repo (the default)")
    ap.add_argument("--rule", action="append", default=None,
                    metavar="NAME", help="run only this rule "
                    "(repeatable)")
    ap.add_argument("--fixture", default=None, metavar="NAME",
                    help="seed a deliberately-broken fixture from "
                    "tests/fixtures/bad_kernels.py (expected exit: 1)")
    ap.add_argument("--list", action="store_true",
                    help="list registered rules and exit")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore .sunlint-baseline suppressions")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)

    load_rules()
    if args.list:
        for name in sorted(RULES):
            print(f"{name:20s} {RULES[name].doc}")
        return 0

    ctx = LintContext()
    if args.fixture:
        fixtures = load_fixtures()
        if args.fixture not in fixtures:
            print(f"unknown fixture {args.fixture!r}; available: "
                  f"{', '.join(sorted(fixtures))}", file=sys.stderr)
            return 1
        expected_rule, setup = fixtures[args.fixture]
        setup(ctx)
        print(f"fixture {args.fixture!r} seeded "
              f"(expects rule {expected_rule!r} to fire)")

    try:
        violations = run_rules(ctx, args.rule)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 1
    baseline = [] if args.no_baseline else load_baseline(
        ctx.baseline_path)
    kept = [v for v in violations if not is_suppressed(v, baseline)]
    muted = len(violations) - len(kept)

    n_rules = len(args.rule) if args.rule else len(RULES)
    for v in kept:
        loc = f"  [{v.src[0]}:{v.src[1]}]" if v.src else ""
        print(f"{v.rule}: {v.where}: {v.message}{loc}")
    summary = (f"sunlint: {len(kept)} violation"
               f"{'' if len(kept) == 1 else 's'} "
               f"({n_rules} rules, {muted} suppressed)")
    print(summary)
    return 1 if kept else 0


if __name__ == "__main__":
    # under `python -m` this file is the __main__ module; delegate to
    # the canonical import so rules register into the same RULES dict.
    from repro.analysis import lint as _lint
    sys.exit(_lint.main())
