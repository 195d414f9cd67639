"""Execution policies — the *ExecPolicy analog (paper §4.1).

SUNDIALS lets users swap kernel-launch policies (ThreadDirect /
GridStride / BlockReduce) per vector without touching integrator code.
On TPU the tunable quantities are (a) whether an op runs as plain jnp
(XLA-fused) or as a hand-written Pallas kernel, and (b) the Pallas
BlockSpec tile shape (the VMEM working set — the analog of grid/block
size).  A policy object carries those choices; native data structures
accept one and thread it through to the kernels.

Backend selection
-----------------
The policy is consumed by :mod:`repro.core.dispatch`, whose **op table**
routes each hot N_Vector operation to the implementation the policy
names.  The matrix below is rendered from ``OP_TABLE`` itself
(``python -m repro.core.dispatch`` regenerates it; a test asserts it is
embedded verbatim, so ops cannot drift out of this doc):

============================  ===========================  ============================
op                            'jnp' backend                'pallas' backend
============================  ===========================  ============================
linear_sum                    vector.linear_sum            vecops lincomb (K=2)
linear_combination            vector.linear_combination    vecops lincomb kernel
scale_add_multi               vector.scale_add_multi       vecops scale_add_multi
axpy                          vector.axpy                  vecops lincomb (K=2)
dot                           vector.dot                   vecops dot_partial
wrms_norm                     vector.wrms_norm             vecops wrms_partial
wrms_norm_mask                vector.wrms_norm_mask        vecops wrms_mask_partial
dot_prod_multi                vector.dot_prod_multi        vecops multi_dot_partial
wrms_ss                       vector prod+dot              vecops wrms_partial (raw ss)
block_solve_soa               direct.gauss_jordan_batched  GJ kernel (b>8: row-tiled)
block_inverse_soa             ref.block_inverse_soa_ref    GJ inverse (b>8: row-tiled)
blockdiag_spmv_soa            jnp.einsum                   blockdiag_spmv kernel
newton_residual_soa           ref (z - gamma*f - psi)      newton fused residual
masked_update_wrms_soa        ref (where + wrms)           newton fused update+WRMS
lagrange_rescale_soa          Lagrange W + AoS einsum      newton in-kernel weights
wrms_soa                      ref (per-system WRMS)        newton wrms_soa kernel
csr_spmv                      segment_sum                  sparse ELL gather kernel
bsr_spmv_soa                  einsum+segment_sum           sparse unrolled-pattern
bsr_block_jacobi_inverse_soa  jnp.linalg.inv               diag gather + GJ inverse
============================  ===========================  ============================

The ``*_soa`` entries are the ensemble (batched-BDF) linear algebra:
the system batch rides the 128-wide lane axis and ``batch_tile`` sets
how many systems one grid program owns — the TPU analog of the paper's
CUDA-stream bundle size.  The sparse entries carry their static
pattern as hashable tuples (see :mod:`repro.core.sunmatrix`), so the
structure is compiled into the program.

Integrators thread the policy via ``ODEOptions(policy=...)``; Krylov and
Newton solvers take a ``policy=`` kwarg; :class:`MeshVectorSpec` carries
one per vector.  At the run level, a
:class:`repro.core.context.Context` owns the policy and
``ctx.options(...)`` builds ODEOptions bound to it.  ``backend='jnp'`` (XLA_FUSED, the default) reproduces
the pre-dispatch behavior exactly; ``backend='pallas'`` compiles the
fused kernels to Mosaic when the program runs on a TPU and emulates
them in interpret mode anywhere else (the CPU parity checks).
``interpret=True``/``False`` forces either mode.

Cost-model-driven dispatch (``backend='auto'``)
-----------------------------------------------
``backend='auto'`` defers the jnp-vs-pallas choice to each call site:
at trace time the dispatch layer extracts the op's shape signature —
(n, nsys, b, K, nnz, dtype) — and resolves it through
:mod:`repro.core.autotune`: a measured entry in the persisted autotune
cache (``.autotune/<device>.json``, regenerated by
``PYTHONPATH=src python -m repro.core.autotune`` and committed with the
code) wins outright; otherwise the analytical roofline model
(:mod:`repro.analysis.opcost`, over the
:data:`repro.analysis.roofline.DEVICES` table) predicts the winner and
a VMEM-feasible tile.  Per-op pins go in ``op_overrides``; resolved
decisions are inspectable via ``Context.dispatch_report()``.  ``AUTO``
is the ready-made auto policy.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple


@dataclass(frozen=True)
class ExecPolicy:
    """Execution policy for vector/matrix/solver operations.

    backend       : 'jnp'    — plain jnp ops, XLA fuses (default);
                    'pallas' — hand-written kernels from repro.kernels;
                    'auto'   — per-call-site choice from the autotune
                                cache / analytical cost model (see the
                                module docstring).
    block_elems   : streaming-kernel tile length (lane-aligned, /128).
    reduce_tile   : reduction-kernel tile length (BlockReduce analog).
    batch_tile    : batched block-solver bundle tile (systems per grid
                    program; kernels/ops.py takes the largest lane-
                    multiple divisor of the lane-padded batch not above
                    this, so any nsys — including non-multiples of 128 —
                    pads by less than one lane of identity blocks).
    interpret     : run Pallas in interpret mode.  ``None`` (default)
                    derives it from where the program runs: compiled on
                    a TPU, interpreted on any other backend.
    device        : roofline device-table name used by 'auto' dispatch
                    ('' derives it: interpret -> 'interpret', compiled
                    -> the default device's ``device_kind`` mapped
                    through :data:`repro.analysis.roofline.DEVICE_KINDS`;
                    an unmapped kind raises).
    op_overrides  : per-op backend pins for 'auto' — a hashable tuple of
                    ``(op_name, backend)`` pairs (the policy keys jit
                    caches, so this is not a dict).  Validated at
                    construction against ``dispatch.op_names()`` — an
                    unknown op or backend raises ``ValueError`` naming
                    the valid keys instead of being silently ignored at
                    resolve time.
    """

    backend: str = "jnp"
    block_elems: int = 8 * 128
    reduce_tile: int = 64 * 128
    batch_tile: int = 128
    interpret: Optional[bool] = None
    device: str = ""
    op_overrides: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        if not self.op_overrides:
            return
        # lazy: dispatch imports this module, and the presets below are
        # built (override-free) before dispatch finishes importing.
        from . import dispatch
        valid_ops = dispatch.op_names()
        bad = []
        for name, be in self.op_overrides:
            if name not in valid_ops:
                bad.append(f"unknown dispatch op {name!r}")
            if be not in ("jnp", "pallas", "auto"):
                bad.append(f"unknown backend {be!r} for op {name!r} "
                           f"(valid: 'jnp', 'pallas', 'auto')")
        if bad:
            raise ValueError(
                "invalid ExecPolicy.op_overrides: %s; valid OP_TABLE "
                "ops: %s" % ("; ".join(bad),
                             ", ".join(sorted(valid_ops))))

    def interpreted(self) -> bool:
        """Whether Pallas kernels run in interpret mode under this
        policy (``interpret``, or derived from the default backend)."""
        from repro.kernels import resolve_interpret
        return resolve_interpret(self.interpret)

    def device_name(self) -> str:
        """The roofline device this policy targets ('' -> derived)."""
        if self.device:
            return self.device
        if self.interpreted():
            return "interpret"
        import jax
        from repro.analysis.roofline import device_for_kind
        return device_for_kind(jax.devices()[0].device_kind)

    def backend_for(self, op: str) -> str:
        """Backend for one op: an ``op_overrides`` pin wins, else the
        policy-wide ``backend``."""
        for name, be in self.op_overrides:
            if name == op:
                return be
        return self.backend

    def override(self, **ops: str) -> "ExecPolicy":
        """A copy with per-op pins, e.g.
        ``AUTO.override(block_solve_soa='pallas')``."""
        merged = dict(self.op_overrides)
        merged.update(ops)
        return replace(self, op_overrides=tuple(sorted(merged.items())))


# ThreadDirect analog: one element per "thread" -> smallest aligned tiles.
THREAD_DIRECT = ExecPolicy(backend="pallas", block_elems=128)
# GridStride analog: each program strides over a large tile.
GRID_STRIDE = ExecPolicy(backend="pallas", block_elems=64 * 128)
# BlockReduce analog for reductions.
BLOCK_REDUCE = ExecPolicy(backend="pallas", reduce_tile=64 * 128)
# Pure-XLA default.
XLA_FUSED = ExecPolicy(backend="jnp")
# Cost-model/autotune-driven per-call-site selection.
AUTO = ExecPolicy(backend="auto")
