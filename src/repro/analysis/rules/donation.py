"""donation-aliasing: donated buffers are exclusive and dead after use.

The ensemble-BDF step loop runs with its carry **donated**
(:func:`repro.core.batched._donated_loop`) so XLA updates the history
window in place.  That is only sound when (a) no donated argument
aliases another argument of the same call — two tree leaves bound to
one buffer would make XLA write through a live alias — and (b) nothing
reads a donated buffer after the call, since donation invalidates it.
Both properties are visible in the trace: this rule scans every
``jit`` equation with ``donated_invars`` set, flags repeated
variables among its donated inputs, and flags any later equation (or
an enclosing output) that mentions a donated variable again.
"""
from repro.analysis import lint


def _scan(where, jaxpr, opaque_names, out):
    from jax.extend import core as jex_core
    for idx, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name == lint.JIT_PRIM:
            don = eqn.params.get("donated_invars", ())
            if any(don):
                invars = [v if isinstance(v, jex_core.Var) else None
                          for v in eqn.invars]
                donated = set()
                for v, d in zip(invars, don):
                    if d and v is not None:
                        donated.add(v)
                # (a) aliased leaves among the call's arguments
                for v in sorted(donated, key=str):
                    if invars.count(v) > 1:
                        out.append(lint.Violation(
                            "donation-aliasing", where,
                            f"donated call argument {v} is passed "
                            f"{invars.count(v)} times (aliased leaves "
                            f"in a donated carry)",
                            src=lint.eqn_src(eqn)))
                # (b) donated buffer read after the call
                for later in jaxpr.eqns[idx + 1:]:
                    used = [v for v in later.invars
                            if isinstance(v, jex_core.Var)
                            and v in donated]
                    for v in used:
                        out.append(lint.Violation(
                            "donation-aliasing", where,
                            f"donated buffer {v} is read after the "
                            f"donating call (by "
                            f"{later.primitive.name})",
                            src=lint.eqn_src(later)))
                escaped = [v for v in jaxpr.outvars
                           if isinstance(v, jex_core.Var)
                           and v in donated]
                for v in escaped:
                    out.append(lint.Violation(
                        "donation-aliasing", where,
                        f"donated buffer {v} escapes as an output of "
                        f"the enclosing jaxpr",
                        src=lint.eqn_src(eqn)))
        if not lint.is_opaque(eqn, opaque_names):
            for sub in lint.subjaxprs(eqn):
                _scan(where, sub, opaque_names, out)


@lint.register(
    "donation-aliasing",
    "donated carries hold no aliased leaves; no read-after-donation")
def check(ctx):
    out = []
    for tgt in ctx.donation_targets:
        _scan(tgt.name, tgt.jaxpr(), ctx.opaque_names, out)
    return out
