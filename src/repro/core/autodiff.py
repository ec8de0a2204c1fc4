"""Adjoint-based differentiation rules for the transform layers.

The paper's structural dichotomy -- the direct and inverse SHT are (up to
quadrature weights) adjoints of each other -- is exactly the identity JAX
needs to differentiate a transform without tracing through its
implementation.  Every layer of the transform stack is a *linear* map with
a hand-written adjoint that is just the opposite-direction transform of the
same layer:

  ===========================  =======================================
  layer (forward)              adjoint (transpose)
  ===========================  =======================================
  Legendre synthesis           Legendre analysis with unit weights
  Legendre analysis (w)        w * Legendre synthesis
  phase synthesis              fac_m * phase analysis / weights
  phase analysis               phase synthesis(w * cotangent / fac_m)
  ===========================  =======================================

:func:`linear_pair` packages one such (forward, transpose) pair as a
function that is differentiable in both modes:

* **JVP** (forward mode): the map is linear, so the tangent rule is the
  forward map applied to the tangents (``jax.custom_jvp``).
* **VJP** (reverse mode): the tangent-side computation is expressed with
  :func:`jax.custom_derivatives.linear_call`, whose registered transpose
  rule invokes the supplied adjoint -- so ``jax.grad`` calls the
  opposite-direction transform instead of transposing loop internals
  (which also avoids storing one recurrence panel per multipole).

Contract
--------
``fwd(residuals, operands)`` must be linear in ``operands``;
``transpose(residuals, cotangents)`` must be its exact transpose with
respect to the standard real inner product, returning arrays whose
shapes/dtypes match ``operands``.  ``residuals`` (geometry, seed tables,
index maps) are treated as constants of the differentiation: their
tangents are dropped, and gradients with respect to them are not defined.
Double-backward (reverse-over-reverse) is not supported by ``linear_call``;
forward-over-forward and first-order reverse are.

The adjointness of every registered pair is enforced by the property-based
dot-product tests in ``tests/test_adjoint.py``:
``<fwd(x), y> == <x, transpose(y)>`` to dtype rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero, linear_call
from jax.tree_util import tree_flatten, tree_unflatten

__all__ = ["linear_pair"]


def _is_szero(x) -> bool:
    return isinstance(x, SymbolicZero)


def linear_pair(fwd, transpose, residuals, operands):
    """Run ``fwd(residuals, operands)`` with adjoint-based custom AD rules.

    Parameters
    ----------
    fwd : callable(residuals, operands) -> outputs, linear in ``operands``.
    transpose : callable(residuals, cotangents) -> operand cotangents; the
        exact transpose of ``fwd`` (see module docstring contract).
    residuals : pytree of non-differentiated arrays (may be traced, e.g.
        sharded geometry operands inside shard_map; may include ints).
    operands : pytree of arrays carrying the linearity (and the gradients).

    Returns ``fwd(residuals, operands)``, differentiable in forward mode
    (tangent = ``fwd`` on tangents) and first-order reverse mode
    (cotangent = ``transpose`` on cotangents).
    """

    @jax.custom_jvp
    def call(ops, res):
        return fwd(res, ops)

    @functools.partial(call.defjvp, symbolic_zeros=True)
    def call_jvp(primals, tangents):
        ops, res = primals
        d_ops, d_res = tangents
        # Residuals are constants of the differentiation: a perturbed
        # residual (non-symbolic-zero tangent) means someone is asking for
        # d/d(weights, geometry, seeds, ...), which this rule does not
        # provide -- fail loudly rather than return a silently-zero grad.
        if any(not _is_szero(t) for t in tree_flatten(
                d_res, is_leaf=_is_szero)[0]):
            raise ValueError(
                "linear_pair: differentiation with respect to a residual "
                "argument (quadrature weights, grid geometry, seed tables, "
                "index maps) is not supported -- only the linear operands "
                "(alm / maps / delta) carry adjoint-based gradients")
        y = call(ops, res)
        # linear_call transposition requires every linear operand to be an
        # actual linear (undefined-primal) input: operands with symbolic-zero
        # tangents (not differentiated) must stay OUT of the linear slot, so
        # partition the tangent leaves and close the zeros over as constants.
        t_leaves, tdef = tree_flatten(d_ops, is_leaf=_is_szero)
        dead = [_is_szero(t) for t in t_leaves]
        live = [t for t, z in zip(t_leaves, dead) if not z]
        if not live:                       # nothing perturbed: zero tangent
            return y, jax.tree_util.tree_map(
                lambda v: SymbolicZero(jax.core.get_aval(v).at_least_vspace()),
                y)

        def fwd_live(res_, live_ops):
            it = iter(live_ops)
            full = [jnp.zeros(t.aval.shape, t.aval.dtype) if z else next(it)
                    for t, z in zip(t_leaves, dead)]
            return fwd(res_, tree_unflatten(tdef, full))

        def bwd_live(res_, cts):
            full_ct = tree_flatten(transpose(res_, cts))[0]
            return [c for c, z in zip(full_ct, dead) if not z]

        return y, linear_call(fwd_live, bwd_live, res, live)

    return call(operands, residuals)
