"""Rule-based closed-jaxpr analyzer guarding the mask-native invariants.

The walker (`lint_jaxpr`) descends into ``scan``/``while``/``cond``/
``custom_vjp``/``pjit`` sub-jaxprs; the ``pallas_call`` equation is
never descended into — its innards live in VMEM, which is the entire
point being proved.  Call-like equations that merely forward inner
results are shown to rules as *call sites* (`check_call`) and recursed
into instead of being treated as defining equations, so a leaf-rule hit
is a real compute/materialization step.

Shipped rules:

  * `weight_f32_temporaries` — weight-shaped f32 defs outside the
    kernel boundary (the original ``count_weight_f32_defs_jaxpr`` from
    ``benchmarks/kernels_bench.py``, promoted here; the bench and the
    tier-1 twin are thin callers of this one traversal);
  * `mask_materialization` — weight-shaped bool/uint8/int8 defs: a
    mask made it into HBM;
  * `DtypePromotionRule` — any f64 value (numerics are f32/bf16 end to
    end), plus weight-shaped bf16→f32 ``convert_element_type`` (an
    upcast that doubles a weight-sized tensor's HBM footprint);
  * `DonationAliasRule` — a donated pjit operand read again after the
    call that consumed its buffer.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis.report import Finding

# pure view/layout primitives: no new value is computed, XLA aliases
# them to the operand (lax.scan feeds per-layer score slices to the
# kernels through squeeze) — not weight-sized HBM traffic
_VIEW_PRIMS = frozenset({"squeeze", "reshape"})


def _subjaxprs(params):
    found = []
    stack = list(params.values())
    while stack:
        p = stack.pop()
        if isinstance(p, jcore.ClosedJaxpr):
            found.append(p.jaxpr)
        elif isinstance(p, jcore.Jaxpr):
            found.append(p)
        elif isinstance(p, (tuple, list)):
            stack.extend(p)
    return found


class JaxprRule:
    """One invariant over the equations of a (closed) jaxpr.

    `check_eqn` sees every defining equation outside pallas_call;
    `check_call` sees every call-like equation (one that carries
    sub-jaxprs) together with its enclosing jaxpr and position, before
    the walker recurses into it.  Both return iterables of `Finding`s.
    """

    name = "abstract"

    def check_eqn(self, eqn):
        return ()

    def check_call(self, eqn, enclosing, idx):
        return ()


def lint_jaxpr(jaxpr, rules: Sequence[JaxprRule]) -> list:
    """Run `rules` over every equation of `jaxpr`, recursively."""
    findings: list = []

    def walk(jx):
        for idx, eqn in enumerate(jx.eqns):
            if eqn.primitive.name == "pallas_call":
                continue
            inner = _subjaxprs(eqn.params)
            if inner:
                for r in rules:
                    findings.extend(r.check_call(eqn, jx, idx))
                for j in inner:
                    walk(j)
                continue  # call wrapper: only inner eqns define values
            for r in rules:
                findings.extend(r.check_eqn(eqn))

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return findings


class ShapedDefRule(JaxprRule):
    """Flag leaf equations defining a value of `shape` with a dtype in
    `dtypes`, excluding `exempt_prims` (view-only by default)."""

    def __init__(self, name, shape, dtypes, exempt_prims=_VIEW_PRIMS):
        self.name = name
        self._shape = tuple(shape)
        self._dtypes = frozenset(jnp.dtype(d) for d in dtypes)
        self._exempt = frozenset(exempt_prims)

    def check_eqn(self, eqn):
        if eqn.primitive.name in self._exempt:
            return ()
        out = []
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if (aval is not None and tuple(aval.shape) == self._shape
                    and aval.dtype in self._dtypes):
                out.append(Finding(
                    self.name, eqn.primitive.name,
                    f"defines {aval.dtype}{list(aval.shape)}"))
        return out


def weight_f32_temporaries(weight_shape, exempt_prims=_VIEW_PRIMS):
    """Weight-shaped f32 values computed outside pallas_call — the
    invariant behind the fused path's zero-HBM-weight-traffic claim."""
    return ShapedDefRule("weight-f32-temporary", weight_shape,
                         (jnp.float32,), exempt_prims)


def mask_materialization(weight_shape):
    """Weight-shaped bool/uint8/int8 defs — a materialized mask.  On
    the fused path masks exist only as per-tile VMEM values inside the
    kernels, never as an HBM tensor."""
    return ShapedDefRule("mask-materialization", weight_shape,
                         (jnp.bool_, jnp.uint8, jnp.int8))


class DtypePromotionRule(JaxprRule):
    """Unexpected dtype promotions on masked paths: any f64 value
    anywhere (the repo's numerics are f32/bf16 end to end), and
    weight-shaped bf16→f32 `convert_element_type` outside pallas_call
    (the materialized reference's ``w.astype(f32)`` — doubles the
    weight tensor's HBM footprint).  With no `weight_shapes` given only
    the f64 check applies."""

    name = "dtype-promotion"

    def __init__(self, weight_shapes=()):
        self._shapes = frozenset(tuple(s) for s in weight_shapes)

    def check_eqn(self, eqn):
        out = []
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if aval.dtype == jnp.dtype("float64"):
                out.append(Finding(
                    self.name, eqn.primitive.name,
                    f"f64 value of shape {list(aval.shape)}"))
                continue
            if (eqn.primitive.name == "convert_element_type"
                    and tuple(aval.shape) in self._shapes
                    and aval.dtype == jnp.dtype(jnp.float32)):
                src = getattr(eqn.invars[0], "aval", None)
                if src is not None and src.dtype == jnp.dtype(jnp.bfloat16):
                    out.append(Finding(
                        self.name, eqn.primitive.name,
                        f"weight-shaped bf16->f32 upcast "
                        f"{list(aval.shape)}"))
        return out


class DonationAliasRule(JaxprRule):
    """A donated pjit operand must not be read again: donation hands
    the buffer to the callee, so a later use aliases freed memory (XLA
    silently copies instead, defeating the donation)."""

    name = "donation-alias"

    def check_call(self, eqn, enclosing, idx):
        donated = eqn.params.get("donated_invars")
        if not donated or not any(donated):
            return ()
        later_uses = set()
        for later in enclosing.eqns[idx + 1:]:
            for v in later.invars:
                if isinstance(v, jcore.Var):
                    later_uses.add(v)
        for v in enclosing.outvars:
            if isinstance(v, jcore.Var):
                later_uses.add(v)
        out = []
        for flag, v in zip(donated, eqn.invars):
            if flag and isinstance(v, jcore.Var) and v in later_uses:
                aval = getattr(v, "aval", None)
                out.append(Finding(
                    self.name, eqn.primitive.name,
                    f"donated operand ({aval}) is read after the call"))
        return out


def count_weight_f32_defs_jaxpr(jaxpr, weight_shape) -> int:
    """Number of equations (recursively) in a jaxpr defining an f32
    value of `weight_shape` outside any `pallas_call` — the original
    bench counter, now one rule of the shared walker (per-outvar
    counting, `_VIEW_PRIMS` skipped, call wrappers recursed into but
    never counted: semantics unchanged, so BENCH_kernels.json counts
    stay comparable)."""
    return len(lint_jaxpr(jaxpr, [weight_f32_temporaries(weight_shape)]))


def count_weight_f32_defs(fn, args, weight_shape) -> int:
    """`count_weight_f32_defs_jaxpr` of `jax.make_jaxpr(fn)(*args)`."""
    return count_weight_f32_defs_jaxpr(jax.make_jaxpr(fn)(*args),
                                       weight_shape)
