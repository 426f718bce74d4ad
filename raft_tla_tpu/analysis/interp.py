"""Abstract interpretation of action-kernel jaxprs.

The action kernels (``models/actions.py``) are pure, statically-shaped
JAX functions, so the model can be analyzed without running the state
space: trace each family once to a jaxpr, then re-evaluate that jaxpr
under an abstract domain instead of on device.  Two domains share one
evaluator:

- :class:`TaintDomain` (effects pass): each value carries ELEMENT-WISE
  dependency masks per ``StateBatch`` field, split into a value-level
  half (``vdeps`` — any element may depend on the masked field
  elements) and a positional half (``pdeps`` — element ``p`` depends on
  the field only through element ``p``), an element-wise "may differ
  from input field F at this position" mask (``origin`` / ``diff``),
  and a partial concrete evaluation (``known``/``vals``) so
  parameter-derived index masks like ``arange(N) == i`` stay exact and
  writes stay confined to the instance's own lanes.  Indexed accesses
  with parameter-concrete indices touch exactly their window; a
  state-dependent index component widens only its own axis.  The
  positional/value split is what makes point updates read only their
  own row: intersecting ``pdeps`` with a write's changed positions
  discards the identity pass-through.
- :class:`IntervalDomain` (bounds pass): each value is an element-wise
  integer interval ``[lo, hi]`` in int64, so packed-lane bounds and
  int32 wrap are decided by monotone transfer functions; parameters and
  literals are degenerate intervals, which makes the evaluation a
  partial evaluation of the kernel (concrete where the model is
  concrete, abstract only where state flows in).

Both domains are *conservative*: a primitive without a precise rule
falls back to "depends on everything that flowed in / full dtype range"
and records the imprecision in ``domain.notes`` so a pass can surface
it instead of silently claiming a proof.

Tracing happens once per action family with abstract scalar parameters;
per-instance results come from re-running the evaluator with that
instance's concrete parameter values.  This matches the executed
semantics exactly: ``build_expand`` vmaps the same kernels over the
same parameter arrays.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, FrozenSet, List, Optional, Tuple

import numpy as np

_I64 = np.int64

# Call-like primitives whose single inner jaxpr is evaluated inline
# ("jit" is what jax 0.9 names the primitive of a nested jax.jit).
_CALL_PRIMS = ("jit", "closed_call", "core_call", "remat", "checkpoint",
               "custom_jvp_call", "custom_vjp_call")


def _dtype_range(dtype) -> Tuple[int, int]:
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return 0, 1
    info = np.iinfo(dtype)
    return int(info.min), int(info.max)


def _axes(eqn_params) -> Tuple[int, ...]:
    return tuple(eqn_params.get("axes", ()))


def _out_aval(eqn, k: int = 0):
    return eqn.outvars[k].aval


# ---------------------------------------------------------------------------
# Shared evaluator


def eval_jaxpr(closed, args: list, domain) -> list:
    """Evaluate a ClosedJaxpr under ``domain``.  ``args`` are domain
    values (or anything ``domain.lift`` accepts) for the invars."""
    from jax.extend.core import Literal
    jaxpr = closed.jaxpr
    env: Dict = {}

    def read(atom):
        if isinstance(atom, Literal):
            return domain.lift(np.asarray(atom.val))
        return env[atom]

    for var, const in zip(jaxpr.constvars, closed.consts):
        env[var] = domain.lift(np.asarray(const))
    assert len(jaxpr.invars) == len(args)
    for var, val in zip(jaxpr.invars, args):
        env[var] = domain.lift(val)

    for eqn in jaxpr.eqns:
        invals = [read(x) for x in eqn.invars]
        name = eqn.primitive.name
        if name in _CALL_PRIMS:
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is not None and len(inner.jaxpr.invars) == len(invals):
                outs = eval_jaxpr(inner, invals, domain)
            else:
                outs = [domain.unknown(v.aval, invals, f"call:{name}")
                        for v in eqn.outvars]
        else:
            outs = domain.apply(name, eqn, invals)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        for var, out in zip(eqn.outvars, outs):
            env[var] = out
    return [read(x) for x in jaxpr.outvars]


# ---------------------------------------------------------------------------
# Interval domain


@dataclasses.dataclass
class Interval:
    """Element-wise integer interval; ``lo``/``hi`` are int64 arrays of
    the value's shape, ``dtype`` the traced dtype (bools are 0/1)."""

    lo: np.ndarray
    hi: np.ndarray
    dtype: np.dtype

    @property
    def shape(self):
        return self.lo.shape

    @property
    def degenerate(self) -> np.ndarray:
        return self.lo == self.hi

    def is_concrete(self) -> bool:
        return bool(np.all(self.lo == self.hi))


def _ival(lo, hi, dtype) -> Interval:
    lo = np.asarray(lo, _I64)
    hi = np.asarray(hi, _I64)
    lo, hi = np.broadcast_arrays(lo, hi)
    return Interval(np.array(lo), np.array(hi), np.dtype(dtype))


def _bool_ival(must, may) -> Interval:
    return _ival(np.asarray(must, _I64), np.asarray(may, _I64), np.bool_)


def _or_upper(ha, hb):
    """Upper bound for x | y (and x ^ y) with x in [0,ha], y in [0,hb]:
    the all-ones value at the wider operand's bit length."""
    m = np.maximum(np.maximum(ha, hb), 0).astype(np.float64)
    bits = np.ceil(np.log2(m + 1)).astype(_I64)
    return (np.int64(1) << bits) - 1


class IntervalDomain:
    """Transfer functions over element-wise intervals.  Conservative:
    every rule's output interval contains every concretely reachable
    value; unhandled primitives widen to the full dtype range and are
    recorded in ``notes``.  Integer overflow of the *traced* dtype
    (e.g. int32 wrap inside a kernel) is recorded in ``wraps`` and the
    value widened to the dtype's range."""

    def __init__(self):
        self.notes: List[str] = []
        self.wraps: List[str] = []

    # -- lifting -------------------------------------------------------
    def lift(self, x):
        if isinstance(x, Interval):
            return x
        arr = np.asarray(x)
        return _ival(arr.astype(_I64), arr.astype(_I64), arr.dtype)

    def unknown(self, aval, invals, why: str) -> Interval:
        if why not in self.notes:
            self.notes.append(why)
        lo, hi = _dtype_range(aval.dtype)
        return _ival(np.full(aval.shape, lo), np.full(aval.shape, hi),
                     aval.dtype)

    # -- helpers -------------------------------------------------------
    def _wrap_check(self, prim: str, out: Interval) -> Interval:
        lo, hi = _dtype_range(out.dtype)
        if bool(np.any(out.lo < lo)) or bool(np.any(out.hi > hi)):
            self.wraps.append(prim)
            return _ival(np.clip(out.lo, lo, hi), np.clip(out.hi, lo, hi),
                         out.dtype)
        return out

    # -- dispatch ------------------------------------------------------
    def apply(self, name: str, eqn, invals):
        rule = getattr(self, "_p_" + name, None)
        if rule is None:
            return [self.unknown(v.aval, invals, f"primitive:{name}")
                    for v in eqn.outvars]
        out = rule(eqn, *invals)
        if isinstance(out, Interval):
            out = self._wrap_check(name, out)
        return out

    # -- arithmetic ----------------------------------------------------
    def _p_add(self, eqn, a, b):
        return _ival(a.lo + b.lo, a.hi + b.hi, _out_aval(eqn).dtype)

    def _p_sub(self, eqn, a, b):
        return _ival(a.lo - b.hi, a.hi - b.lo, _out_aval(eqn).dtype)

    def _p_mul(self, eqn, a, b):
        ps = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return _ival(np.minimum.reduce(ps), np.maximum.reduce(ps),
                     _out_aval(eqn).dtype)

    def _p_neg(self, eqn, a):
        return _ival(-a.hi, -a.lo, _out_aval(eqn).dtype)

    def _p_abs(self, eqn, a):
        lo = np.where((a.lo <= 0) & (a.hi >= 0), 0,
                      np.minimum(np.abs(a.lo), np.abs(a.hi)))
        return _ival(lo, np.maximum(np.abs(a.lo), np.abs(a.hi)),
                     _out_aval(eqn).dtype)

    def _p_max(self, eqn, a, b):
        return _ival(np.maximum(a.lo, b.lo), np.maximum(a.hi, b.hi),
                     _out_aval(eqn).dtype)

    def _p_min(self, eqn, a, b):
        return _ival(np.minimum(a.lo, b.lo), np.minimum(a.hi, b.hi),
                     _out_aval(eqn).dtype)

    def _p_clamp(self, eqn, lo_b, x, hi_b):
        return _ival(np.clip(x.lo, lo_b.lo, hi_b.lo),
                     np.clip(x.hi, lo_b.hi, hi_b.hi),
                     _out_aval(eqn).dtype)

    # -- comparisons ---------------------------------------------------
    def _p_eq(self, eqn, a, b):
        must = a.degenerate & b.degenerate & (a.lo == b.lo)
        may = (a.lo <= b.hi) & (b.lo <= a.hi)
        return _bool_ival(must, may)

    def _p_ne(self, eqn, a, b):
        eq = self._p_eq(eqn, a, b)
        return _bool_ival(1 - eq.hi, 1 - eq.lo)

    def _p_lt(self, eqn, a, b):
        return _bool_ival(a.hi < b.lo, a.lo < b.hi)

    def _p_le(self, eqn, a, b):
        return _bool_ival(a.hi <= b.lo, a.lo <= b.hi)

    def _p_gt(self, eqn, a, b):
        return _bool_ival(a.lo > b.hi, a.hi > b.lo)

    def _p_ge(self, eqn, a, b):
        return _bool_ival(a.lo >= b.hi, a.hi >= b.lo)

    # -- logic / bitwise -----------------------------------------------
    def _p_and(self, eqn, a, b):
        if np.dtype(_out_aval(eqn).dtype) == np.bool_:
            return _bool_ival(np.minimum(a.lo, b.lo), np.minimum(a.hi, b.hi))
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            return _ival(0, np.minimum(a.hi, b.hi), _out_aval(eqn).dtype)
        return self.unknown(_out_aval(eqn), (a, b), "bitwise-and:negative")

    def _p_or(self, eqn, a, b):
        if np.dtype(_out_aval(eqn).dtype) == np.bool_:
            return _bool_ival(np.maximum(a.lo, b.lo), np.maximum(a.hi, b.hi))
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            return _ival(np.maximum(a.lo, b.lo), _or_upper(a.hi, b.hi),
                         _out_aval(eqn).dtype)
        return self.unknown(_out_aval(eqn), (a, b), "bitwise-or:negative")

    def _p_xor(self, eqn, a, b):
        if np.dtype(_out_aval(eqn).dtype) == np.bool_:
            return _bool_ival(np.zeros_like(a.lo), np.ones_like(a.hi))
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            return _ival(0, _or_upper(a.hi, b.hi), _out_aval(eqn).dtype)
        return self.unknown(_out_aval(eqn), (a, b), "bitwise-xor:negative")

    def _p_not(self, eqn, a):
        if np.dtype(_out_aval(eqn).dtype) == np.bool_:
            return _bool_ival(1 - a.hi, 1 - a.lo)
        return _ival(~a.hi, ~a.lo, _out_aval(eqn).dtype)   # monotone dec.

    def _p_shift_left(self, eqn, a, b):
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            sh_lo = np.clip(b.lo, 0, 62)
            sh_hi = np.clip(b.hi, 0, 62)
            return _ival(a.lo << sh_lo, a.hi << sh_hi, _out_aval(eqn).dtype)
        return self.unknown(_out_aval(eqn), (a, b), "shift_left:negative")

    def _p_shift_right_arithmetic(self, eqn, a, b):
        sh_lo = np.clip(b.lo, 0, 62)
        sh_hi = np.clip(b.hi, 0, 62)
        return _ival(np.minimum(a.lo >> sh_lo, a.lo >> sh_hi),
                     np.maximum(a.hi >> sh_lo, a.hi >> sh_hi),
                     _out_aval(eqn).dtype)

    def _p_shift_right_logical(self, eqn, a, b):
        if np.all(a.lo >= 0):
            return self._p_shift_right_arithmetic(eqn, a, b)
        return self.unknown(_out_aval(eqn), (a, b), "shift_right:negative")

    # -- selection -----------------------------------------------------
    def _p_select_n(self, eqn, pred, *cases):
        shape = _out_aval(eqn).shape
        plo = np.broadcast_to(pred.lo, shape)
        phi = np.broadcast_to(pred.hi, shape)
        deg = plo == phi
        los = [np.broadcast_to(c.lo, shape) for c in cases]
        his = [np.broadcast_to(c.hi, shape) for c in cases]
        join_lo = np.minimum.reduce(los)
        join_hi = np.maximum.reduce(his)
        sel_lo = np.select([deg & (plo == k) for k in range(len(cases))],
                           los, join_lo)
        sel_hi = np.select([deg & (plo == k) for k in range(len(cases))],
                           his, join_hi)
        lo = np.where(deg, sel_lo, join_lo)
        hi = np.where(deg, sel_hi, join_hi)
        return _ival(lo, hi, _out_aval(eqn).dtype)

    # -- structure -----------------------------------------------------
    def _p_broadcast_in_dim(self, eqn, a):
        shape = tuple(eqn.params["shape"])
        bdims = tuple(eqn.params["broadcast_dimensions"])
        mid = [1] * len(shape)
        for opd, outd in enumerate(bdims):
            mid[outd] = a.lo.shape[opd]
        lo = np.broadcast_to(a.lo.reshape(mid), shape)
        hi = np.broadcast_to(a.hi.reshape(mid), shape)
        return _ival(lo, hi, _out_aval(eqn).dtype)

    def _p_reshape(self, eqn, a):
        shape = tuple(eqn.params["new_sizes"])
        return _ival(a.lo.reshape(shape), a.hi.reshape(shape),
                     _out_aval(eqn).dtype)

    def _p_squeeze(self, eqn, a):
        shape = _out_aval(eqn).shape
        return _ival(a.lo.reshape(shape), a.hi.reshape(shape),
                     _out_aval(eqn).dtype)

    def _p_expand_dims(self, eqn, a):
        shape = _out_aval(eqn).shape
        return _ival(a.lo.reshape(shape), a.hi.reshape(shape),
                     _out_aval(eqn).dtype)

    def _p_concatenate(self, eqn, *parts):
        d = eqn.params["dimension"]
        return _ival(np.concatenate([p.lo for p in parts], axis=d),
                     np.concatenate([p.hi for p in parts], axis=d),
                     _out_aval(eqn).dtype)

    def _p_slice(self, eqn, a):
        idx = tuple(slice(s, l, st or 1) for s, l, st in zip(
            eqn.params["start_indices"], eqn.params["limit_indices"],
            eqn.params["strides"] or [1] * len(eqn.params["start_indices"])))
        return _ival(a.lo[idx], a.hi[idx], _out_aval(eqn).dtype)

    def _p_transpose(self, eqn, a):
        perm = tuple(eqn.params["permutation"])
        return _ival(np.transpose(a.lo, perm), np.transpose(a.hi, perm),
                     _out_aval(eqn).dtype)

    def _p_rev(self, eqn, a):
        dims = tuple(eqn.params["dimensions"])
        return _ival(np.flip(a.lo, dims), np.flip(a.hi, dims),
                     _out_aval(eqn).dtype)

    def _p_iota(self, eqn):
        shape = tuple(eqn.params["shape"])
        dim = eqn.params["dimension"]
        mid = [1] * len(shape)
        mid[dim] = shape[dim]
        arr = np.broadcast_to(
            np.arange(shape[dim], dtype=_I64).reshape(mid), shape)
        return _ival(arr, arr, _out_aval(eqn).dtype)

    def _p_convert_element_type(self, eqn, a):
        dtype = np.dtype(_out_aval(eqn).dtype)
        if dtype == np.bool_:
            must = (a.lo > 0) | (a.hi < 0)
            may = ~((a.lo == 0) & (a.hi == 0))
            return _bool_ival(must, may)
        out = _ival(a.lo, a.hi, dtype)
        return out          # _wrap_check in apply() handles narrowing

    def _p_stop_gradient(self, eqn, a):
        return a

    def _p_copy(self, eqn, a):
        return a

    # -- reductions ----------------------------------------------------
    def _p_reduce_sum(self, eqn, a):
        ax = _axes(eqn.params)
        return _ival(a.lo.sum(axis=ax), a.hi.sum(axis=ax),
                     _out_aval(eqn).dtype)

    def _p_reduce_max(self, eqn, a):
        ax = _axes(eqn.params)
        return _ival(a.lo.max(axis=ax), a.hi.max(axis=ax),
                     _out_aval(eqn).dtype)

    def _p_reduce_min(self, eqn, a):
        ax = _axes(eqn.params)
        return _ival(a.lo.min(axis=ax), a.hi.min(axis=ax),
                     _out_aval(eqn).dtype)

    def _p_reduce_and(self, eqn, a):
        ax = _axes(eqn.params)
        return _bool_ival(a.lo.min(axis=ax), a.hi.min(axis=ax))

    def _p_reduce_or(self, eqn, a):
        ax = _axes(eqn.params)
        return _bool_ival(a.lo.max(axis=ax), a.hi.max(axis=ax))

    def _p_argmax(self, eqn, a):
        return self._arg_reduce(eqn, a, np.argmax)

    def _p_argmin(self, eqn, a):
        return self._arg_reduce(eqn, a, np.argmin)

    def _arg_reduce(self, eqn, a, fn):
        ax = tuple(eqn.params["axes"])[0]
        if a.is_concrete():
            out = fn(a.lo, axis=ax)
            return _ival(out, out, _out_aval(eqn).dtype)
        return _ival(np.zeros(_out_aval(eqn).shape, _I64),
                     np.full(_out_aval(eqn).shape, a.lo.shape[ax] - 1),
                     _out_aval(eqn).dtype)

    # -- indexed access ------------------------------------------------
    def _p_gather(self, eqn, operand, indices):
        dn = eqn.params["dimension_numbers"]
        slice_sizes = tuple(eqn.params["slice_sizes"])
        out_aval = _out_aval(eqn)
        # Restrict each indexed operand axis to the range the (possibly
        # abstract) start index admits — jax clamps starts into range —
        # then join (min/max) over the indexed axes, keeping window axes
        # positional.  Exact when indices are degenerate scalars and the
        # slice is size-1; conservative join otherwise.
        lo, hi = operand.lo, operand.hi
        idx_lo = indices.lo.reshape(-1, indices.lo.shape[-1]) \
            if indices.lo.ndim else indices.lo.reshape(1, -1)
        idx_hi = indices.hi.reshape(idx_lo.shape)
        n_batches = idx_lo.shape[0]
        exact = n_batches == 1
        for k, ax in enumerate(dn.start_index_map):
            size = slice_sizes[ax]
            dim = operand.lo.shape[ax]
            s_lo = int(np.clip(idx_lo[:, k].min(), 0, max(dim - size, 0)))
            s_hi = int(np.clip(idx_hi[:, k].max(), 0, max(dim - size, 0)))
            sl = [slice(None)] * operand.lo.ndim
            sl[ax] = slice(s_lo, s_hi + size)
            lo, hi = lo[tuple(sl)], hi[tuple(sl)]
            if s_lo != s_hi or not exact:
                # Join over the uncertainty window, collapse to width
                # ``size`` by pooling (sound: every possible slice of
                # width ``size`` is contained in the pooled join).
                lo = np.min(lo, axis=ax, keepdims=True)
                hi = np.max(hi, axis=ax, keepdims=True)
                reps = [1] * lo.ndim
                reps[ax] = size
                lo, hi = np.tile(lo, reps), np.tile(hi, reps)
        for ax in sorted(dn.collapsed_slice_dims, reverse=True):
            lo = np.squeeze(lo, axis=ax)
            hi = np.squeeze(hi, axis=ax)
        try:
            lo = np.broadcast_to(lo.reshape(lo.shape), out_aval.shape)
            hi = np.broadcast_to(hi.reshape(hi.shape), out_aval.shape)
        except ValueError:
            # Batched / reordered gather beyond the simple form: smear.
            lo = np.full(out_aval.shape, operand.lo.min())
            hi = np.full(out_aval.shape, operand.hi.max())
        return _ival(lo, hi, out_aval.dtype)

    def _p_scatter(self, eqn, operand, indices, updates):
        out_aval = _out_aval(eqn)
        dn = eqn.params["dimension_numbers"]
        if indices.is_concrete() and updates.lo.size == 1 \
                and len(dn.scatter_dims_to_operand_dims) == operand.lo.ndim:
            # Single fully-indexed scalar update (the ``.at[k].set(v)``
            # shape the kernels use): exact positional set.
            pos = tuple(int(x) for x in indices.lo.reshape(-1))
            lo, hi = operand.lo.copy(), operand.hi.copy()
            lo[pos] = updates.lo.reshape(())
            hi[pos] = updates.hi.reshape(())
            return _ival(lo, hi, out_aval.dtype)
        lo = np.minimum(operand.lo, updates.lo.min())
        hi = np.maximum(operand.hi, updates.hi.max())
        return _ival(lo, hi, out_aval.dtype)

    def _p_dynamic_slice(self, eqn, operand, *starts):
        sizes = tuple(eqn.params["slice_sizes"])
        lo, hi = operand.lo, operand.hi
        for ax, (st, size) in enumerate(zip(starts, sizes)):
            dim = operand.lo.shape[ax]
            s_lo = int(np.clip(st.lo, 0, max(dim - size, 0)))
            s_hi = int(np.clip(st.hi, 0, max(dim - size, 0)))
            sl = [slice(None)] * lo.ndim
            sl[ax] = slice(s_lo, s_hi + size)
            lo, hi = lo[tuple(sl)], hi[tuple(sl)]
            if s_lo != s_hi:
                lo = np.tile(np.min(lo, axis=ax, keepdims=True),
                             [size if i == ax else 1
                              for i in range(lo.ndim)])
                hi = np.tile(np.max(hi, axis=ax, keepdims=True),
                             [size if i == ax else 1
                              for i in range(hi.ndim)])
        return _ival(lo, hi, _out_aval(eqn).dtype)

    def _p_dynamic_update_slice(self, eqn, operand, update, *starts):
        lo, hi = operand.lo.copy(), operand.hi.copy()
        if all(s.is_concrete() for s in starts):
            pos = []
            for ax, st in enumerate(starts):
                dim = operand.lo.shape[ax]
                size = update.lo.shape[ax]
                pos.append(slice(
                    int(np.clip(st.lo, 0, dim - size)),
                    int(np.clip(st.lo, 0, dim - size)) + size))
            lo[tuple(pos)] = update.lo
            hi[tuple(pos)] = update.hi
            return _ival(lo, hi, _out_aval(eqn).dtype)
        # Unknown placement: any element may be original or updated.
        return _ival(np.minimum(lo, update.lo.min()),
                     np.maximum(hi, update.hi.max()),
                     _out_aval(eqn).dtype)


# ---------------------------------------------------------------------------
# Taint domain


_EMPTY: FrozenSet[str] = frozenset()

#: Element-wise dependency footprint: field name -> bool mask over THAT
#: FIELD's shape.  Masks are treated as immutable (never updated in
#: place), so dictionaries may share arrays freely.
Deps = Dict[str, np.ndarray]


def _dunion(*dicts: Deps) -> Deps:
    """Key-wise OR of dependency footprints."""
    out: Deps = {}
    for d in dicts:
        for f, m in d.items():
            prev = out.get(f)
            out[f] = m if prev is None else (prev | m)
    return out


def read_mask(t: "Taint") -> Deps:
    """The value's full element-wise read set (value-level join of the
    positional and value-level halves)."""
    return _dunion(t.vdeps, t.pdeps)


@dataclasses.dataclass
class Taint:
    """Element-wise dependency/identity abstraction.

    Dependencies are tracked per input-field ELEMENT, split in two:

    - ``vdeps[f]`` — value-level: ANY element of this value may depend
      on the masked elements of field ``f``.
    - ``pdeps[f]`` — positional: element ``p`` of this value may depend
      on field ``f`` only through ``f[p]`` (the mask marks which
      positions).  Only meaningful while the value's shape equals the
      field's shape; every shape-changing primitive graduates the
      positional half into ``vdeps`` (conservative).  This is what lets
      a point update like ``where(arange(N) == i, term + 1, term)``
      read only ``term[i]`` instead of the whole field: the changed
      positions (``diff``) intersect the positional mask.
    - ``origin``/``diff``: if ``origin`` is field F, elements where
      ``diff`` is False are *provably equal to input field F at the
      same position* — the write-set extractor reads successor fields'
      ``diff`` masks directly.
    - ``known``/``vals``: partial concrete evaluation (True where the
      value is a compile-time constant for this instance's parameters);
      keeps index masks like ``arange(N) == i`` exact so writes stay
      confined to the instance's own rows.
    """

    vdeps: Deps
    pdeps: Deps
    origin: Optional[str]
    diff: np.ndarray          # bool, value shape
    known: np.ndarray         # bool, value shape
    vals: np.ndarray          # int64, valid where known
    dtype: np.dtype

    @property
    def shape(self):
        return self.diff.shape

    @property
    def deps(self) -> FrozenSet[str]:
        """Field-level view of the read set (compat / summaries)."""
        return frozenset(set(self.vdeps) | set(self.pdeps))


def _taint(vdeps, pdeps, origin, diff, known, vals, dtype) -> Taint:
    diff = np.asarray(diff, bool)
    known = np.asarray(known, bool)
    vals = np.asarray(vals, _I64)
    diff, known, vals = np.broadcast_arrays(diff, known, vals)
    if known.all():
        vdeps, pdeps, origin = {}, {}, None
    vdeps = {f: m for f, m in vdeps.items() if m.any()}
    pdeps = {f: m for f, m in pdeps.items() if m.any()}
    return Taint(vdeps, pdeps, origin, np.array(diff), np.array(known),
                 np.array(vals), np.dtype(dtype))


def _opaque(vdeps, shape, dtype) -> Taint:
    """Depends (value-level) on ``vdeps``, nothing known element-wise."""
    z = np.zeros(shape, bool)
    return _taint(vdeps, {}, None, ~z, z, np.zeros(shape, _I64), dtype)


class TaintDomain:
    """Transfer functions for dependency/identity extraction.  The only
    precision that matters downstream: (1) ``vdeps``/``pdeps`` never
    lose a real dependency, (2) ``diff`` is True wherever the element
    can differ from its origin field, (3) parameter-concrete index
    arithmetic stays ``known`` so per-instance write masks are
    lane-accurate, (4) the positional half is claimed only through
    shape-preserving element-wise flows, so intersecting it with a
    write's ``diff`` mask yields a sound slot-precise read set."""

    #: numpy implementations for the concrete (known) path.
    _NP = {
        "add": np.add, "sub": np.subtract, "mul": np.multiply,
        "max": np.maximum, "min": np.minimum, "neg": np.negative,
        "abs": np.abs,
        "eq": np.equal, "ne": np.not_equal, "lt": np.less,
        "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal,
        "and": np.logical_and, "or": np.logical_or,
        "xor": np.logical_xor, "not": np.logical_not,
        "shift_left": np.left_shift,
        "shift_right_arithmetic": np.right_shift,
        "shift_right_logical": np.right_shift,
    }

    def __init__(self):
        self.notes: List[str] = []

    def lift(self, x):
        if isinstance(x, Taint):
            return x
        arr = np.asarray(x)
        return _taint({}, {}, None, np.ones(arr.shape, bool),
                      np.ones(arr.shape, bool), arr.astype(_I64), arr.dtype)

    def unknown(self, aval, invals, why: str) -> Taint:
        if why not in self.notes:
            self.notes.append(why)
        vdeps = _dunion(*(read_mask(v) for v in invals)) if invals else {}
        return _opaque(vdeps, aval.shape, aval.dtype)

    def apply(self, name: str, eqn, invals):
        if name in self._NP and len(invals) <= 2:
            return self._elementwise(eqn, name, invals)
        rule = getattr(self, "_p_" + name, None)
        if rule is None:
            return [self.unknown(v.aval, invals, f"primitive:{name}")
                    for v in eqn.outvars]
        return rule(eqn, *invals)

    def _join_deps(self, shape, invals) -> Tuple[Deps, Deps]:
        """(vdeps, pdeps) of an element-wise combination: an input of
        the output's shape keeps its positional half; a broadcast input
        graduates it to value-level (element p of the output no longer
        maps to element p of the field)."""
        vd: List[Deps] = []
        pd: List[Deps] = []
        for v in invals:
            if v.shape == shape:
                vd.append(v.vdeps)
                pd.append(v.pdeps)
            else:
                vd.append(read_mask(v))
        return _dunion(*vd), _dunion(*pd)

    # -- elementwise with partial evaluation ---------------------------
    def _elementwise(self, eqn, name, invals):
        aval = _out_aval(eqn)
        shape = aval.shape
        knowns = [np.broadcast_to(v.known, shape) for v in invals]
        vals = [np.broadcast_to(v.vals, shape) for v in invals]
        known = np.logical_and.reduce(knowns)
        # Absorbing elements make the result known even when the other
        # operand is state-dependent: False & x, True | x, 0 * x.
        if len(invals) == 2:
            a_k, b_k = knowns
            a_v, b_v = vals
            if name == "and":
                known = known | (a_k & (a_v == 0)) | (b_k & (b_v == 0))
            elif name == "or":
                known = known | (a_k & (a_v != 0)) | (b_k & (b_v != 0))
            elif name == "mul":
                known = known | (a_k & (a_v == 0)) | (b_k & (b_v == 0))
        with np.errstate(over="ignore"):
            out_vals = self._NP[name](*vals) if vals else vals
        out_vals = np.asarray(out_vals)
        if np.dtype(aval.dtype) == np.bool_:
            out_vals = out_vals.astype(bool)
        vdeps, pdeps = self._join_deps(shape, invals)
        return _taint(vdeps, pdeps, None, np.ones(shape, bool), known,
                      out_vals.astype(_I64), aval.dtype)

    # -- selection -----------------------------------------------------
    def _p_select_n(self, eqn, pred, *cases):
        aval = _out_aval(eqn)
        shape = aval.shape
        pk = np.broadcast_to(pred.known, shape)
        pv = np.broadcast_to(pred.vals, shape)
        case_known = [np.broadcast_to(c.known, shape) for c in cases]
        case_vals = [np.broadcast_to(c.vals, shape) for c in cases]
        known = np.zeros(shape, bool)
        vals = np.zeros(shape, _I64)
        used = [False] * len(cases)
        for k in range(len(cases)):
            sel = pk & (pv == k)
            known |= sel & case_known[k]
            vals = np.where(sel, case_vals[k], vals)
            used[k] = bool(np.any(sel)) or not pk.all()
        # deps: predicate plus every case that can be selected somewhere.
        vdeps, pdeps = self._join_deps(
            shape, [pred] + [c for k, c in enumerate(cases) if used[k]])
        # origin/diff: keep identity only when exactly one input field
        # appears as a case origin.
        origins = {c.origin for c in cases if c.origin is not None}
        if len(origins) == 1:
            origin = next(iter(origins))
            diffs = [np.broadcast_to(c.diff, shape)
                     if c.origin == origin else np.ones(shape, bool)
                     for c in cases]
            chosen = np.select([pk & (pv == k) for k in range(len(cases))],
                               diffs, np.logical_or.reduce(diffs))
            diff = np.where(pk, chosen, np.logical_or.reduce(diffs))
        else:
            origin, diff = None, np.ones(shape, bool)
        return _taint(vdeps, pdeps, origin, diff, known, vals, aval.dtype)

    # -- structure -----------------------------------------------------
    def _p_broadcast_in_dim(self, eqn, a):
        aval = _out_aval(eqn)
        shape = tuple(eqn.params["shape"])
        bdims = tuple(eqn.params["broadcast_dimensions"])
        mid = [1] * len(shape)
        for opd, outd in enumerate(bdims):
            mid[outd] = a.shape[opd]
        known = np.broadcast_to(a.known.reshape(mid), shape)
        vals = np.broadcast_to(a.vals.reshape(mid), shape)
        same = shape == a.shape and bdims == tuple(range(len(shape)))
        origin = a.origin if same else None
        diff = np.broadcast_to(a.diff.reshape(mid), shape) if same \
            else np.ones(shape, bool)
        vdeps = a.vdeps if same else read_mask(a)
        pdeps = a.pdeps if same else {}
        return _taint(vdeps, pdeps, origin, diff, known, vals, aval.dtype)

    def _p_reshape(self, eqn, a):
        shape = tuple(eqn.params["new_sizes"])
        same = shape == a.shape
        return _taint(a.vdeps if same else read_mask(a),
                      a.pdeps if same else {},
                      a.origin, a.diff.reshape(shape),
                      a.known.reshape(shape), a.vals.reshape(shape),
                      _out_aval(eqn).dtype)

    def _p_squeeze(self, eqn, a):
        shape = _out_aval(eqn).shape
        return _taint(read_mask(a), {}, None, np.ones(shape, bool),
                      a.known.reshape(shape), a.vals.reshape(shape),
                      _out_aval(eqn).dtype)

    def _p_expand_dims(self, eqn, a):
        shape = _out_aval(eqn).shape
        return _taint(read_mask(a), {}, None, np.ones(shape, bool),
                      a.known.reshape(shape), a.vals.reshape(shape),
                      _out_aval(eqn).dtype)

    def _p_concatenate(self, eqn, *parts):
        d = eqn.params["dimension"]
        vdeps = _dunion(*(read_mask(p) for p in parts))
        return _taint(vdeps, {}, None,
                      np.ones(_out_aval(eqn).shape, bool),
                      np.concatenate([p.known for p in parts], axis=d),
                      np.concatenate([p.vals for p in parts], axis=d),
                      _out_aval(eqn).dtype)

    def _p_slice(self, eqn, a):
        idx = tuple(slice(s, l, st or 1) for s, l, st in zip(
            eqn.params["start_indices"], eqn.params["limit_indices"],
            eqn.params["strides"] or [1] * len(eqn.params["start_indices"])))
        # The untouched positional region is not read through this value.
        region = np.zeros(a.shape, bool)
        region[idx] = True
        vdeps = _dunion(a.vdeps, {f: m & region for f, m in a.pdeps.items()})
        return _taint(vdeps, {}, None,
                      np.ones(_out_aval(eqn).shape, bool),
                      a.known[idx], a.vals[idx], _out_aval(eqn).dtype)

    def _p_iota(self, eqn):
        shape = tuple(eqn.params["shape"])
        dim = eqn.params["dimension"]
        mid = [1] * len(shape)
        mid[dim] = shape[dim]
        arr = np.broadcast_to(
            np.arange(shape[dim], dtype=_I64).reshape(mid), shape)
        return self.lift(arr.astype(_out_aval(eqn).dtype))

    def _p_convert_element_type(self, eqn, a):
        dtype = np.dtype(_out_aval(eqn).dtype)
        vals = a.vals.astype(bool).astype(_I64) if dtype == np.bool_ \
            else a.vals
        return _taint(a.vdeps, a.pdeps, a.origin, a.diff, a.known, vals,
                      dtype)

    def _p_stop_gradient(self, eqn, a):
        return a

    def _p_copy(self, eqn, a):
        return a

    def _p_transpose(self, eqn, a):
        perm = tuple(eqn.params["permutation"])
        return _taint(read_mask(a), {}, None,
                      np.ones(_out_aval(eqn).shape, bool),
                      np.transpose(a.known, perm),
                      np.transpose(a.vals, perm), _out_aval(eqn).dtype)

    def _p_rev(self, eqn, a):
        dims = tuple(eqn.params["dimensions"])
        return _taint(read_mask(a), {}, None,
                      np.ones(_out_aval(eqn).shape, bool),
                      np.flip(a.known, dims), np.flip(a.vals, dims),
                      _out_aval(eqn).dtype)

    # -- reductions (concrete when input fully known) ------------------
    _REDUCE = {"reduce_sum": np.sum, "reduce_max": np.max,
               "reduce_min": np.min, "reduce_prod": np.prod,
               "reduce_and": np.all, "reduce_or": np.any}

    def _reduce(self, eqn, a, name):
        aval = _out_aval(eqn)
        if a.known.all():
            out = np.asarray(self._REDUCE[name](a.vals,
                                                axis=_axes(eqn.params)))
            return self.lift(out.astype(aval.dtype))
        return _opaque(read_mask(a), aval.shape, aval.dtype)

    def _p_reduce_sum(self, eqn, a):
        return self._reduce(eqn, a, "reduce_sum")

    def _p_reduce_max(self, eqn, a):
        return self._reduce(eqn, a, "reduce_max")

    def _p_reduce_min(self, eqn, a):
        return self._reduce(eqn, a, "reduce_min")

    def _p_reduce_prod(self, eqn, a):
        return self._reduce(eqn, a, "reduce_prod")

    def _p_reduce_and(self, eqn, a):
        return self._reduce(eqn, a, "reduce_and")

    def _p_reduce_or(self, eqn, a):
        return self._reduce(eqn, a, "reduce_or")

    def _p_argmax(self, eqn, a):
        return self._arg_reduce(eqn, a, np.argmax)

    def _p_argmin(self, eqn, a):
        return self._arg_reduce(eqn, a, np.argmin)

    def _arg_reduce(self, eqn, a, fn):
        aval = _out_aval(eqn)
        if a.known.all():
            out = np.asarray(fn(a.vals, axis=tuple(eqn.params["axes"])[0]))
            return self.lift(out.astype(aval.dtype))
        return _opaque(read_mask(a), aval.shape, aval.dtype)

    def _p_clamp(self, eqn, lo_b, x, hi_b):
        aval = _out_aval(eqn)
        known = lo_b.known & x.known & hi_b.known
        known = np.broadcast_to(known, aval.shape)
        vals = np.clip(np.broadcast_to(x.vals, aval.shape),
                       np.broadcast_to(lo_b.vals, aval.shape),
                       np.broadcast_to(hi_b.vals, aval.shape))
        vdeps, pdeps = self._join_deps(aval.shape, (lo_b, x, hi_b))
        return _taint(vdeps, pdeps, None, np.ones(aval.shape, bool), known,
                      vals, aval.dtype)

    # -- indexed access (element-precise where the indices are) --------
    #
    # These are the rules that turn whole-field footprints into
    # slot/column-granular ones: an access whose index components are
    # parameter-concrete touches exactly the indexed window; a
    # state-dependent component widens ONLY its own axis to the full
    # dimension.  The widening stays per-element — the touched region is
    # intersected with the operand's positional mask, so e.g.
    # ``st.msg[s]`` with a concrete slot parameter reads row ``s`` only,
    # while ``st.term[mdest]`` with a message-dependent index reads the
    # whole ``term`` field (genuine, not an analyzer artifact).

    @staticmethod
    def _index_region(operand_shape, indexed_axes, slice_sizes,
                      idx_known, idx_vals) -> np.ndarray:
        """Bool mask over the operand of positions the access may touch.
        ``indexed_axes`` maps index-vector component -> operand axis;
        ``idx_known``/``idx_vals`` are [B, k] (B index rows)."""
        comp = {ax: c for c, ax in enumerate(indexed_axes)}
        axis_masks = []
        for ax, dim in enumerate(operand_shape):
            size = slice_sizes[ax]
            m = np.zeros(dim, bool)
            c = comp.get(ax)
            if c is None:
                m[:size] = True
            elif bool(idx_known[:, c].all()):
                for s in np.unique(np.clip(idx_vals[:, c], 0,
                                           max(dim - size, 0))):
                    m[int(s):int(s) + size] = True
            else:
                m[:] = True
            axis_masks.append(m)
        region = axis_masks[0]
        for m in axis_masks[1:]:
            region = region[..., None] & m
        return region

    @staticmethod
    def _flat_indices(indices) -> Tuple[np.ndarray, np.ndarray]:
        if indices.vals.ndim:
            k = indices.vals.shape[-1]
            return (indices.known.reshape(-1, k),
                    indices.vals.reshape(-1, k))
        return indices.known.reshape(1, 1), indices.vals.reshape(1, 1)

    def _read_through(self, operand, region) -> Deps:
        """Element-wise read set of an access touching ``region`` of
        ``operand``: the positional half is restricted to the touched
        positions, the value-level half cannot be."""
        return _dunion(operand.vdeps,
                       {f: m & region for f, m in operand.pdeps.items()})

    @staticmethod
    def _bind_concrete(eqn, *arrays):
        """Evaluate the eqn's primitive eagerly on concrete numpy
        arrays (used to push partially-``known`` values through indexed
        access: the gather of a known mask is the output's known
        mask)."""
        import jax.numpy as jnp
        out = eqn.primitive.bind(*(jnp.asarray(a) for a in arrays),
                                 **eqn.params)
        return np.asarray(out)

    def _p_gather(self, eqn, operand, indices):
        aval = _out_aval(eqn)
        dn = eqn.params["dimension_numbers"]
        ik, iv = self._flat_indices(indices)
        region = self._index_region(
            operand.shape, tuple(dn.start_index_map),
            tuple(eqn.params["slice_sizes"]), ik, iv)
        vdeps = _dunion(read_mask(indices),
                        self._read_through(operand, region))
        if bool(indices.known.all()) and bool(operand.known.any()):
            known = self._bind_concrete(eqn, operand.known, indices.vals)
            vals = self._bind_concrete(eqn, operand.vals, indices.vals)
            return _taint(vdeps, {}, None, np.ones(aval.shape, bool),
                          known, vals, aval.dtype)
        return _opaque(vdeps, aval.shape, aval.dtype)

    def _p_dynamic_slice(self, eqn, operand, *starts):
        aval = _out_aval(eqn)
        ik = np.array([[bool(s.known.all()) for s in starts]])
        iv = np.array([[int(s.vals.reshape(-1)[0]) for s in starts]],
                      _I64)
        region = self._index_region(
            operand.shape, tuple(range(operand.vals.ndim)),
            tuple(eqn.params["slice_sizes"]), ik, iv)
        vdeps = _dunion(self._read_through(operand, region),
                        *(read_mask(s) for s in starts))
        if bool(ik.all()) and bool(operand.known.any()):
            svals = [s.vals.reshape(()) for s in starts]
            known = self._bind_concrete(eqn, operand.known, *svals)
            vals = self._bind_concrete(eqn, operand.vals, *svals)
            return _taint(vdeps, {}, None, np.ones(aval.shape, bool),
                          known, vals, aval.dtype)
        return _opaque(vdeps, aval.shape, aval.dtype)

    def _p_dynamic_update_slice(self, eqn, operand, update, *starts):
        aval = _out_aval(eqn)
        exact = all(bool(s.known.all()) for s in starts)
        if exact:
            pos = []
            for ax, st in enumerate(starts):
                dim = operand.shape[ax]
                size = update.shape[ax]
                p = int(np.clip(int(st.vals.reshape(-1)[0]), 0,
                                dim - size))
                pos.append(slice(p, p + size))
            region = np.zeros(operand.shape, bool)
            region[tuple(pos)] = True
            known = operand.known & ~region
            vals = operand.vals.copy()
            known = known.copy()
            known[tuple(pos)] = update.known
            vals[tuple(pos)] = update.vals
        else:
            region = np.ones(operand.shape, bool)
            known = np.zeros(operand.shape, bool)
            vals = np.zeros(operand.shape, _I64)
        vdeps = _dunion(operand.vdeps, read_mask(update),
                        *(read_mask(s) for s in starts))
        # Outside the (possibly unknown) window the operand flows
        # through positionally; inside it only where the window is
        # exact does the operand element stop mattering.
        pdeps = {f: (m & ~region if exact else m)
                 for f, m in operand.pdeps.items()}
        diff = operand.diff | region
        return _taint(vdeps, pdeps, operand.origin, diff, known, vals,
                      aval.dtype)

    def _p_scatter(self, eqn, operand, indices, updates):
        aval = _out_aval(eqn)
        dn = eqn.params["dimension_numbers"]
        ik, iv = self._flat_indices(indices)
        full = len(dn.scatter_dims_to_operand_dims) == operand.vals.ndim
        # "Exact" requires concrete IN-BOUNDS unique positions: an
        # out-of-bounds update is dropped by XLA (mode-dependent), so a
        # clipped position would both record a wrong known value and
        # unsoundly clear the positional dep of the untouched element.
        exact = full and bool(ik.all()) \
            and updates.vals.size == ik.shape[0]
        if exact:
            pos = [tuple(int(iv[r, c]) for c in range(iv.shape[1]))
                   for r in range(iv.shape[0])]
            exact = len(set(pos)) == len(pos) and all(
                0 <= p[c] < operand.shape[c]
                for p in pos for c in range(len(p)))
        if exact:
            region = np.zeros(operand.shape, bool)
            for p in pos:
                region[p] = True
            # Concrete semantics via the primitive itself — the known
            # mask and values are scattered exactly the way XLA would.
            known = self._bind_concrete(eqn, operand.known, indices.vals,
                                        updates.known)
            vals = self._bind_concrete(eqn, operand.vals, indices.vals,
                                       updates.vals)
        else:
            region = self._index_region(
                operand.shape, tuple(dn.scatter_dims_to_operand_dims),
                tuple(1 if full else d for d in operand.shape), ik, iv) \
                if full else np.ones(operand.shape, bool)
            known = operand.known & ~region
            vals = operand.vals
        vdeps = _dunion(operand.vdeps, read_mask(indices),
                        read_mask(updates))
        pdeps = {f: m & ~region for f, m in operand.pdeps.items()} \
            if exact else dict(operand.pdeps)
        diff = operand.diff | region
        return _taint(vdeps, pdeps, operand.origin, diff, known, vals,
                      aval.dtype)


# ---------------------------------------------------------------------------
# Tracing


def trace_family(kernel, dims, n_params: int):
    """Trace one action-family kernel to a ClosedJaxpr with abstract
    state fields and abstract scalar parameters.  Invars are the 13
    ``StateBatch`` fields (lane_map.FIELDS order) followed by the
    parameters; outvars are ``(enabled, overflow, *successor fields)``.
    Traced once per family — per-instance analysis re-evaluates the same
    jaxpr under a domain with that instance's concrete parameters, which
    matches ``build_expand``'s vmap over the same parameter arrays."""
    import jax
    import jax.numpy as jnp

    from ..models.schema import StateBatch
    from . import lane_map

    shapes = lane_map.field_shapes(dims)

    def flat(*args):
        st = StateBatch(*args[:len(lane_map.FIELDS)])
        en, ovf, succ = kernel(st, *args[len(lane_map.FIELDS):])
        return (en, ovf) + tuple(succ)

    in_avals = [jax.ShapeDtypeStruct(shapes[f], jnp.int32)
                for f in lane_map.FIELDS]
    in_avals += [jax.ShapeDtypeStruct((), jnp.int32)] * n_params
    return jax.make_jaxpr(flat)(*in_avals)


@functools.lru_cache(maxsize=8)
def traced_kernels(dims):
    """``build_kernels(dims)`` with each family already traced:
    ``((name, closed_jaxpr, params), ...)`` in ``dims.family_names``
    order.  Memoized on ``dims`` (a frozen dataclass) because every
    pass re-derives the same jaxprs — ``build_kernels`` returns fresh
    closures each call, so jax's own trace cache never hits across
    passes; without this, an ``analyze`` run traces the full kernel set
    once per pass instead of once per model."""
    from ..models.actions import build_kernels
    return tuple((name, trace_family(kern, dims, len(params)), params)
                 for name, kern, params in build_kernels(dims))
