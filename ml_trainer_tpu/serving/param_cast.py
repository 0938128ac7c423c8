"""The parameter tree a serving engine's programs are handed.

A flax module with ``dtype=bfloat16`` and float32 parameters casts each
weight where it is used (``nn.Dense`` runs ``promote_dtype`` on its kernel
and bias before the product), so a decode step reads every such leaf as
float32 and rounds it to bfloat16 inside the program: twice the bytes the
product consumes, on every step.  Casting such a leaf once, ahead of the
programs, changes no value they compute: they apply the same rounding to
the same numbers, and a cast to the dtype a leaf already has is no
operation at all.

The rule reads what the programs do, not names: a leaf is held in dtype
``T`` when every use of it in the traced programs is a cast to ``T``
(``convert_element_type``), followed into the calls that take it as an
operand.  Any other use, a cast to two dtypes, or no use keeps the leaf
as handed, and so does a cast to a WIDER dtype (a bfloat16 leaf that a
program reads as float32): held ahead, it would only make every step read
more bytes.  Nothing runs on the device to find this: the programs are
traced on abstract shapes.
"""

from __future__ import annotations

import collections
import functools
from typing import List, Optional

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Var
from jax.extend.core.primitives import (
    closed_call_p,
    convert_element_type_p,
    custom_jvp_call_p,
    custom_vjp_call_p,
    jit_p,
    remat_p,
    scan_p,
)

# Calls whose operands are their callee's inputs, position for position,
# and read once: a use inside the callee is a use of the operand.  Any
# other primitive that takes a leaf (a loop's carry, a branch, a kernel)
# counts as a use that is no cast.
_CALLS = (jit_p, closed_call_p, remat_p, custom_jvp_call_p,
          custom_vjp_call_p)


def _callee(eqn, i: int):
    """The jaxpr and input through which operand ``i`` of ``eqn`` is read,
    or None where the operand is read some other way.  A scan's leading
    ``num_consts`` operands are read unchanged by every iteration; its
    carries and the slices of its inputs are not the operand."""
    if eqn.primitive is scan_p:
        if i >= eqn.params["num_consts"]:
            return None
        inner = eqn.params["jaxpr"].jaxpr
        return inner, inner.invars[i]
    if eqn.primitive not in _CALLS:
        return None
    for key in ("jaxpr", "call_jaxpr"):
        inner = eqn.params.get(key)
        if isinstance(inner, ClosedJaxpr):
            inner = inner.jaxpr
        if isinstance(inner, Jaxpr) and len(inner.invars) == len(eqn.invars):
            return inner, inner.invars[i]
    return None


def _targets(closed: ClosedJaxpr, n: int) -> List[Optional[np.dtype]]:
    """For each of the first ``n`` inputs of ``closed``: the one dtype
    that every use of it casts it to, or None."""
    uses: dict = {}

    def uses_of(jaxpr):
        found = uses.get(id(jaxpr))
        if found is None:
            found = collections.defaultdict(list)
            for eqn in jaxpr.eqns:
                for i, v in enumerate(eqn.invars):
                    if isinstance(v, Var):
                        found[v].append((eqn, i))
            for v in jaxpr.outvars:
                if isinstance(v, Var):
                    found[v].append((None, 0))
            uses[id(jaxpr)] = found
        return found

    def into(jaxpr, var, found: set) -> None:
        for eqn, i in uses_of(jaxpr).get(var, ()):
            if eqn is not None and eqn.primitive is convert_element_type_p:
                found.add(np.dtype(eqn.params["new_dtype"]))
                continue
            inner = None if eqn is None else _callee(eqn, i)
            if inner is None:
                found.add(None)
            else:
                into(*inner, found)

    out = []
    for var in closed.jaxpr.invars[:n]:
        found: set = set()
        into(closed.jaxpr, var, found)
        out.append(found.pop() if len(found) == 1 else None)
    return out


def cast_targets(tree, programs) -> List[Optional[np.dtype]]:
    """For each leaf of ``tree`` (``jax.tree.leaves`` order), the narrower
    dtype it is to be held in, or None where it stays as it is.

    ``programs`` are ``(program, args)`` pairs, each called as
    ``program(tree, *args)``; they are traced together on abstract shapes
    (arrays or ``jax.ShapeDtypeStruct``s both do)."""
    leaves = jax.tree.leaves(tree)

    def calls(t, arg_sets):
        return [run(t, *args) for (run, _), args in zip(programs, arg_sets)]

    closed = jax.make_jaxpr(calls)(*jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype),
        (tree, [args for _, args in programs])))
    return [
        target if target is not None
        and target.itemsize < np.dtype(leaf.dtype).itemsize else None
        for leaf, target in zip(leaves, _targets(closed, len(leaves)))
    ]


@functools.partial(jax.jit, static_argnums=1)
def _astype(leaves, dtypes):
    return [x.astype(d) for x, d in zip(leaves, dtypes)]


def cast_at_use(tree, programs):
    """``tree`` with each leaf that ``cast_targets`` picks held in its
    dtype, and the bytes, as handed, of those leaves.

    The cast leaves are made in ONE jitted program; the caller's arrays are
    neither donated nor changed, and every other leaf stays the caller's
    object."""
    leaves, treedef = jax.tree.flatten(tree)
    picked = [(k, target)
              for k, target in enumerate(cast_targets(tree, programs))
              if target is not None]
    if not picked:
        return tree, 0
    cast = _astype([leaves[k] for k, _ in picked],
                   tuple(target for _, target in picked))
    served = list(leaves)
    for (k, _), leaf in zip(picked, cast):
        served[k] = leaf
    return (jax.tree.unflatten(treedef, served),
            sum(int(leaves[k].nbytes) for k, _ in picked))
