"""Split a traced model into what it computes from its weights alone and
the rest.

A stateless model's weights are arguments of its window program
(``filters/jax_xla.py``), so nothing of their values reaches the
program's text and one compiled program serves every set of weights of
the same shapes.  While the weights were literals of the program, XLA
folded whatever the model computes from them alone (the cast of a
float32 checkpoint to the compute type, a batch-norm's ``scale *
rsqrt(var + eps)``, a bias broadcast to the activation's rank) when it
built the program; as arguments that work would run every window.
:func:`split` takes it out of the traced function once: a forward walk
over the equations sorts each into

- the **weights prologue**: every operand derives from weights (or is a
  constant), and the result has no more elements than the weights-derived
  operands it was made from — so a bias broadcast to a whole activation
  stays where XLA fuses it into its consumer instead of becoming a
  buffer of its own;
- the **window program**: everything that reads an input, has an
  effect, or grows; and every sub-graph that depends on neither weights
  nor inputs (anchors, masks, iotas), which stays the constant of the
  program it was.

The prologue runs once when the filter opens (and once for each
weights-only swap); its results, on the device, are what the window
program takes as arguments.  Equations keep their source info, so the
``nns.*`` scopes of the device trace stay on the window program's
operations (``jax.core.eval_jaxpr`` re-binds each under its own name
stack).
"""

from __future__ import annotations

import math
from typing import Any, Callable, List, Optional, Sequence


class Split:
    """A traced function in two parts.  ``window(residuals, *inputs)``
    is the function without its weights-only part; :meth:`make` gives
    the residuals it takes for one set of weights: in order, the weights
    ``direct`` indexes (read by the window program as they are) and the
    results of the prologue on the weights ``prologue_in`` indexes
    (``prologue_jaxpr`` is None where the model derives nothing from
    its weights alone).  A weight in neither list is read by nothing."""

    def __init__(self, window_jaxpr, prologue_jaxpr, direct: Sequence[int],
                 prologue_in: Sequence[int], out_tree=None):
        import jax

        self.window_jaxpr = window_jaxpr
        self.prologue_jaxpr = prologue_jaxpr
        self.direct = list(direct)
        self.prologue_in = list(prologue_in)
        self._out_tree = out_tree
        # set by :func:`trace`: what to trace again for other inputs
        self._retrace = self._traced_for = None
        self._others: dict = {}
        self._prologue = None
        if prologue_jaxpr is not None:
            def weights_prologue(*weights):
                return jax.core.eval_jaxpr(
                    prologue_jaxpr.jaxpr, prologue_jaxpr.consts, *weights)

            # a small program of its own: built (or loaded) once, run
            # once for each set of weights
            self._prologue = jax.jit(weights_prologue)

    def window(self, residuals: Sequence[Any], *inputs):
        import jax

        cj, out_tree = self._window_for(inputs)
        out = jax.core.eval_jaxpr(cj.jaxpr, cj.consts, *residuals, *inputs)
        return out if out_tree is None \
            else jax.tree_util.tree_unflatten(out_tree, out)

    def _window_for(self, inputs):
        """The window program for ``inputs``: the one traced, or, for
        inputs of other shapes (``jax.jit`` traces a function anew for
        them, and a filter serves a frame of another batch so), the
        function traced and split again at those shapes.  What it makes
        of its weights alone has to be what it was: the residuals are
        made already."""
        import jax

        key = tuple((tuple(x.shape), str(x.dtype)) for x in inputs)
        if self._retrace is None or key == self._traced_for:
            return self.window_jaxpr, self._out_tree
        found = self._others.get(key)
        if found is None:
            fn, weights = self._retrace
            other = trace(fn, weights, *[
                jax.ShapeDtypeStruct(x.shape, x.dtype) for x in inputs])
            if (other.direct, other.prologue_in, str(other.prologue_jaxpr)) \
                    != (self.direct, self.prologue_in,
                        str(self.prologue_jaxpr)):
                raise ValueError(
                    f"inputs of {key} make other things of the weights "
                    f"alone than the inputs of {self._traced_for} this "
                    "program was built for")
            found = self._others[key] = (other.window_jaxpr, other._out_tree)
        return found

    def make(self, weights: Any) -> List[Any]:
        """The window program's residuals for ``weights`` (a pytree of
        the array leaves the function was traced with), on the device
        and ready: the prologue has run when this returns."""
        import jax

        flat = jax.tree_util.tree_leaves(weights)
        out = [flat[i] for i in self.direct]
        if self._prologue is not None:
            out.extend(self._prologue(*[flat[i] for i in self.prologue_in]))
        return jax.block_until_ready(out)


def trace(fn: Callable, weights: Any, *inputs) -> Split:
    """``fn(weights, *inputs)`` traced once with every array abstract,
    and split.  ``weights`` is a pytree of arrays (or of their shapes),
    ``inputs`` the inputs' shapes."""
    import jax

    tree = jax.tree_util
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
        weights, *inputs)
    parts = split(closed, len(tree.tree_leaves(weights)),
                  tree.tree_structure(out_shape))
    parts._retrace = (fn, tree.tree_map(
        lambda w: jax.ShapeDtypeStruct(w.shape, w.dtype), weights))
    parts._traced_for = tuple((tuple(x.shape), str(x.dtype))
                              for x in inputs)
    return parts


#: equations that only say how the same elements are indexed
_SHAPE_ONLY = ("reshape", "squeeze", "broadcast_in_dim")


def _elements(variables) -> Optional[int]:
    """Elements of a list of jaxpr variables; None where one of them is
    no array (a token, a reference)."""
    total = 0
    for v in variables:
        shape = getattr(v.aval, "shape", None)
        if shape is None:
            return None
        total += math.prod(shape)
    return total


def split(closed, n_weights: int, out_tree=None) -> Split:
    """Split ``closed`` (a ``ClosedJaxpr`` whose first ``n_weights``
    inputs are the weights' flat array leaves) as the module says;
    ``out_tree`` is the structure the window program's flat results are
    given back in."""
    import jax
    from jax.extend import core as jc

    jaxpr = closed.jaxpr
    weights = list(jaxpr.invars[:n_weights])
    derived = set(weights)              # made from weights alone
    varying = set(jaxpr.invars[n_weights:])   # made from an input
    # an equation's side: "p" the prologue, "w" the window program,
    # "c" a constant of both (neither weights nor inputs: the prologue
    # keeps what its equations read, below)
    side = []
    for eqn in jaxpr.eqns:
        ins = [v for v in eqn.invars if isinstance(v, jc.Var)]
        made_from = [v for v in ins if v in derived]
        if eqn.effects or any(v in varying for v in ins):
            side.append("w")
        elif not made_from:
            side.append("c")
            continue
        else:
            n_out = _elements(eqn.outvars)
            side.append("w" if n_out is None
                        or n_out > _elements(made_from) else "p")
        (derived if side[-1] == "p" else varying).update(eqn.outvars)
    # a result is handed over in the shape it was computed in: a
    # reshape or a rank-expanding broadcast at the prologue's end (a
    # bias as [1, 1, n] for the add that follows) is free inside the
    # window program, while as an argument of that shape it costs a
    # relayout every window (on the TPU, a reduce over the unit dims)
    feeds_prologue = set()
    for i in reversed(range(len(side))):
        eqn = jaxpr.eqns[i]
        if side[i] == "p" and eqn.primitive.name in _SHAPE_ONLY \
                and _elements(eqn.outvars) == _elements(eqn.invars[:1]) \
                and not feeds_prologue.intersection(eqn.outvars):
            side[i] = "w"
            derived.difference_update(eqn.outvars)
        if side[i] == "p":
            feeds_prologue.update(
                v for v in eqn.invars if isinstance(v, jc.Var))
    pro_eqns = [e for e, where in zip(jaxpr.eqns, side) if where in "pc"]
    win_eqns = [e for e, where in zip(jaxpr.eqns, side) if where in "wc"]
    # what the window program reads of the weights' side, in the order
    # it was made: weights first, then the prologue's results
    read = set()
    for eqn in win_eqns:
        read.update(v for v in eqn.invars if isinstance(v, jc.Var))
    read.update(v for v in jaxpr.outvars if isinstance(v, jc.Var))
    made = [v for eqn in pro_eqns for v in eqn.outvars
            if v in derived and v in read]
    direct = [i for i, v in enumerate(weights) if v in read]
    # the prologue: the equations its results need, and no other
    live, kept = set(made), []
    for eqn in reversed(pro_eqns):
        if any(v in live for v in eqn.outvars):
            kept.append(eqn)
            live.update(v for v in eqn.invars if isinstance(v, jc.Var))
    kept.reverse()
    prologue_in = [i for i, v in enumerate(weights) if v in live]
    residuals = [weights[i] for i in direct] + made
    window_jaxpr = jc.ClosedJaxpr(jaxpr.replace(
        invars=residuals + list(jaxpr.invars[n_weights:]), eqns=win_eqns),
        closed.consts)
    prologue_jaxpr = None
    if made:
        prologue_jaxpr = jc.ClosedJaxpr(jaxpr.replace(
            invars=[weights[i] for i in prologue_in], outvars=made,
            eqns=kept, effects=jax.core.no_effects), closed.consts)

    return Split(window_jaxpr, prologue_jaxpr, direct, prologue_in, out_tree)
