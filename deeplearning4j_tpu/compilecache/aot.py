"""AOT dispatch: route executions to ahead-of-time compiled programs.

``jax.jit`` compiles on FIRST CALL — so the first training window and
the first serving request of every shape pay the compiler inline, on
the latency path. JAX's AOT API (``jit_fn.lower(abstract).compile()``)
builds the executable from shapes alone, but the resulting ``Compiled``
object lives outside the jit call cache: a later ``jit_fn(args)`` would
compile AGAIN. :class:`AOTDispatch` closes that gap — it pairs the lazy
jit function with a map of AOT executables keyed by the placeholder
shape signature, dispatching to the prebuilt program when the shapes
match and falling back to lazy jit when they don't (a ragged final
batch nobody predicted still works, it just compiles lazily like
before).

The signature deliberately covers only the *placeholder/stacked-window*
argument: parameter, optimizer-state and constant shapes are fixed for
a given graph version, and the jit cache key that owns this dispatcher
already pins the version — placeholder shapes are the only axis a fit
or serving loop varies.

Only a shape nobody predicted takes the lazy path. ``Compiled``
re-validates every input aval and raises — ``TypeError`` for
shape/dtype/weak-type, ``ValueError`` for sharding or layout — and
that error is NOT absorbed here: a predicted shape whose executable
rejects the live arguments means the abstract arguments it was lowered
from were wrong (a missing sharding, a weak type, a key of another
implementation). Re-dispatching through lazy ``jit`` would turn a
warmed program into a compile under the first request, silently; the
fix belongs at the lowering site.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple


def ph_shape_sig(ph: Dict[str, Any]) -> Tuple:
    """Canonical shape signature of a placeholder dict — the key both
    the window executor's compile accounting and AOT dispatch use, so
    they cannot drift."""
    return tuple(sorted((n, tuple(v.shape)) for n, v in ph.items()))


class AOTDispatch:
    """A jitted train/step function plus its AOT-compiled variants.

    Stored in ``SameDiff._fn_cache`` wherever a bare ``jax.jit`` result
    used to be; callable with the exact same positional signature. With
    no AOT entries (the default) the overhead is one attribute check.
    """

    __slots__ = ("jit_fn", "aot", "ph_arg", "ran")

    def __init__(self, jit_fn: Callable, ph_arg: int):
        self.jit_fn = jit_fn
        self.aot: Dict[Tuple, Any] = {}   # shape sig -> jax Compiled
        self.ph_arg = int(ph_arg)         # index of the placeholder dict
        # shape sigs the scanned fit has dispatched: the first dispatch
        # of one builds its program (samediff._first_dispatch)
        self.ran: set = set()

    def __call__(self, *args):
        if self.aot:
            compiled = self.aot.get(ph_shape_sig(args[self.ph_arg]))
            if compiled is not None:
                return compiled(*args)
        return self.jit_fn(*args)

    # keep the jit AOT surface reachable (SameDiff.precompile uses it)
    def lower(self, *args, **kwargs):
        return self.jit_fn.lower(*args, **kwargs)


class AOTOutput:
    """An AOT-compiled inference executable, stored under ``output()``'s
    exact cache key (the type marks the entry as prebuilt).

    Unlike :class:`AOTDispatch` (one jit fn, MANY placeholder shapes),
    an output cache key already pins the placeholder signature — there
    is exactly one predicted shape set, so the executable is always the
    one called.
    """

    __slots__ = ("compiled",)

    def __init__(self, compiled: Any):
        self.compiled = compiled

    def __call__(self, params, consts, ph, key):
        return self.compiled(params, consts, ph, key)


__all__ = ["AOTDispatch", "AOTOutput", "ph_shape_sig"]
