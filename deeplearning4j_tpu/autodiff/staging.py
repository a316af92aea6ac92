"""The working copies a fit trains on, made by ONE program.

Every fit tier's step program DONATES its parameter, state-variable and
updater-state buffers, so a fit never hands it the graph's own arrays:
it works on copies, the graph's stored arrays stay valid for
``output()`` / ``save()`` / a checkpoint capture while the fit runs (and
as they were if it fails), and the trained arrays replace them when the
fit commits. HBM therefore holds two copies of the state while a fit
runs (docs/training_performance.md "The fit boundary").

The copies are made by one compiled program over the whole tree, not by
one eager ``copy`` a leaf: gpt2-medium under Adam is 876 leaves, and 876
dispatches of 0.27 ms each kept the device waiting 230 ms at the start
of every fit for 11 ms of copying (PERF.md, PR 38). jax keys the program
by the tree's structure, shapes, dtypes and shardings, so a graph's
second fit dispatches the executable its first one built. Under ``jit``
``jnp.copy`` is the ``copy`` primitive: an output is a new buffer and
never its input forwarded, nothing is donated, and on a mesh each copy
keeps its source's sharding with no collective in the program
(tests/test_fit_staging.py holds all three).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def stage_fit_state(sd, tc):
    """``(params, svars, state, stats)`` for one fit of ``sd`` under the
    training config ``tc``: copies of the trainable parameters, the
    state variables and the updater state, safe to donate. A restored
    updater state is reusable only if the trainable set has not changed
    since (e.g. ``convert_to_constant`` between fits); otherwise it is
    initialised anew. A fresh state goes through the program like a
    kept one: the tree a graph stages is then the same at its first fit
    as at every later one, so ONE executable serves the graph's life
    and its second fit compiles nothing (a first fit pays one copy of
    zeros for that). ``stats`` says how the staging went, for
    ``last_fit_stats`` and the ``fit.stage`` span: ``stage_programs``
    (dispatches it took) and ``stage_leaves`` (arrays it copied)."""
    params = sd.trainable_params()
    state = sd._updater_state
    if state is None or state.keys() != params.keys():
        state = tc.updater.init(params)
    staged = _copy_tree((params, sd.state_vars_map(), state))
    stats = {"stage_programs": 1,
             "stage_leaves": len(jax.tree_util.tree_leaves(staged))}
    return (*staged, stats)


__all__ = ["stage_fit_state"]
