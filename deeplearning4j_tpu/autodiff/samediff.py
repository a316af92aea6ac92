"""SameDiff-equivalent define-then-run autodiff graph.

Reference parity: org.nd4j.autodiff.samediff.SameDiff (SameDiff.java) — the
graph is a map of variables + ops; training/inference walk it. The reference
executes **op-by-op in a Java interpreter** with per-op JNI dispatch
(InferenceSession.java:690, TrainingSession.java:74); gradients come from a
separately-built grad graph via per-op doDiff (SameDiff.java:4999
createGradFunction).

TPU-native redesign (SURVEY.md §7 stage 4): the graph records op *names*
from the registry; execution *traces* the pruned DAG into a pure jax
function and compiles it ONCE with jax.jit. Gradients come from jax.grad of
that traced function — no hand-maintained grad graph, no per-op dispatch at
runtime, and the whole training step (forward + backward + updater) is a
single XLA computation in which the compiler fuses elementwise chains into
matmuls and schedules the MXU. Parameters are donated across steps so HBM
holds one copy.

Execution caches are keyed by (graph version, output set, placeholder
shapes/dtypes) — the analogue of the reference's per-thread InferenceSession
map (SameDiff.java:126), except a cache hit costs a dict lookup instead of
an interpreter pass.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.autodiff.staging import stage_fit_state
from deeplearning4j_tpu.autodiff.variable import SDVariable, VariableType
from deeplearning4j_tpu.compilecache.aot import (AOTDispatch,
                                                 AOTOutput as _AOTOutput,
                                                 ph_shape_sig)
from deeplearning4j_tpu.compilecache.cache import COMPILE_STATS
from deeplearning4j_tpu.monitor import memstats
from deeplearning4j_tpu.monitor.trace import TRACER as _tracer
from deeplearning4j_tpu.ndarray.dtype import DataType
from deeplearning4j_tpu.ndarray.ndarray import NDArray
from deeplearning4j_tpu.ops import registry


#: what fit() opens in place of fit.build on a graph it has run
_NOT_BUILDING = contextlib.nullcontext()


def _steady_dispatch(epoch: int):
    return _tracer.span("fit.dispatch", cat="train", epoch=epoch)


@contextlib.contextmanager
def _first_dispatch(epoch: int):
    """The ``fit.dispatch`` that builds its program: its self time is
    ``build_seconds``, and it carries the program's row of a warm-up's
    table (``CompileStats.program_row``)."""
    at = COMPILE_STATS.mark()
    with COMPILE_STATS.span("fit.dispatch", cat="train", epoch=epoch,
                            first=1) as phase:
        yield phase
        row = COMPILE_STATS.program_row("", at)
        phase.set(**{k: row[k] for k in ("trace_s", "lower_s", "backend_s",
                                         "cache_hit")})


class NumericsException(ArithmeticError):
    """Raised by numerics panic modes (reference: the ND4JIllegalState
    thrown by DefaultOpExecutioner NAN_PANIC/INF_PANIC checks)."""


def _to_jnp(value, dtype=None):
    if isinstance(value, NDArray):
        value = value.data
    arr = jnp.asarray(value)
    if dtype is not None:
        arr = arr.astype(DataType.from_any(dtype).jnp)
    return arr


def _placement(a):
    """The sharding an executable must be lowered for to accept ``a``:
    a committed array's own (mesh-placed by ``shard_model``, produced
    by a sharded fit, pinned by ``device_put``), ``None`` for anything
    still free to move (host values, uncommitted default-device
    arrays). An abstract argument may itself name one."""
    if isinstance(a, jax.ShapeDtypeStruct):
        return a.sharding
    return a.sharding if isinstance(a, jax.Array) and a.committed else None


def _abstract(arrays):
    """AOT lowering arguments for live arrays — THE one rule both
    ``precompile`` and ``precompile_output`` lower by: shape, dtype and
    the placement read off each array itself, so the executable accepts
    exactly what a dispatch will pass (compilecache/aot.py absorbs no
    rejection)."""
    return {n: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype,
                                    sharding=_placement(a))
            for n, a in arrays.items()}


@dataclasses.dataclass
class OpNode:
    """One recorded op (reference: samediff.internal.SameDiffOp)."""
    name: str                 # unique node name
    op: str                   # registry op name
    inputs: List[str]         # input variable names
    outputs: List[str]        # output variable names
    attrs: Dict[str, Any]     # static attributes (iArgs/tArgs/bArgs analogue)
    random: bool = False      # needs a PRNG key threaded at trace time
    group: Optional[str] = None  # remat group id (see SameDiff.remat_scope)


class SameDiff:
    """Define-then-run graph with whole-graph XLA compilation."""

    def __init__(self):
        self._vars: Dict[str, SDVariable] = {}
        self._arrays: Dict[str, jax.Array] = {}   # VARIABLE/CONSTANT values
        self._ops: Dict[str, OpNode] = {}
        self._op_order: List[str] = []            # creation order = topo order
        self._producer: Dict[str, str] = {}       # var name -> op node name
        self._name_counter: Dict[str, int] = {}
        self.loss_variables: List[str] = []
        # non-trainable state vars (e.g. BN running stats): carried through
        # the compiled step, updated from graph outputs, never given to the
        # updater (reference: BatchNormalization's self-updated mean/var
        # params, excluded from the gradient view)
        self._state_var_names: set = set()
        self._state_updates: Dict[str, str] = {}  # state var -> source output
        self._version = 0                         # bump on any mutation
        self._fn_cache: Dict[Any, Any] = {}
        self._active_group: Optional[str] = None  # current remat_scope id
        self._group_counter = 0
        self.training_config = None
        self._updater_state = None
        self._seed = 0
        # pre-compile static analysis (analyze/): the last
        # AnalysisReport fit()/precompile() produced, plus the cache
        # key (graph version + context) that makes repeat fits pay a
        # dict lookup, not a re-analysis
        self.last_analysis = None
        self._analysis_key = None
        # dispatch/compile accounting of the most recent fit() epoch
        # (tier, dispatches_per_epoch, window sizes/compiles) — consumed
        # by ui/stats StatsListener
        self.last_fit_stats = None
        # the graph version fit() last prepared: what a fit does before
        # its first fit.stage at a new version is a phase of the start
        # (fit.build, compilecache/cache.py)
        self._fit_built = None
        # which path each attention site of the train step traced last
        # took (monitor/attention.py AttentionSites; None before any)
        self.attention_sites = None
        # op namespaces (reference: SDMath/SDNN/... generated classes)
        from deeplearning4j_tpu.autodiff.ops_namespaces import make_namespaces
        for ns_name, ns in make_namespaces(self).items():
            setattr(self, ns_name, ns)

    # ------------------------------------------------------------------
    # naming
    def _unique_name(self, base: str) -> str:
        if base not in self._vars and base not in self._ops:
            return base
        while True:
            i = self._name_counter.get(base, 0) + 1
            self._name_counter[base] = i
            cand = f"{base}_{i}"
            if cand not in self._vars and cand not in self._ops:
                return cand

    def _mutated(self):
        self._version += 1
        self._fn_cache.clear()

    def _drop_aot_executables(self):
        """Forget every AOT executable, keeping the lazy jit functions.
        An executable is lowered for ONE placement of its arguments;
        after the arrays move to another mesh (parallel/trainer.py
        ``shard_model``) it would reject them, and AOT dispatch does
        not absorb that. The lazy jit under each dispatcher
        re-specialises for the new placement by itself."""
        for key, fn in list(self._fn_cache.items()):
            if isinstance(fn, AOTDispatch):
                fn.aot.clear()
            elif isinstance(fn, _AOTOutput):
                del self._fn_cache[key]

    @property
    def training_config(self):
        return self._training_config

    @training_config.setter
    def training_config(self, tc):
        # assigning a new config must invalidate compiled train steps — the
        # closure bakes in updater/regularization/clip hyperparameters
        self._training_config = tc
        self._mutated()

    # ------------------------------------------------------------------
    # variable creation (reference: SameDiff.var/constant/placeHolder)
    def var(self, name: str = "var", shape: Optional[Sequence[int]] = None,
            dtype: str = "float32", value=None,
            weight_init: Optional[Callable] = None) -> SDVariable:
        """Trainable VARIABLE. Provide ``value`` or ``shape`` (+ optional
        ``weight_init(shape) -> array``)."""
        name = self._unique_name(name)
        if value is not None:
            arr = _to_jnp(value, dtype)
        elif shape is not None:
            if weight_init is not None:
                arr = _to_jnp(weight_init(tuple(shape)), dtype)
            else:
                arr = jnp.zeros(tuple(shape), DataType.from_any(dtype).jnp)
        else:
            raise ValueError("var() needs value= or shape=")
        v = SDVariable(self, name, VariableType.VARIABLE, arr.shape,
                       str(arr.dtype))
        self._vars[name] = v
        self._arrays[name] = arr
        self._mutated()
        return v

    def constant(self, value, name: str = "const", dtype=None) -> SDVariable:
        name = self._unique_name(name)
        arr = _to_jnp(value, dtype)
        v = SDVariable(self, name, VariableType.CONSTANT, arr.shape,
                       str(arr.dtype))
        self._vars[name] = v
        self._arrays[name] = arr
        self._mutated()
        return v

    def placeholder(self, name: str, shape: Optional[Sequence[int]] = None,
                    dtype: str = "float32") -> SDVariable:
        """PLACEHOLDER fed at exec time; -1/None dims = batch dims."""
        name = self._unique_name(name)
        shp = tuple(-1 if (d is None or d == -1) else int(d) for d in shape) \
            if shape is not None else None
        v = SDVariable(self, name, VariableType.PLACEHOLDER, None, dtype)
        v._shape = shp
        self._vars[name] = v
        self._mutated()
        return v

    # alias matching the reference API
    place_holder = placeholder

    def zero(self, name, shape, dtype="float32"):
        return self.constant(jnp.zeros(tuple(shape), DataType.from_any(dtype).jnp), name)

    def one(self, name, shape, dtype="float32"):
        return self.constant(jnp.ones(tuple(shape), DataType.from_any(dtype).jnp), name)

    def _lift(self, value) -> SDVariable:
        """Coerce a python scalar/array into a CONSTANT variable."""
        if isinstance(value, SDVariable):
            if value.sd is not self:
                raise ValueError("variable belongs to a different SameDiff")
            return value
        return self.constant(value)

    # ------------------------------------------------------------------
    # graph access
    def variables(self) -> List[SDVariable]:
        return list(self._vars.values())

    def get_variable(self, name: str) -> SDVariable:
        return self._vars[name]

    def has_variable(self, name: str) -> bool:
        return name in self._vars

    def ops(self) -> List[OpNode]:
        return [self._ops[n] for n in self._op_order]

    def trainable_params(self) -> Dict[str, jax.Array]:
        return {n: self._arrays[n] for n, v in self._vars.items()
                if v.var_type == VariableType.VARIABLE
                and n not in self._state_var_names}

    def state_var(self, name: str, value, dtype: str = "float32") -> SDVariable:
        """Non-trainable state variable (e.g. BN running mean): updated via
        update_state(), not by the updater."""
        v = self.var(name, value=value, dtype=dtype)
        self._state_var_names.add(v.name)
        return v

    def update_state(self, state_var: Union[str, SDVariable],
                     new_value: Union[str, SDVariable]) -> None:
        """Declare that ``state_var`` takes the value of graph output
        ``new_value`` after each training step."""
        sn = state_var.name if isinstance(state_var, SDVariable) else state_var
        nn_ = new_value.name if isinstance(new_value, SDVariable) else new_value
        if sn not in self._state_var_names:
            raise ValueError(f"{sn!r} is not a state var")
        self._state_updates[sn] = nn_
        self._mutated()

    def state_vars_map(self) -> Dict[str, jax.Array]:
        return {n: self._arrays[n] for n in self._state_var_names}

    def constants_map(self) -> Dict[str, jax.Array]:
        return {n: self._arrays[n] for n, v in self._vars.items()
                if v.var_type == VariableType.CONSTANT}

    def placeholders(self) -> List[str]:
        return [n for n, v in self._vars.items()
                if v.var_type == VariableType.PLACEHOLDER]

    def get_arr_for_var(self, name: str):
        return NDArray(self._arrays[name]) if name in self._arrays else None

    def set_arr_for_var(self, name: str, value):
        v = self._vars[name]
        if v.var_type not in (VariableType.VARIABLE, VariableType.CONSTANT):
            raise ValueError(f"{name} is {v.var_type.value}; has no stored array")
        self._arrays[name] = _to_jnp(value)  # values are runtime args; no retrace

    def set_loss_variables(self, names: Sequence[Union[str, SDVariable]]):
        self.loss_variables = [n.name if isinstance(n, SDVariable) else n
                               for n in names]

    def rename_variable(self, old: str, new: str) -> SDVariable:
        if new in self._vars:
            raise ValueError(f"variable {new!r} already exists")
        v = self._vars.pop(old)
        v.name = new
        self._vars[new] = v
        if old in self._arrays:
            self._arrays[new] = self._arrays.pop(old)
        if old in self._producer:
            self._producer[new] = self._producer.pop(old)
        for node in self._ops.values():
            node.inputs = [new if i == old else i for i in node.inputs]
            node.outputs = [new if o == old else o for o in node.outputs]
        self.loss_variables = [new if n == old else n for n in self.loss_variables]
        if old in self._state_var_names:
            self._state_var_names.discard(old)
            self._state_var_names.add(new)
        self._state_updates = {
            (new if k == old else k): (new if s == old else s)
            for k, s in self._state_updates.items()}
        self._mutated()
        return v

    def convert_to_constant(self, v: SDVariable) -> SDVariable:
        if v.var_type != VariableType.VARIABLE:
            raise ValueError("only VARIABLE can convert to constant")
        v.var_type = VariableType.CONSTANT
        self._mutated()
        return v

    def convert_to_variable(self, v: SDVariable) -> SDVariable:
        if v.var_type != VariableType.CONSTANT:
            raise ValueError("only CONSTANT can convert to variable")
        v.var_type = VariableType.VARIABLE
        self._mutated()
        return v

    # ------------------------------------------------------------------
    # op recording (reference: DynamicCustomOp registration into the graph)
    def invoke(self, op_name: str, inputs: Sequence[SDVariable],
               attrs: Optional[Dict[str, Any]] = None,
               name: Optional[str] = None, n_outputs: int = 1) -> Union[SDVariable, List[SDVariable]]:
        """Record a registry op; returns its output variable(s)."""
        o = registry.get_op(op_name)
        attrs = dict(attrs or {})
        node_name = self._unique_name(name or op_name)
        is_random = o.needs_key    # op() folds category=="random" into it
        out_names = []
        for i in range(n_outputs):
            base = node_name if n_outputs == 1 else f"{node_name}:{i}"
            out_name = self._unique_name(base)
            ov = SDVariable(self, out_name, VariableType.ARRAY, None, "float32")
            self._vars[out_name] = ov
            out_names.append(out_name)
        node = OpNode(name=node_name, op=o.name,
                      inputs=[v.name for v in inputs], outputs=out_names,
                      attrs=attrs, random=is_random,
                      group=self._active_group)
        self._ops[node_name] = node
        self._op_order.append(node_name)
        for on in out_names:
            self._producer[on] = node_name
        self._mutated()
        outs = [self._vars[n] for n in out_names]
        return outs[0] if n_outputs == 1 else outs

    # ------------------------------------------------------------------
    # control flow (reference: AbstractSession.java:46-101 executes
    # Enter/Exit/Switch/Merge frames host-side; redesigned per ADR 0020's
    # invokable-subgraph direction, lowered to lax.while_loop/cond/scan —
    # see ops/control_flow.py for semantics + differentiability)
    @staticmethod
    def _var_shape(v) -> Optional[Tuple[int, ...]]:
        """Best-effort static shape: the .shape property runs lazy
        inference for ARRAY vars (derived op outputs), so control-flow
        bodies see real shapes, not just placeholder declarations."""
        try:
            return v.shape
        except Exception:
            return None

    def _record_subgraph(self, fn, arg_vars, arg_shapes=None,
                         prefix: str = "p"):
        from deeplearning4j_tpu.ops import control_flow as cf
        sub = SameDiff()
        phs = []
        for i, v in enumerate(arg_vars):
            shape = (arg_shapes[i] if arg_shapes is not None
                     else self._var_shape(v))
            ph = sub.placeholder(f"{prefix}{i}", shape=shape,
                                 dtype=getattr(v, "dtype", "float32"))
            phs.append(ph)
        res = fn(sub, *phs)
        if isinstance(res, SDVariable):
            res = [res]
        if not res:
            raise ValueError("control-flow subgraph returned no outputs")
        return cf.subgraph_to_json(sub, [p.name for p in phs],
                                   [r.name for r in res])

    def while_loop(self, cond_fn, body_fn, loop_vars, captures=(),
                   name: str = "while"):
        """Data-dependent loop: ``cond_fn(sub, *loop_vars, *captures) ->
        scalar bool var``, ``body_fn(sub, *loop_vars, *captures) -> new
        loop vars``. Returns the final loop vars. Lowered to
        ``lax.while_loop`` (forward-only; use scan() for gradients)."""
        loop_vars, captures = list(loop_vars), list(captures)
        allv = loop_vars + captures
        cg = self._record_subgraph(cond_fn, allv)
        bg = self._record_subgraph(body_fn, allv)
        if len(bg["outputs"]) != len(loop_vars):
            raise ValueError(
                f"while_loop body returned {len(bg['outputs'])} values "
                f"for {len(loop_vars)} loop vars")
        return self.invoke("while_loop", allv,
                           {"cond_graph": cg, "body_graph": bg,
                            "n_loop": len(loop_vars)},
                           name=name, n_outputs=len(loop_vars))

    def cond(self, pred, true_fn, false_fn, operands, name: str = "cond"):
        """Branch: ``true_fn/false_fn(sub, *operands) -> same-shaped
        outputs``. Lowered to ``lax.cond`` (differentiable)."""
        operands = list(operands)
        tg = self._record_subgraph(true_fn, operands)
        fg = self._record_subgraph(false_fn, operands)
        if len(tg["outputs"]) != len(fg["outputs"]):
            raise ValueError("cond branches must return the same arity")
        return self.invoke("cond_branch", [pred, *operands],
                           {"true_graph": tg, "false_graph": fg},
                           name=name, n_outputs=len(tg["outputs"]))

    def scan(self, body_fn, carries, scanned=(), captures=(),
             length: Optional[int] = None, reverse: bool = False,
             name: str = "scan"):
        """Static-trip recurrence: ``body_fn(sub, *carries, *x_slices,
        *captures) -> (new_carries..., per_step_outputs...)``; scanned
        vars are consumed along their leading axis. Returns final
        carries + stacked per-step outputs. Lowered to ``lax.scan`` —
        fully reverse-mode differentiable (the trainable-RNN path)."""
        carries, scanned, captures = (list(carries), list(scanned),
                                      list(captures))
        shapes = [self._var_shape(v) for v in carries]
        for v in scanned:
            s = self._var_shape(v)
            shapes.append(tuple(s[1:]) if s else None)
        shapes += [self._var_shape(v) for v in captures]
        bg = self._record_subgraph(body_fn, carries + scanned + captures,
                                   arg_shapes=shapes)
        n_out = len(bg["outputs"])
        if n_out < len(carries):
            raise ValueError("scan body must return at least the carries")
        return self.invoke("scan_loop", carries + scanned + captures,
                           {"body_graph": bg, "n_carry": len(carries),
                            "n_scan": len(scanned), "length": length,
                            "reverse": reverse},
                           name=name, n_outputs=n_out)

    def remat_scope(self, name: str = "remat"):
        """Context manager: ops recorded inside form a rematerialized
        (gradient-checkpointed) group — at trace time the group becomes one
        ``jax.checkpoint`` call, so its internal activations are NOT saved
        for the backward pass but recomputed from the group's inputs.

        The TPU-native memory/workspace lever (SURVEY §2.1 memory &
        workspaces): where the reference manages activation memory with
        workspaces + MemoryManager, here HBM held-live set is traded for
        FLOPs at the XLA level. Typical use: one scope per transformer
        layer, which drops activation memory from O(layers) to
        O(sqrt-ish) and lets batch/seq grow to MXU-saturating sizes::

            for i in range(num_layers):
                with sd.remat_scope(f"layer{i}"):
                    x = block(sd, x, ...)

        Nesting records the innermost scope only (one checkpoint level).
        """
        import contextlib

        @contextlib.contextmanager
        def _scope():
            prev = self._active_group
            self._group_counter += 1
            self._active_group = f"{name}#{self._group_counter}"
            try:
                yield
            finally:
                self._active_group = prev

        return _scope()

    # ------------------------------------------------------------------
    # tracing: graph -> pure jax function
    def _prune(self, outputs: Sequence[str]) -> List[OpNode]:
        """Subgraph of ops needed for ``outputs``, in recorded (topo) order.

        Reference: AbstractSession subgraph build (AbstractSession.java:140+).
        """
        needed_vars = set(outputs)
        needed_ops = set()
        for op_name in reversed(self._op_order):
            node = self._ops[op_name]
            if any(o in needed_vars for o in node.outputs):
                needed_ops.add(op_name)
                needed_vars.update(node.inputs)
        return [self._ops[n] for n in self._op_order if n in needed_ops]

    def _trace_fn(self, outputs: Tuple[str, ...]) -> Callable:
        """Build fn(params, constants, placeholders, key) -> {name: array}.

        Consecutive ops sharing a remat group (recorded under
        ``remat_scope``) execute inside one ``jax.checkpoint`` region:
        the group's boundary values are the only activations XLA keeps
        live for the backward pass."""
        order = self._prune(outputs)
        out_set = set(outputs)

        # segment the topo order into (group, [(global_idx, node), ...])
        segments: List[Tuple[Optional[str], List[Tuple[int, OpNode]]]] = []
        for idx, node in enumerate(order):
            g = node.group
            if segments and segments[-1][0] == g and g is not None:
                segments[-1][1].append((idx, node))
            else:
                segments.append((g, [(idx, node)]))

        def _run_nodes(nodes, env, key):
            for idx, node in nodes:
                o = registry.get_op(node.op)
                attrs = dict(node.attrs)
                if node.random:
                    attrs["key"] = jax.random.fold_in(key, idx)
                try:
                    args = [env[i] for i in node.inputs]
                except KeyError as e:
                    raise KeyError(
                        f"op {node.name!r} needs variable {e.args[0]!r} — "
                        f"missing placeholder?") from None
                res = o.fn(*args, **attrs)
                if isinstance(res, (tuple, list)):
                    for out_name, r in zip(node.outputs, res):
                        env[out_name] = r
                else:
                    env[node.outputs[0]] = res

        # per remat segment: external inputs (read, not produced inside)
        # and external outputs (produced inside, consumed later/returned)
        seg_specs = []
        for si, (g, nodes) in enumerate(segments):
            if g is None:
                seg_specs.append((None, nodes, None, None))
                continue
            produced = {o for _, n in nodes for o in n.outputs}
            ext_in, seen = [], set()
            for _, n in nodes:
                for i in n.inputs:
                    if i not in produced and i not in seen:
                        seen.add(i)
                        ext_in.append(i)
            later = set()
            for _, nodes2 in segments[si + 1:]:
                for _, n2 in nodes2:
                    later.update(n2.inputs)
            ext_out = [o for _, n in nodes for o in n.outputs
                       if o in later or o in out_set]
            seg_specs.append((g, nodes, ext_in, ext_out))

        def fn(params: Dict[str, jax.Array], constants: Dict[str, jax.Array],
               placeholders: Dict[str, jax.Array], key) -> Dict[str, jax.Array]:
            env: Dict[str, jax.Array] = {}
            env.update(constants)
            env.update(params)
            env.update(placeholders)
            for g, nodes, ext_in, ext_out in seg_specs:
                if g is None:
                    _run_nodes(nodes, env, key)
                    continue

                def seg_fn(k, *args, _nodes=nodes, _ein=ext_in,
                           _eout=ext_out):
                    local = dict(zip(_ein, args))
                    _run_nodes(_nodes, local, k)
                    return tuple(local[o] for o in _eout)

                try:
                    args = [env[i] for i in ext_in]
                except KeyError as e:
                    raise KeyError(
                        f"remat group {g!r} needs variable {e.args[0]!r} — "
                        f"missing placeholder?") from None
                res = jax.checkpoint(seg_fn)(key, *args)
                env.update(zip(ext_out, res))
            missing = [o for o in outputs if o not in env]
            if missing:
                raise KeyError(f"outputs not computable: {missing}")
            return {o: env[o] for o in outputs}

        return fn

    def _ph_sig(self, placeholders: Dict[str, jax.Array]):
        return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                            for k, v in placeholders.items()))

    def _output_cache_key(self, out_names, ph):
        """The execution-cache key for an inference program — shared by
        output() and precompile_output() so an AOT executable installed
        by serving warmup is found by the exact lazy lookup (a drift
        between the two would silently reintroduce the first-request
        compile warmup exists to kill)."""
        return ("output", self._version, tuple(out_names),
                self._ph_sig(ph))

    def _prep_placeholders(self, placeholders) -> Dict[str, jax.Array]:
        out = {}
        for k, v in (placeholders or {}).items():
            if isinstance(k, SDVariable):
                k = k.name
            out[k] = _to_jnp(v, self._vars[k].dtype if k in self._vars else None)
        return out

    # ------------------------------------------------------------------
    # inference (reference: SameDiff.output, SameDiff.java:2568)
    def output(self, placeholders=None, outputs: Optional[Sequence[Union[str, SDVariable]]] = None,
               key=None) -> Dict[str, NDArray]:
        if outputs is None:
            outputs = self.outputs()
        out_names = tuple(o.name if isinstance(o, SDVariable) else o
                          for o in outputs)
        ph = self._prep_placeholders(placeholders)
        cache_key = self._output_cache_key(out_names, ph)
        compiled = self._fn_cache.get(cache_key)
        if compiled is None:
            fn = self._trace_fn(out_names)
            compiled = jax.jit(fn)
            self._fn_cache[cache_key] = compiled
        if key is None:
            key = jax.random.key(self._seed)
            self._seed += 1
        res = compiled({**self.trainable_params(), **self.state_vars_map()},
                       self.constants_map(), ph, key)
        return {k: NDArray(v) for k, v in res.items()}

    # reference names
    exec = output
    batch_output = output

    def exec_debug(self, placeholders=None, outputs=None, key=None,
                   check: str = "nan_inf"):
        """Eager op-by-op execution with per-op numerics checks — the
        NAN_PANIC/INF_PANIC diagnosis path (reference:
        DefaultOpExecutioner.java:397-437 checkForAny/checkForNaN).

        Under jit there is nothing between ops to hook, so panic-mode
        LOCALIZATION runs the pruned graph eagerly (one tiny XLA program
        per op) and raises NumericsException at the first op whose output
        goes non-finite, naming the op, its inputs and their stats. Slow
        by design; use after fit() flags a non-finite loss
        (TrainingConfig.nan_panic)."""
        import numpy as _np
        if outputs is None:
            outputs = self.outputs()
        out_names = tuple(o.name if isinstance(o, SDVariable) else o
                          for o in outputs)
        ph = self._prep_placeholders(placeholders)
        if key is None:
            key = jax.random.key(0)
        env: Dict[str, jax.Array] = {}
        env.update(self.constants_map())
        env.update({**self.trainable_params(), **self.state_vars_map()})
        env.update(ph)

        def _bad(a):
            a = _np.asarray(a)
            if not _np.issubdtype(a.dtype, _np.floating):
                return None
            if check in ("nan", "nan_inf") and _np.isnan(a).any():
                return "NaN"
            if check in ("inf", "nan_inf") and _np.isinf(a).any():
                return "Inf"
            return None

        for name, arr in env.items():
            kind = _bad(arr)
            if kind:
                raise NumericsException(f"input/parameter {name!r} already "
                                        f"contains {kind}")
        for idx, node in enumerate(self._prune(out_names)):
            o = registry.get_op(node.op)
            attrs = dict(node.attrs)
            if node.random:
                attrs["key"] = jax.random.fold_in(key, idx)
            try:
                args = [env[i] for i in node.inputs]
            except KeyError as e:
                raise KeyError(
                    f"exec_debug: op {node.name!r} needs variable "
                    f"{e.args[0]!r} — pass it in placeholders=") from None
            res = o.fn(*args, **attrs)
            results = list(res) if isinstance(res, (tuple, list)) else [res]
            for out_name, r in zip(node.outputs, results):
                env[out_name] = r
                kind = _bad(r)
                if kind:
                    stats = "; ".join(
                        f"{i}: shape {tuple(_np.shape(env[i]))}, "
                        f"range [{float(_np.nanmin(_np.asarray(env[i]))):.4g}"
                        f", {float(_np.nanmax(_np.asarray(env[i]))):.4g}]"
                        for i in node.inputs)
                    raise NumericsException(
                        f"{kind} produced by op {node.op!r} (node "
                        f"{node.name!r}) in output {out_name!r}; "
                        f"inputs: {stats}")
        return {o: NDArray(env[o]) for o in out_names}

    def outputs(self) -> List[str]:
        """Graph outputs = ARRAY vars consumed by no op (reference:
        SameDiff.outputs())."""
        consumed = set()
        for node in self._ops.values():
            consumed.update(node.inputs)
        outs = [n for n, v in self._vars.items()
                if v.var_type == VariableType.ARRAY and n not in consumed]
        return outs

    def infer_shape(self, name: str) -> Optional[Tuple[int, ...]]:
        """Shape inference via jax.eval_shape over the pruned subgraph —
        the analogue of calculateOutputShapes2 (NativeOps.h), done by the
        tracer instead of per-op C++ shape functions."""
        v = self._vars[name]
        if name in self._arrays:
            return tuple(self._arrays[name].shape)
        if v.var_type == VariableType.PLACEHOLDER:
            return v._shape
        fn = self._trace_fn((name,))
        ph_specs = {}
        faked_dims = False
        for pn in self.placeholders():
            pv = self._vars[pn]
            if pv._shape is None or any(d == -1 for d in pv._shape):
                shape = tuple(1 if d == -1 else d for d in (pv._shape or (1,)))
                faked_dims = True
            else:
                shape = pv._shape
            ph_specs[pn] = jax.ShapeDtypeStruct(shape, DataType.from_any(pv.dtype).jnp)
        try:
            out = jax.eval_shape(fn,
                                 {**self.trainable_params(),
                                  **self.state_vars_map()},
                                 self.constants_map(),
                                 ph_specs, jax.random.key(0))
            return tuple(out[name].shape)
        except (jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError):
            # ops with structural-tensor args (tf_compat Reshape etc.) need
            # concrete values the abstract tracer can't provide — the shape
            # is genuinely not statically inferable here.
            return None
        except (TypeError, ValueError):
            if faked_dims:
                # unknown placeholder dims were substituted with 1 to make
                # abstract eval possible; a shape-compat failure is then an
                # artifact of the fake dims, not a user bug
                return None
            # fully-known shapes that still fail to trace = a real graph
            # error the caller must see (round-2 Weak #3: don't swallow)
            raise

    # ------------------------------------------------------------------
    # gradients (reference: createGradFunction + calculateGradients,
    # SameDiff.java:4999,5013 — replaced by jax.grad of the traced fn)
    def calculate_gradients(self, placeholders=None,
                            wrt: Optional[Sequence[Union[str, SDVariable]]] = None,
                            loss: Optional[Union[str, SDVariable]] = None,
                            key=None) -> Dict[str, NDArray]:
        wrt_names = tuple(w.name if isinstance(w, SDVariable) else w
                          for w in (wrt or self.trainable_params().keys()))
        loss_names = self._resolve_loss(loss)
        ph = self._prep_placeholders(placeholders)
        cache_key = ("grad", self._version, wrt_names, loss_names, self._ph_sig(ph))
        compiled = self._fn_cache.get(cache_key)
        if compiled is None:
            fn = self._trace_fn(loss_names)

            def loss_fn(wrt_params, other_params, constants, phv, k):
                params = {**other_params, **wrt_params}
                outs = fn(params, constants, phv, k)
                return sum(jnp.sum(outs[ln]) for ln in loss_names)

            compiled = jax.jit(jax.grad(loss_fn))
            self._fn_cache[cache_key] = compiled
        params = {**self.trainable_params(), **self.state_vars_map()}
        wrt_params = {n: params[n] for n in wrt_names}
        other = {n: p for n, p in params.items() if n not in wrt_names}
        if key is None:
            key = jax.random.key(self._seed)
            self._seed += 1
        grads = compiled(wrt_params, other, self.constants_map(), ph, key)
        return {k: NDArray(v) for k, v in grads.items()}

    def _device_span(self) -> int:
        """How many devices the model's arrays lie on: 1 unless
        ``parallel.trainer.shard_model`` (or anything else) has placed
        them on a mesh."""
        return max((len(a.sharding.device_set)
                    for a in self._arrays.values()
                    if isinstance(a, jax.Array)), default=1)

    def _resolve_loss(self, loss=None) -> Tuple[str, ...]:
        if loss is not None:
            return (loss.name if isinstance(loss, SDVariable) else loss,)
        if self.loss_variables:
            return tuple(self.loss_variables)
        # fall back: single graph output
        outs = self.outputs()
        if len(outs) == 1:
            return (outs[0],)
        raise ValueError("no loss variable set; call set_loss_variables()")

    # ------------------------------------------------------------------
    # training (reference: SameDiff.fit → TrainingSession.java:74; here the
    # step — forward+backward+updater+param update — is ONE jitted fn with
    # donated param/state buffers)
    def _build_step_parts(self):
        """The two halves of the train step, separated so gradient
        accumulation (autodiff/window.py) can run the gradient half every
        micro-step and the apply half every ``accum_steps``-th:

        - ``grad_fn(params, svars, iteration, constants, phv, base_key)
          -> (grads, new_svars, data_loss)`` — forward + backward with
          the optional mixed-precision policy applied (cast params/inputs
          to the compute dtype inside the trace; gradients flow back
          through the casts as float32 master-param grads);
        - ``apply_fn(params, grads, state, iteration)
          -> (new_params, new_state)`` — regularization + clipping +
          updater + parameter update.
        """
        tc = self.training_config
        if tc is None:
            raise ValueError("set sd.training_config = TrainingConfig(...) first")
        loss_names = self._resolve_loss()
        state_updates = dict(self._state_updates)
        trace_outputs = loss_names + tuple(state_updates.values())
        fn = self._trace_fn(trace_outputs)
        updater = tc.updater
        regs = tc.regularization or []

        from deeplearning4j_tpu.learning.schedules import resolve_lr
        pre_regs = [r for r in regs if r.apply_step == "BEFORE_UPDATER"]
        post_regs = [r for r in regs if r.apply_step == "POST_UPDATER"]

        mp = getattr(tc, "mixed_precision", None)
        if mp is not None:
            cdt = DataType.from_any(mp.compute_dtype).jnp
            loss_scale = mp.loss_scale

            def _cast(tree):
                return jax.tree_util.tree_map(
                    lambda x: x.astype(cdt)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)
        else:
            loss_scale = None
            _cast = None
        # CE-tail precision policy (MixedPrecision.softmax_dtype): the
        # scope is consulted by the loss ops at TRACE time, so it wraps
        # the graph fn's execution inside loss_fn below
        _ce_dt = getattr(mp, "softmax_dtype", None) if mp is not None \
            else None

        def _ce_scope():
            if _ce_dt is None:
                import contextlib
                return contextlib.nullcontext()
            from deeplearning4j_tpu.ops.loss import softmax_dtype_scope
            return softmax_dtype_scope(_ce_dt)

        def _attention_scope():
            # attention's choice of path (ops/nn_ops.py
            # scaled_dot_product_attention) needs what only the tracer
            # of the step knows: how many devices the model's arrays
            # span. Read when the step is TRACED (a jit retraces when
            # its arguments move onto a mesh), with a fresh tally of the
            # sites each trace (monitor/attention.py)
            from deeplearning4j_tpu.monitor.attention import open_train_step
            from deeplearning4j_tpu.ops.nn_ops import attention_trace_scope
            self.attention_sites = open_train_step(self._device_span())
            return attention_trace_scope(self.attention_sites)

        def grad_fn(params, svars, iteration, constants, phv, base_key):
            # per-step key derived ON DEVICE (a host-side jax.random.key per
            # step costs a host dispatch; fold_in is free inside the jit)
            key = jax.random.fold_in(base_key, iteration)

            def loss_fn(p):
                with _ce_scope(), _attention_scope():
                    if _cast is not None:
                        # bf16 compute: params/inputs/constants cast at
                        # the top of the trace (XLA fuses the casts);
                        # state vars (BN running stats) stay f32 — the
                        # norm ops keep their statistics math in f32 and
                        # emit x-dtype activations
                        outs = fn({**_cast(p),
                                   **jax.lax.stop_gradient(svars)},
                                  _cast(constants), _cast(phv), key)
                    else:
                        outs = fn({**p, **jax.lax.stop_gradient(svars)},
                                  constants, phv, key)
                loss = sum(jnp.sum(outs[ln]).astype(jnp.float32)
                           for ln in loss_names)
                if loss_scale is not None:
                    return loss * loss_scale, (outs, loss)
                return loss, (outs, loss)

            (_, (outs, data_loss)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            if loss_scale is not None:
                grads = jax.tree_util.tree_map(
                    lambda g: g / loss_scale, grads)
            # chaos harness (faults/chaos.py): deterministic NaN-gradient
            # injection at one absolute iteration, traced into the
            # program — fires inside fused windows/scans too. A None
            # spec (production) leaves the trace untouched.
            _chaos = getattr(tc, "_chaos_spec", None)
            _nan_at = getattr(_chaos, "nan_grads_at", None) \
                if _chaos is not None else None
            if _nan_at is not None:
                grads = jax.tree_util.tree_map(
                    lambda g: jnp.where(iteration == int(_nan_at),
                                        jnp.full_like(g, jnp.nan), g),
                    grads)
            new_svars = {sn: outs[src].astype(svars[sn].dtype)
                         for sn, src in state_updates.items()}
            # state vars with no declared update carry over unchanged
            new_svars = {**svars, **new_svars}
            return grads, new_svars, data_loss

        def apply_fn(params, grads, state, iteration):
            lr = resolve_lr(getattr(updater, "learning_rate", 0.0), iteration, 0)
            # L1/L2 modify the gradient pre-updater; WeightDecay modifies the
            # update post-updater (reference: BaseMultiLayerUpdater.update)
            for r in pre_regs:
                grads = jax.tree_util.tree_map(
                    lambda p, g: r.apply(p, g, lr), params, grads)
            grads = tc.clip_gradients(grads)
            updates, new_state = updater.apply(grads, state, iteration)
            for r in post_regs:
                updates = jax.tree_util.tree_map(
                    lambda p, u: r.apply(p, u, lr), params, updates)
            new_params = jax.tree_util.tree_map(
                lambda p, u: p - u, params, updates)
            return new_params, new_state

        return grad_fn, apply_fn, loss_names

    def _ts_stats_fn(self, tensorstats):
        """The traced tensorstats sampler (monitor/tensorstats.py):
        ``stats_fn(iteration, params, new_params, grads) -> stats`` —
        the configured per-layer summaries under a ``lax.cond`` that
        fires only on sampled steps (zeros otherwise; shape-stable).
        Layer order is the sorted trainable-param names, the SAME order
        the host-side record builder uses."""
        from deeplearning4j_tpu.monitor.tensorstats import (compute_stats,
                                                            layer_names,
                                                            zeros_stats)
        ts = tensorstats
        names = layer_names(self.trainable_params())

        def stats_fn(take, params, new_params, grads):
            def _sampled():
                updates = jax.tree_util.tree_map(
                    lambda a, b: a - b, params, new_params) \
                    if "updates" in ts.families else None
                return compute_stats(
                    ts, names,
                    grads=grads if "grads" in ts.families else None,
                    updates=updates,
                    params=new_params if "params" in ts.families else None)

            return jax.lax.cond(take, _sampled,
                                lambda: zeros_stats(len(names), ts))

        return stats_fn, names

    def _build_step_body(self, sentinel: bool = False, tensorstats=None):
        """One full train step (forward + backward + updater + param
        update) composed from _build_step_parts — shared by the per-batch
        step, the fused-window step and the scanned whole-epoch step.

        ``sentinel=True`` (TrainingConfig.sentinel, faults/sentinels.py)
        makes the body additionally emit one boolean from
        ``_sentinel_ok``: finite loss AND finite global gradient norm.
        ``tensorstats`` (TrainingConfig.tensorstats, monitor/
        tensorstats.py) appends the sampled per-layer stats pytree
        (zeros on unsampled steps — the host keeps only sampled ones).
        Both are computed from values the step already produces;
        parameter math is untouched (training with either rail on is
        bit-identical to off)."""
        grad_fn, apply_fn, loss_names = self._build_step_parts()
        if tensorstats is not None:
            from deeplearning4j_tpu.monitor.tensorstats import sample_mask
            stats_fn, _ = self._ts_stats_fn(tensorstats)

        def step_body(params, svars, state, iteration, constants, phv,
                      base_key):
            grads, new_svars, data_loss = grad_fn(params, svars, iteration,
                                                  constants, phv, base_key)
            new_params, new_state = apply_fn(params, grads, state, iteration)
            # iteration advances on device — no per-step int transfer
            out = [new_params, new_svars, new_state, iteration + 1,
                   data_loss]
            if sentinel:
                out.append(self._sentinel_ok(data_loss, grads))
            if tensorstats is not None:
                out.append(stats_fn(sample_mask(iteration, tensorstats),
                                    params, new_params, grads))
            return tuple(out)

        return step_body, loss_names

    def make_train_step(self, donate: bool = True, sentinel: bool = False,
                        tensorstats=None):
        step_body, loss_names = self._build_step_body(
            sentinel=sentinel, tensorstats=tensorstats)
        cache_key = ("train_step", self._version, loss_names, donate,
                     bool(sentinel),
                     tensorstats.key() if tensorstats is not None else None)
        compiled = self._fn_cache.get(cache_key)
        if compiled is None:
            self._verbose_log(f"compiling train step (graph v{self._version}, "
                              f"{len(self._ops)} ops, donate={donate})")
            compiled = AOTDispatch(
                jax.jit(step_body,
                        donate_argnums=(0, 1, 2, 3) if donate else ()),
                ph_arg=5)
            self._fn_cache[cache_key] = compiled
        return compiled

    @staticmethod
    def _sentinel_ok(data_loss, grads):
        """The divergence sentinel's per-step verdict: finite loss AND a
        finite global gradient L1 norm. The norm touches every gradient
        leaf — NO sampling — because a where-based op (relu, dropout
        masks) can launder NaN activations into a FINITE loss while one
        weight's gradient (``x^T @ delta`` with NaN x) silently poisons
        that parameter forever; only a reduction over all leaves sees
        it. The check is a boolean ``isfinite``-AND reduce (not a float
        norm accumulation): XLA fuses the elementwise ``isfinite`` into
        each gradient's producer and the AND-reduce has no serial float
        dependency chain."""
        ok = jnp.isfinite(data_loss)
        for g in jax.tree_util.tree_leaves(grads):
            ok = ok & jnp.all(jnp.isfinite(g))
        return ok

    @staticmethod
    def _nan_panic_active(tc) -> bool:
        """Loss checking is on when the config asks for it OR the runtime
        Environment is in debug mode — debug set after a TrainingConfig
        was built must still take effect at fit time."""
        if getattr(tc, "nan_panic", False):
            return True
        from deeplearning4j_tpu.environment import environment
        return environment().is_debug()

    @staticmethod
    def _verbose_log(msg: str) -> None:
        """Environment verbose mode (reference: Environment.h verbose —
        the runtime narrates compile/dispatch events)."""
        from deeplearning4j_tpu.environment import environment
        env = environment()
        if env.is_verbose() or env.is_debug():
            print(f"[deeplearning4j_tpu] {msg}")

    def make_train_epoch(self, donate: bool = True, unroll: int = 1,
                         sentinel: bool = False, fingerprint: bool = False):
        """Whole-epoch train step: lax.scan of the step body over batches
        stacked on a leading steps axis. ONE device dispatch per epoch —
        on a host-bottlenecked chip this removes the per-step
        dispatch latency that dominates small models (no reference
        analogue; the reference pays per-OP dispatch, SURVEY §3.2).
        ``unroll`` unrolls the scan body (fewer while-loop iterations at
        the cost of compile time; the runtime's per-iteration sync can
        dominate small step bodies).

        An epoch IS a window of length n_steps — this delegates to
        make_train_window."""
        return self.make_train_window(donate=donate, unroll=unroll,
                                      sentinel=sentinel,
                                      fingerprint=fingerprint)

    def make_train_window(self, accum_steps: int = 1, donate: bool = True,
                          unroll: int = 1, sentinel: bool = False,
                          tensorstats=None, fingerprint: bool = False):
        """Fused-window train step: K consecutive steps in ONE compiled
        dispatch — a lax.scan of the step body over a (K, batch, ...)
        stacked window of placeholders. Per-step losses come back as a
        device-side (K,) buffer, so listeners cost one transfer per
        flush, not one per step (autodiff/window.py owns the loop).

        The returned jitted fn specializes per window length K (the
        leading dim of the stacked placeholders), so ONE cache entry
        serves the full window and every ragged-tail bucket.

        With ``accum_steps > 1``, micro-batch gradients accumulate in the
        scan carry and the updater applies every ``accum_steps``-th
        micro-step on the AVERAGED gradient (effective batch =
        accum_steps * batch). The updater sees the update count
        (``iteration // accum_steps``) so schedules/bias-correction step
        per update, while RNG keys still fold the absolute micro-step
        iteration. Signature then gains an ``accum`` carry (zeros_like
        params) threaded between windows — an accumulation cycle may
        span window boundaries.

        ``sentinel=True`` (TrainingConfig.sentinel) adds ONE extra int32
        output: the absolute iteration of the first step in the window
        whose loss or gradients went non-finite (-1 = clean). The
        flag folds into the scan carry, so the window still syncs with
        the host only at its boundaries (faults/sentinels.py).

        ``tensorstats`` (TrainingConfig.tensorstats, monitor/
        tensorstats.py) folds the sampled per-layer stats into the scan
        carry the same way: TWO extra outputs — the stats pytree of the
        LAST sampled step in the window (zeros when none) and the int32
        iteration it was sampled at (-1 = no sample point). The host
        fetches both at flush boundaries in the same device_get burst
        as losses and sentinel verdicts; no per-step sync.

        ``fingerprint=True`` (TrainingConfig.fingerprints, integrity/
        fingerprint.py) appends ONE extra uint32 output: the bitwise
        word-sum digest of the window's final params + state vars +
        optimizer state — the silent-corruption sentinel. Computed once
        per window on the final carry (not per step), order-independent
        so the host can recompute it from captured bytes; parameter
        math is untouched.
        """
        ts = tensorstats
        if ts is not None:
            from deeplearning4j_tpu.monitor.tensorstats import (sample_mask,
                                                                zeros_stats)
            ts_n_layers = len(self.trainable_params())
        if fingerprint:
            from deeplearning4j_tpu.integrity.fingerprint import \
                tree_fingerprint as _tree_fp
        if accum_steps <= 1:
            step_body, loss_names = self._build_step_body(
                sentinel=sentinel, tensorstats=ts)

            def window_fn(params, svars, state, iteration, constants,
                          stacked_phv, base_key):
                def body(carry, phv):
                    # carry layout: p, sv, st, it [, bad] [, stats, at]
                    p, sv, st, it = carry[:4]
                    i = 4
                    if sentinel:
                        bad = carry[i]; i += 1
                    if ts is not None:
                        stats_c, stats_at = carry[i], carry[i + 1]
                    res = step_body(p, sv, st, it, constants, phv,
                                    base_key)
                    p, sv, st, it2, loss = res[:5]
                    out = [p, sv, st, it2]
                    r = 5
                    if sentinel:
                        ok = res[r]; r += 1
                        # absolute iteration of the FIRST bad step in
                        # the window; -1 = clean (faults/sentinels.py)
                        bad = jnp.where((bad < 0) & jnp.logical_not(ok),
                                        it, bad)
                        out.append(bad)
                    if ts is not None:
                        # keep the LAST sampled step's stats (step_body
                        # already gated the compute under lax.cond; the
                        # selects below touch only the small stat
                        # arrays)
                        take = sample_mask(it, ts)
                        stats_c = jax.tree_util.tree_map(
                            lambda n, o: jnp.where(take, n, o), res[r],
                            stats_c)
                        out.extend([stats_c,
                                    jnp.where(take, it, stats_at)])
                    return tuple(out), loss

                carry0 = [params, svars, state, iteration]
                if sentinel:
                    carry0.append(jnp.asarray(-1, jnp.int32))
                if ts is not None:
                    carry0.extend([zeros_stats(ts_n_layers, ts),
                                   jnp.asarray(-1, jnp.int32)])
                carry, losses = jax.lax.scan(body, tuple(carry0),
                                             stacked_phv, unroll=unroll)
                out = list(carry[:4]) + [losses] + list(carry[4:])
                if fingerprint:
                    # digest of the window's FINAL state, once per
                    # window on the post-scan carry — not per step
                    out.append(_tree_fp(carry[0], carry[1], carry[2]))
                return tuple(out)

            donate_args = (0, 1, 2, 3)
        else:
            grad_fn, apply_fn, loss_names = self._build_step_parts()
            n_accum = int(accum_steps)
            if ts is not None:
                stats_fn, _ = self._ts_stats_fn(ts)

            def window_fn(params, svars, state, accum, iteration, constants,
                          stacked_phv, base_key):
                def body(carry, phv):
                    # carry layout: p, sv, st, acc, it [, bad] [, stats,
                    # at]
                    p, sv, st, acc, it = carry[:5]
                    i = 5
                    if sentinel:
                        bad = carry[i]; i += 1
                    if ts is not None:
                        stats_c, stats_at = carry[i], carry[i + 1]
                    grads, sv, loss = grad_fn(p, sv, it, constants, phv,
                                              base_key)
                    acc = jax.tree_util.tree_map(jnp.add, acc, grads)

                    def do_apply(args):
                        p_, st_, acc_ = args
                        mean_g = jax.tree_util.tree_map(
                            lambda g: g / n_accum, acc_)
                        p_, st_ = apply_fn(p_, mean_g, st_, it // n_accum)
                        return (p_, st_, jax.tree_util.tree_map(
                            jnp.zeros_like, acc_))

                    p_pre = p
                    p, st, acc = jax.lax.cond(
                        (it + 1) % n_accum == 0, do_apply, lambda a: a,
                        (p, st, acc))
                    out = [p, sv, st, acc, it + 1]
                    if sentinel:
                        # the MICRO-step grads, pre-accumulation: the bad
                        # step is named, not its whole cycle
                        ok = self._sentinel_ok(loss, grads)
                        bad = jnp.where((bad < 0) & jnp.logical_not(ok),
                                        it, bad)
                        out.append(bad)
                    if ts is not None:
                        # sampling aligns to apply boundaries
                        # (sample_mask with accum_steps): the updates
                        # family always describes a real parameter
                        # delta, never a mid-cycle zero
                        take = sample_mask(it, ts, accum_steps=n_accum)
                        stats_c = jax.tree_util.tree_map(
                            lambda n, o: jnp.where(take, n, o),
                            stats_fn(take, p_pre, p, grads), stats_c)
                        out.extend([stats_c,
                                    jnp.where(take, it, stats_at)])
                    return tuple(out), loss

                carry0 = [params, svars, state, accum, iteration]
                if sentinel:
                    carry0.append(jnp.asarray(-1, jnp.int32))
                if ts is not None:
                    carry0.extend([zeros_stats(ts_n_layers, ts),
                                   jnp.asarray(-1, jnp.int32)])
                carry, losses = jax.lax.scan(body, tuple(carry0),
                                             stacked_phv, unroll=unroll)
                out = list(carry[:5]) + [losses] + list(carry[5:])
                if fingerprint:
                    # params/svars/updater state only: the accum carry
                    # is NOT part of the checkpoint schema, so it stays
                    # outside the digest too (autodiff/window.py)
                    out.append(_tree_fp(carry[0], carry[1], carry[2]))
                return tuple(out)

            donate_args = (0, 1, 2, 3, 4)
        cache_key = ("train_window", self._version, loss_names,
                     int(accum_steps), donate, int(unroll), bool(sentinel),
                     ts.key() if ts is not None else None,
                     bool(fingerprint))
        compiled = self._fn_cache.get(cache_key)
        if compiled is None:
            self._verbose_log(
                f"compiling fused-window step (graph v{self._version}, "
                f"accum_steps={accum_steps}, donate={donate})")
            compiled = AOTDispatch(
                jax.jit(window_fn,
                        donate_argnums=donate_args if donate else ()),
                ph_arg=6 if accum_steps > 1 else 5)
            self._fn_cache[cache_key] = compiled
        return compiled

    # ------------------------------------------------------------------
    # pre-compile static analysis (analyze/ — docs/static_analysis.md)
    def _maybe_analyze(self, has_listeners=None, context="fit"):
        """Run the static analyzer per ``TrainingConfig.analyze``
        (True = warn on error findings and proceed; "strict" = raise
        GraphAnalysisError BEFORE any compile; False = off). Cached on
        the graph version + fit context, so only the first fit of a
        given graph pays the walk — warm dispatches see a dict
        lookup."""
        tc = self.training_config
        mode = getattr(tc, "analyze", True) if tc is not None else False
        if not mode:
            return None
        # content fingerprint, not id(tc): the config is mutable and
        # the common pattern is in-place mutation (tc.sharding = ...,
        # fused_steps set by fit kwargs) — an identity key would serve
        # a stale clean report for exactly the knob that changed.
        # loss_variables rides the key too: set_loss_variables does
        # not bump the graph version.
        key = (self._version, has_listeners,
               tuple(self.loss_variables), self._tc_fingerprint(tc))
        if self._analysis_key == key and self.last_analysis is not None:
            report = self.last_analysis
            fresh = False
        else:
            from deeplearning4j_tpu.analyze import analyze_training
            # a cache hit keeps the first producer's context — only a
            # FRESH analysis stamps the entry point that ran it
            report = analyze_training(self, tc,
                                      has_listeners=has_listeners,
                                      device_count=jax.device_count(),
                                      context=context)
            self.last_analysis = report
            self._analysis_key = key
            fresh = True
            self._verbose_log(
                f"static analysis ({report.context}): "
                + ", ".join(f"{n} {s}"
                            for s, n in report.counts().items())
                + f" in {report.seconds:.3f}s")
        errs = report.errors()
        if errs:
            # strict enforcement applies on EVERY call — a cached
            # report of a still-broken graph must keep refusing, not
            # just the fit that first analyzed it
            if str(mode).lower() == "strict":
                report.raise_if_errors()
            if fresh:
                from deeplearning4j_tpu.analyze import \
                    GraphAnalysisWarning
                import warnings as _warnings
                _warnings.warn(
                    f"static analysis found {len(errs)} error(s) — "
                    f"the compile will likely fail; "
                    f"sd.last_analysis.render() has the located "
                    f"diagnostics (docs/static_analysis.md):\n"
                    + "\n".join(f.render() for f in errs[:5]),
                    GraphAnalysisWarning, stacklevel=3)
        return report

    @staticmethod
    def _tc_fingerprint(tc):
        """Cheap content key of the analysis-relevant TrainingConfig
        fields (NOT iteration/epoch counters, which advance every
        fit and would defeat the cache)."""
        import json as _json
        mp = getattr(tc, "mixed_precision", None)
        sh = getattr(tc, "sharding", None)
        if sh is not None:
            sh = (sh if hasattr(sh, "to_json") else sh.to_spec()) \
                .to_json()
        ts = getattr(tc, "tensorstats", None)
        return (tuple(getattr(tc, "data_set_feature_mapping", ()) or ()),
                tuple(getattr(tc, "data_set_label_mapping", ()) or ()),
                max(1, int(getattr(tc, "fused_steps", 1) or 1)),
                max(1, int(getattr(tc, "accum_steps", 1) or 1)),
                None if mp is None
                else tuple(sorted(mp.to_json().items())),
                None if sh is None
                else _json.dumps(sh, sort_keys=True, default=str),
                (ts.key() if hasattr(ts, "key") else bool(ts))
                if ts is not None else None,
                getattr(tc, "_chaos_spec", None) is not None,
                str(getattr(tc, "analyze", True)))

    # ------------------------------------------------------------------
    # AOT precompilation (compilecache/ — docs/cold_start.md)
    def _placeholder_specs(self, names=None, batch_size=None,
                           batch_shapes=None) -> Dict[str, Any]:
        """Abstract ``ShapeDtypeStruct``s for placeholders: declared
        shapes with ``-1`` batch dims resolved from ``batch_size``, or
        overridden wholesale per name via ``batch_shapes``."""
        specs = {}
        for pn in (names if names else self.placeholders()):
            v = self._vars[pn]
            shape = v._shape
            if batch_shapes and pn in batch_shapes:
                shape = tuple(int(d) for d in batch_shapes[pn])
            if shape is None:
                raise ValueError(
                    f"placeholder {pn!r} has no declared shape; pass "
                    f"batch_shapes={{{pn!r}: (...)}} to precompile")
            if any(d == -1 for d in shape):
                if batch_size is None:
                    raise ValueError(
                        f"placeholder {pn!r} has batch dims {shape}; pass "
                        f"batch_size= (or batch_shapes=) to precompile")
                shape = tuple(int(batch_size) if d == -1 else int(d)
                              for d in shape)
            specs[pn] = jax.ShapeDtypeStruct(
                tuple(shape), DataType.from_any(v.dtype).jnp)
        return specs

    def precompile(self, batch_size: Optional[int] = None,
                   batch_shapes: Optional[Dict[str, Sequence[int]]] = None,
                   epoch_steps: Optional[int] = None,
                   tiers: Optional[Sequence[str]] = None) -> dict:
        """AOT-compile the training programs from ABSTRACT shapes, before
        the first batch exists — ``fit()`` then dispatches straight into
        the prebuilt executables instead of paying XLA inside its first
        window (compilecache/, docs/cold_start.md).

        What gets built follows ``training_config``: with
        ``fused_steps``/``accum_steps`` > 1 the fused-window fn at the
        full window length K **plus every pow2 ragged-tail bucket**
        (all powers of two ≤ K-1 — the complete set the window executor
        can ever dispatch for full-size batches; log2(K)+1 shapes for a
        pow2 K); otherwise the per-step
        train fn, plus — when ``epoch_steps`` is given — the scanned
        whole-epoch fn. Placeholder batch dims resolve from
        ``batch_size``/``batch_shapes``. With a persistent compilation
        cache configured (``Environment compilation_cache_dir``), the
        builds themselves become cache hits on a warm restart, so
        restart-to-first-step approaches data-loading time.

        Returns a summary dict (targets built/reused, wall seconds, the
        process-wide backend-compile / cache-hit / cache-miss deltas
        this call produced, and ``programs``: a server warm-up's row for
        each target built). Precompiled executables live in the same
        version-keyed cache as lazy compiles: any graph mutation
        invalidates them, and unpredicted shapes (a ragged final BATCH)
        still compile lazily exactly as before — outputs are
        bit-identical either way (tests/test_cold_start.py).
        """
        import time as _time
        from deeplearning4j_tpu.compilecache import install_compile_watcher
        from deeplearning4j_tpu.environment import environment
        tc = self.training_config
        if tc is None:
            raise ValueError("precompile() needs sd.training_config "
                             "(use precompile_output() for inference "
                             "graphs)")
        environment().apply_compilation_cache()
        install_compile_watcher()
        # static analysis gates AOT builds too: a strict config fails
        # with named diagnostics before paying any lowering/compile
        # (listener presence unknown at precompile time)
        self._maybe_analyze(has_listeners=None, context="precompile")
        K = max(1, int(getattr(tc, "fused_steps", 1) or 1))
        A = max(1, int(getattr(tc, "accum_steps", 1) or 1))
        sentinel = bool(getattr(tc, "sentinel", False))
        # tensorstats rides the listener rail; precompile builds the
        # stats-enabled signature fit() will dispatch when listeners are
        # attached (a listener-free fused fit compiles the stats-free
        # variant lazily — docs/observability.md)
        ts = getattr(tc, "tensorstats", None)
        names = list(tc.data_set_feature_mapping) + \
            list(tc.data_set_label_mapping)
        ph = self._placeholder_specs(names or None, batch_size,
                                     batch_shapes)
        if tiers is None:
            tiers = ["window"] if (K > 1 or A > 1) else ["step"]
            if epoch_steps and K <= 1 and A <= 1:
                tiers.append("epoch")
        # a sharded fit feeds mesh-sharded batches; an executable
        # lowered from bare batch shapes would reject them. Under
        # TrainingConfig.sharding place the model NOW, as fit() will;
        # otherwise batches follow the placement a ParallelTrainer
        # already made. The strategy decides batch and window shardings
        # only — parameters, state and constants carry their own
        if getattr(tc, "sharding", None) is not None:
            from deeplearning4j_tpu.parallel.trainer import (
                resolve_strategy, shard_model)
            strategy = resolve_strategy(self, tc.sharding)
            shard_model(self, strategy)
        else:
            strategy = getattr(self, "_placement_strategy", None)
        if strategy is not None:
            ph = {n: jax.ShapeDtypeStruct(
                      s.shape, s.dtype,
                      sharding=strategy.batch_sharding(len(s.shape)))
                  for n, s in ph.items()}
        params_abs = _abstract(self.trainable_params())
        svars_abs = _abstract(self.state_vars_map())
        consts_abs = _abstract(self.constants_map())
        # updater-state leaves mirror their parameter's placement (what
        # updater.init computes from placed parameters)
        state_abs = {
            pn: jax.tree_util.tree_map(
                lambda l, _sh=params_abs[pn].sharding:
                jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=_sh),
                leaves)
            for pn, leaves in jax.eval_shape(tc.updater.init,
                                             params_abs).items()}
        it_abs = jax.ShapeDtypeStruct((), jnp.int32)
        key = jax.random.key(0)   # concrete — only its aval reaches lower()

        mark = COMPILE_STATS.mark()
        t0 = _time.perf_counter()
        built = reused = 0
        programs = []

        def _build(disp, args, sig, label, seen=None, steps=1):
            nonlocal built, reused
            if sig in disp.aot:
                reused += 1
                return
            at = COMPILE_STATS.mark()
            with COMPILE_STATS.precompile(label):
                disp.aot[sig] = disp.lower(*args).compile()
            # static memory & compute plan (monitor/memstats.py): the
            # executable exists — reading memory_analysis/cost_analysis
            # here is cheap observability (plan_analyze_seconds)
            memstats.capture_plan(label, sig, compiled=disp.aot[sig],
                                  steps=steps, graph=self)
            programs.append(COMPILE_STATS.program_row(label, at))
            if seen is not None:
                # pre-register the trace signature so the window
                # executor's compile accounting reports 0 for shapes
                # precompiled here
                seen.add(sig)
            built += 1
            self._verbose_log(f"precompiled {label}")

        def _window_args(k, with_accum):
            sphv = {n: jax.ShapeDtypeStruct(
                        (k,) + tuple(s.shape), s.dtype,
                        sharding=strategy.window_sharding(len(s.shape) + 1)
                        if strategy is not None else None)
                    for n, s in ph.items()}
            base = (params_abs, svars_abs, state_abs)
            if with_accum:
                base = base + (params_abs,)   # accum carry ≅ zeros_like
            return base + (it_abs, consts_abs, sphv, key), \
                ph_shape_sig(sphv)

        # donation is NOT a parameter here: fit() always builds its
        # dispatchers with the donate=True default, and the _fn_cache
        # key includes donate — a divergent value would AOT-compile
        # executables fit() never consults (silently useless work)
        if "step" in tiers:
            disp = self.make_train_step(sentinel=sentinel, tensorstats=ts)
            _build(disp, (params_abs, svars_abs, state_abs, it_abs,
                          consts_abs, ph, key),
                   ph_shape_sig(ph), "train_step", steps=1)
        fp_on = bool(getattr(tc, "fingerprints", False))
        if "window" in tiers:
            disp = self.make_train_window(accum_steps=A, sentinel=sentinel,
                                          tensorstats=ts,
                                          fingerprint=fp_on)
            from deeplearning4j_tpu.autodiff.window import window_trace_set
            seen = window_trace_set(self, A, sentinel,
                                    ts.key() if ts is not None else None,
                                    fp_on)
            # every pow2 the tail decomposition can emit: a ragged tail
            # of r < K steps uses buckets up to the largest pow2 ≤ r,
            # so cover all powers of two ≤ K-1 (for pow2 K this is the
            # log2(K)+1-shape set; a non-pow2 K needs one more)
            sizes = {K} | {1 << i for i in range((K - 1).bit_length())}
            for k in sorted(sizes, reverse=True):
                args, sig = _window_args(k, with_accum=A > 1)
                _build(disp, args, sig, f"window_k{k}", seen=seen,
                       steps=k)
        if "epoch" in tiers:
            if not epoch_steps:
                raise ValueError("the scanned-epoch tier needs "
                                 "epoch_steps= (batches per epoch)")
            unroll = int(getattr(tc, "scan_unroll", 1) or 1)
            disp = self.make_train_epoch(unroll=unroll, sentinel=sentinel,
                                         fingerprint=fp_on)
            args, sig = _window_args(int(epoch_steps), with_accum=False)
            _build(disp, args, sig, f"epoch_{epoch_steps}",
                   steps=int(epoch_steps))
        delta = COMPILE_STATS.delta(mark)
        info = {"compiled": built, "reused": reused,
                "seconds": round(_time.perf_counter() - t0, 4),
                "backend_compiles": delta["backend_compiles"],
                "cache_hits": delta["cache_hits"],
                "cache_misses": delta["cache_misses"],
                "programs": programs}
        # remembered so FaultTolerantFit can re-AOT after a retrace
        # (lr_rescale) instead of paying the compile inside the first
        # retry window (faults/recovery.py)
        self._precompile_spec = {"batch_size": batch_size,
                                 "batch_shapes": batch_shapes,
                                 "epoch_steps": epoch_steps,
                                 "tiers": tuple(tiers)}
        self.last_precompile = info
        self._verbose_log(f"precompile: {info}")
        return info

    def precompile_output(self, placeholders, outputs=None):
        """AOT-compile an inference program for the given placeholder
        shapes (``{name: shape tuple | ShapeDtypeStruct | array}``) and
        install it in the execution cache, so the matching ``output()``
        call runs without compiling — the serving warmup path
        (``ParallelInference(warmup_buckets=...)``). Idempotent per
        shape set; bit-identical to the lazily-compiled path."""
        from deeplearning4j_tpu.compilecache import install_compile_watcher
        from deeplearning4j_tpu.environment import environment
        environment().apply_compilation_cache()
        install_compile_watcher()
        if outputs is None:
            outputs = self.outputs()
        out_names = tuple(o.name if isinstance(o, SDVariable) else o
                          for o in outputs)
        ph_specs = {}
        for k, v in placeholders.items():
            name = k.name if isinstance(k, SDVariable) else k
            shape = tuple(int(d) for d in
                          (v.shape if hasattr(v, "shape") else v))
            # dtype from the DECLARED placeholder — the dtype
            # _prep_placeholders casts live inputs to — NOT from a
            # sample array: a float64 numpy sample would install the
            # executable under a cache key output()'s float32-cast
            # lookup never finds (warmup compiles, first request
            # compiles AGAIN)
            var = self._vars.get(name)
            if var is not None and var.dtype is not None:
                dt = DataType.from_any(var.dtype).jnp
            elif hasattr(v, "dtype"):
                dt = v.dtype
            else:
                raise KeyError(f"unknown placeholder {name!r} and no "
                               f"dtype on its sample value")
            ph_specs[name] = jax.ShapeDtypeStruct(
                shape, dt, sharding=None if isinstance(v, (tuple, list))
                else _placement(v))
        cache_key = self._output_cache_key(out_names, ph_specs)
        existing = self._fn_cache.get(cache_key)
        if isinstance(existing, _AOTOutput):
            return existing       # already an AOT executable
        fn = self._trace_fn(out_names)
        # lowered for where the arrays ARE: a model placed on a mesh
        # (ParallelTrainer.shard_params, a fit under
        # TrainingConfig.sharding) serves from mesh-committed arrays
        params_abs = _abstract({**self.trainable_params(),
                                **self.state_vars_map()})
        consts_abs = _abstract(self.constants_map())
        # per-bucket serving memory plan (monitor/memstats.py): label
        # carries the row count so /report can show the footprint
        # ladder across warmup buckets
        rows = next(iter(ph_specs.values())).shape
        label = f"output_b{rows[0] if rows else 1}"
        with COMPILE_STATS.precompile(label):
            compiled = _AOTOutput(
                jax.jit(fn).lower(params_abs, consts_abs, ph_specs,
                                  jax.random.key(0)).compile())
        memstats.capture_plan(label, ph_shape_sig(ph_specs),
                              compiled=compiled.compiled, graph=self)
        self._fn_cache[cache_key] = compiled
        return compiled

    def fit(self, dataset_iterator, epochs: int = 1, listeners=()):
        """Train (reference: SameDiff.fit(DataSetIterator, epochs),
        SameDiff.java:1833). ``dataset_iterator`` yields objects with
        ``features``/``labels`` (DataSet) or (features, labels) tuples.

        THREE execution tiers (this is a documented contract, not an
        internal detail — see docs/training_performance.md):

        - **scanned fast path** — zero listeners AND an iterator exposing
          ``stacked_batches`` (``DeviceCachedIterator``): the whole epoch
          compiles to ONE lax.scan dispatch. Use this for benchmarking
          and small models, where per-step dispatch latency dominates.
        - **fused windows** — ``TrainingConfig.fused_steps > 1`` (or
          ``accum_steps > 1``): K steps per compiled dispatch with
          device-buffered losses flushed to listeners at window
          boundaries and a background stager double-buffering the next
          window's host→HBM transfer. Works with listeners AND
          host-streaming iterators — the production default fast path.
        - **per-step path** — the legacy tier: one dispatch per step
          with burst loss delivery; every step pays the per-dispatch
          host cost.

        Environment verbose mode announces which tier each fit() took.
        """
        from deeplearning4j_tpu.autodiff.training import History, LossCurve
        tc = self.training_config
        if tc is None:
            raise ValueError("set sd.training_config = TrainingConfig(...) first")
        # on a graph not fitted at this version, what comes before the
        # first fit.stage is a phase of the start: fit.build, here and
        # around the epoch function's building (_fit_scanned_body)
        building = self._fit_built != self._version
        with COMPILE_STATS.span("fit.build", cat="train") if building \
                else _NOT_BUILDING:
            # the persistent compilation cache, placed where the
            # environment says (environment.py): a restarted fit pays
            # deserialisation, not XLA
            from deeplearning4j_tpu.environment import environment
            environment().apply_compilation_cache()
            # pre-compile static analysis (analyze/): named diagnostics
            # BEFORE tier selection, mesh placement, or any XLA compile —
            # strict mode raises here (docs/static_analysis.md)
            self._maybe_analyze(has_listeners=bool(listeners))
            # seekable streaming pipeline (datapipe/): register it on
            # the graph so checkpoint captures embed its PipelineState at
            # flush boundaries and anchor its pass starts to absolute
            # iterations — a mid-epoch restore then SEEKS instead of
            # replaying the pass (docs/data_pipeline.md). Cleared (None)
            # for plain iterators so a previous fit's pipeline can't leak
            # into this fit's snapshots.
            from deeplearning4j_tpu.datapipe.pipeline import find_pipeline
            _dp = find_pipeline(dataset_iterator)
            self._active_datapipe = _dp
            if _dp is not None and hasattr(_dp, "bind_iteration_source"):
                _dp.bind_iteration_source(
                    lambda: int(getattr(tc, "iteration_count", 0) or 0))
                _dp.bind_epoch_source(
                    lambda: int(getattr(tc, "epoch_count", 0) or 0))
            if getattr(tc, "sharding", None) is not None:
                # declarative mesh sharding: place params/state on the
                # spec's mesh and pre-shard batches BEFORE tier
                # selection, so every tier below (scanned / fused windows
                # / per-step) trains under the mesh. A ParallelTrainer
                # front end arrives here with an already-sharded iterator
                # (its explicit strategy wins) and this is a no-op.
                from deeplearning4j_tpu.parallel.trainer import \
                    ensure_sharded
                wrapped = ensure_sharded(self, tc.sharding,
                                         dataset_iterator)
                if wrapped is not dataset_iterator:
                    self._verbose_log(
                        f"fit: sharded over mesh "
                        f"{dict(wrapped._strategy.mesh.mesh.shape)} "
                        f"(TrainingConfig.sharding)")
                dataset_iterator = wrapped
        self._fit_built = self._version
        fused = max(1, int(getattr(tc, "fused_steps", 1) or 1))
        accum = max(1, int(getattr(tc, "accum_steps", 1) or 1))
        if not listeners and hasattr(dataset_iterator, "stacked_batches") \
                and fused <= 1 and accum <= 1:
            self._verbose_log("fit: scanned whole-epoch path "
                              "(one dispatch per epoch)")
            return self._fit_scanned(dataset_iterator, epochs, building)
        if fused > 1 or accum > 1:
            from deeplearning4j_tpu.autodiff.window import fit_windowed
            self._verbose_log(
                f"fit: fused-window path (fused_steps={fused}, "
                f"accum_steps={accum} — ceil(steps/{fused}) dispatches "
                f"per epoch)")
            return fit_windowed(self, dataset_iterator, epochs,
                                listeners=listeners)
        why = ("listeners need per-iteration scalars" if listeners
               else "iterator has no stacked_batches (use "
                    "DeviceCachedIterator for the scanned path)")
        self._verbose_log(f"fit: per-step path — {why} "
                          f"(set TrainingConfig.fused_steps>1 for fused "
                          f"windows)")
        use_sentinel = bool(getattr(tc, "sentinel", False))
        # in-graph tensor statistics need the listener rail to deliver
        # their records; a listener-free fit builds the stats-free step
        # (monitor/tensorstats.py)
        ts_cfg = getattr(tc, "tensorstats", None) if listeners else None
        step = self.make_train_step(sentinel=use_sentinel,
                                    tensorstats=ts_cfg)
        # bitwise state fingerprints (integrity/): the per-step tier
        # does not thread the digest through the step body — a tiny
        # separate digest program dispatches at the flush boundaries
        # (and once at fit end), fetched in the same burst
        fp_on = bool(getattr(tc, "fingerprints", False))
        self._device_fingerprint = None
        if fp_on:
            from deeplearning4j_tpu.integrity.fingerprint import \
                make_fingerprint_fn
            fp_fn = make_fingerprint_fn(self)
        from deeplearning4j_tpu.integrity.watchdog import guard as _wd_guard
        # step() donates param/state buffers; work on copies so the graph's
        # stored arrays stay valid for output()/save() during training
        params, svars, state, staged = stage_fit_state(self, tc)
        constants = self.constants_map()
        iteration = getattr(tc, "iteration_count", 0)
        it_dev = jnp.asarray(iteration, jnp.int32)    # one transfer per fit
        # the base seed is part of the resumable training state: per-step
        # keys are fold_in(key(base_seed), absolute_iteration), so a
        # checkpoint capturing this seed + the iteration counter resumes
        # the exact key sequence (checkpoint/state.py)
        self._fit_base_seed = self._seed
        base_key = jax.random.key(self._seed)          # one key per fit
        self._seed += 1
        history = History()
        deferred_means = []   # device scalars, fetched once at fit end
        for l in listeners:
            l.on_training_start(self)

        def _prep_batch(batch):
            if isinstance(batch, dict):
                ph = dict(batch)  # keys are placeholder names
            else:
                feats, labels = _split_batch(batch)
                ph = dict(zip(tc.data_set_feature_mapping, feats))
                ph.update(zip(tc.data_set_label_mapping, labels))
            return self._prep_placeholders(ph)

        # listeners get loss scalars in BURSTS: per-step losses stay on
        # device and one stacked fetch every flush_every steps feeds
        # iterations_done — the listener path no longer serializes the
        # dispatch pipeline with a float() per step (one round-trip per
        # burst instead of per iteration)
        flush_every = min((max(1, int(getattr(l, "frequency", 10)))
                           for l in listeners), default=0)
        # listeners that evaluate/save mid-epoch need current params in
        # self._arrays at each flush (params otherwise sync at epoch end)
        sync_params_on_flush = any(getattr(l, "needs_params", False)
                                   for l in listeners)

        if ts_cfg is not None:
            from deeplearning4j_tpu.monitor.tensorstats import (
                layer_names, sample_mask)
            ts_names = layer_names(params)
        else:
            ts_names = ()
        # memory-plan capture (monitor/memstats.py): with capture armed
        # a new shape's first compile goes through the AOT path so its
        # memory plan is observable; the sig work is skipped entirely
        # when the rail is off (the common case on this legacy tier)
        mem_on = memstats.plan_capture_enabled() or len(memstats.PLANS)
        mem_sigs: set = set()
        for epoch in range(epochs):
            epoch_losses = []
            epoch_oks: List[jax.Array] = []   # sentinel flags, device-side
            epoch_start_iter = iteration
            pending: List[Tuple[int, jax.Array]] = []
            pending_oks: List[Tuple[int, jax.Array]] = []
            pending_stats: List[Tuple[int, Any]] = []  # sampled stats

            def _flush(pending):
                if not pending:
                    return
                iters = [it for it, _ in pending]
                ts_recs: List[dict] = []
                with _tracer.span("flush", cat="train", steps=len(iters)):
                    # losses + sentinel verdicts + sampled tensorstats in
                    # ONE device->host transfer; verdicts are checked
                    # (and may raise) BEFORE the burst reaches listeners
                    oks_stack = jnp.stack([o for _, o in pending_oks]) \
                        if pending_oks else None
                    stats_burst = list(pending_stats)
                    pending_stats.clear()
                    fp_dev = fp_fn(params, svars, state) if fp_on else None
                    try:
                        with _wd_guard("flush"):
                            vals_arr, oks, stats_host, fp_host = \
                                jax.device_get(
                                    (jnp.stack([lv for _, lv in pending]),
                                     oks_stack,
                                     [s for _, s in stats_burst], fp_dev))
                    except Exception as e:
                        # async dispatch: an allocation failure often
                        # surfaces at the first sync, not the dispatch
                        memstats.reraise_oom(e, program="train_step",
                                             step=iters[-1], epoch=epoch)
                        raise
                    if fp_host is not None:
                        self._device_fingerprint = {
                            "iteration": iters[-1] + 1,
                            "fp": int(fp_host)}
                    if oks is not None:
                        from deeplearning4j_tpu.faults.sentinels import \
                            check_ok_flags
                        ok_iters = [it for it, _ in pending_oks]
                        pending_oks.clear()
                        check_ok_flags(np.asarray(oks), ok_iters, epoch,
                                       epoch_start_iter)
                    if stats_burst:
                        from deeplearning4j_tpu.monitor.tensorstats import \
                            build_record
                        ts_recs = [
                            build_record(ts_names, s, it_, epoch, ts_cfg)
                            for (it_, _), s in zip(stats_burst,
                                                   stats_host)]
                vals = [float(v) for v in vals_arr]
                epoch_losses.extend(vals)
                if sync_params_on_flush:
                    # the FULL training state, not just params: a
                    # checkpoint taken at this flush must capture updater
                    # state and the iteration counter too (mid-epoch
                    # snapshots resume bit-exact, checkpoint/listener.py)
                    for n, p in {**params, **svars}.items():
                        self._arrays[n] = jnp.copy(p)
                    self._updater_state = jax.tree_util.tree_map(
                        jnp.copy, state)
                    tc.iteration_count = iters[-1] + 1
                if self._nan_panic_active(tc):
                    for it, v in zip(iters, vals):
                        if not np.isfinite(v):
                            raise NumericsException(
                                f"non-finite loss {v} at iteration {it} "
                                f"(nan_panic); localize the producing op "
                                f"with sd.exec_debug(placeholders)")
                for l in listeners:
                    l.iterations_done(self, epoch, iters, vals)
                if ts_recs:
                    for l in listeners:
                        hook = getattr(l, "tensorstats_done", None)
                        if hook is not None:
                            hook(self, epoch, ts_recs)
                pending.clear()

            for l in listeners:
                l.on_epoch_start(self, epoch)
            if hasattr(dataset_iterator, "reset"):
                dataset_iterator.reset()
            # one-batch-ahead prefetch: enqueue the NEXT batch's host→HBM
            # transfer before stepping on the current one, so transfers
            # overlap compute (reference: AsyncDataSetIterator's prefetch
            # thread, MultiLayerNetwork.java:1678)
            batch_iter = iter(dataset_iterator)
            ph = next((_prep_batch(b) for b in batch_iter), None)
            while ph is not None:
                # one "step" span per dispatch (the per-step tier's
                # window of k=1) with data_wait/dispatch children;
                # listener flushes record outside it (monitor/steptime)
                with _tracer.span("step", cat="train", k=1,
                                  iteration=iteration):
                    with _tracer.span("data_wait", cat="train"):
                        nxt = next((_prep_batch(b) for b in batch_iter),
                                   None)
                    for l in listeners:
                        if getattr(l, "batch_size", -1) is None:
                            l.batch_size = next(iter(ph.values())).shape[0]
                    with _tracer.span("dispatch", cat="train"):
                        if mem_on:
                            step_sig = ph_shape_sig(ph)
                            if step_sig not in mem_sigs:
                                mem_sigs.add(step_sig)
                                memstats.promote_dispatch(
                                    step, (params, svars, state, it_dev,
                                           constants, ph, base_key),
                                    step_sig, "train_step", steps=1,
                                    graph=self)
                            memstats.note_dispatch(step_sig, steps=1)
                        try:
                            with _wd_guard("step_dispatch"):
                                res = step(params, svars, state, it_dev,
                                           constants, ph, base_key)
                        except Exception as e:
                            memstats.reraise_oom(e, program="train_step",
                                                 step=iteration,
                                                 epoch=epoch)
                            raise
                        params, svars, state, it_dev, loss_val = res[:5]
                        r = 5
                        if use_sentinel:
                            ok = res[r]; r += 1
                            if listeners:
                                pending_oks.append((iteration, ok))
                            else:
                                epoch_oks.append(ok)
                        if ts_cfg is not None and \
                                sample_mask(iteration, ts_cfg):
                            # host-side gate is THE traced predicate on
                            # a host int — the same construction, so it
                            # can never disagree with the in-graph
                            # lax.cond (unsampled steps return zeros
                            # that are simply never retained)
                            pending_stats.append((iteration, res[r]))
                    # without listeners, never force a device sync: losses
                    # stay async device scalars (a scalar fetch is a
                    # blocking host round trip)
                    if listeners:
                        pending.append((iteration, loss_val))
                    else:
                        epoch_losses.append(loss_val)
                    iteration += 1
                if pending and len(pending) >= flush_every:
                    _flush(pending)
                ph = nxt
            if epoch_oks:
                # sentinel without listeners: ONE stacked verdict fetch
                # per epoch (the rail's only extra sync on this path)
                from deeplearning4j_tpu.faults.sentinels import \
                    check_ok_flags
                oks = np.asarray(jnp.stack(epoch_oks))
                epoch_oks.clear()
                check_ok_flags(oks, range(epoch_start_iter,
                                          epoch_start_iter + len(oks)),
                               epoch, epoch_start_iter)
            if listeners:
                _flush(pending)
                mean_loss = float(np.mean(epoch_losses)) \
                    if epoch_losses else float("nan")
            elif self._nan_panic_active(tc):
                # panic mode: fetch the epoch mean NOW (one sync per epoch)
                mean_loss = float(jnp.mean(jnp.stack(epoch_losses))) \
                    if epoch_losses else float("nan")
                if epoch_losses and not np.isfinite(mean_loss):
                    raise NumericsException(
                        f"non-finite epoch-{epoch} mean loss {mean_loss} "
                        f"(nan_panic); localize with sd.exec_debug()")
            else:
                # mean on device, fetch deferred to fit end (one transfer)
                mean_loss = None
                deferred_means.append(
                    jnp.mean(jnp.stack(epoch_losses)) if epoch_losses
                    else jnp.asarray(float("nan")))
            history.add_epoch(epoch, mean_loss)
            tc.epoch_count = getattr(tc, "epoch_count", 0) + 1
            # dispatch accounting (ui/stats 'dispatch' records)
            self.last_fit_stats = {
                "tier": "per_step", "fused_steps": 1, "accum_steps": 1,
                "steps_per_epoch": iteration - epoch_start_iter,
                "dispatches_per_epoch": iteration - epoch_start_iter,
                "window_sizes": {1: iteration - epoch_start_iter},
                "window_compiles": 0, **staged}
            if listeners:
                # sync current params/state into the graph (copies — the next
                # step donates the working buffers) so listeners can save/eval
                for n, p in {**params, **svars}.items():
                    self._arrays[n] = jnp.copy(p)
                self._updater_state = jax.tree_util.tree_map(jnp.copy, state)
                tc.iteration_count = iteration
            stop = False
            for l in listeners:
                if l.on_epoch_end(self, epoch, mean_loss) is False:
                    stop = True
            if stop:
                break
        if deferred_means:
            fetched = np.asarray(jnp.stack(deferred_means))
            history.loss_curve.losses = [float(v) for v in fetched]
        # write trained params back into the graph
        for n, p in {**params, **svars}.items():
            self._arrays[n] = p
        self._updater_state = state
        tc.iteration_count = iteration
        if fp_on:
            # final boundary digest: a checkpoint captured after this
            # fit verifies its host bytes against it
            self._device_fingerprint = {
                "iteration": int(iteration),
                "fp": int(jax.device_get(fp_fn(params, svars, state)))}
        for l in listeners:
            l.on_training_end(self)
        return history

    def _fit_scanned(self, dataset_iterator, epochs: int, building: bool):
        """fit() fast path: epochs of lax.scan over device-stacked batches."""
        with _tracer.span("fit", cat="train", tier="scanned_epoch",
                          epochs=epochs) as fit_span:
            return self._fit_scanned_body(dataset_iterator, epochs, fit_span,
                                          building)

    def _fit_scanned_body(self, dataset_iterator, epochs: int, fit_span,
                          building: bool):
        from deeplearning4j_tpu.autodiff.training import History
        tc = self.training_config
        use_sentinel = bool(getattr(tc, "sentinel", False))
        fp_on = bool(getattr(tc, "fingerprints", False))
        self._device_fingerprint = None
        with COMPILE_STATS.span("fit.build", cat="train") if building \
                else _NOT_BUILDING:
            epoch_step = self.make_train_epoch(
                unroll=getattr(tc, "scan_unroll", 1) or 1,
                sentinel=use_sentinel, fingerprint=fp_on)
        with _tracer.span("fit.stage", cat="train") as stage_span:
            params, svars, state, staged = stage_fit_state(self, tc)
            stage_span.set(programs=staged["stage_programs"],
                           leaves=staged["stage_leaves"])
            constants = self.constants_map()
            iteration = getattr(tc, "iteration_count", 0)
            it_dev = jnp.asarray(iteration, jnp.int32)
            self._fit_base_seed = self._seed  # resumable RNG state, see fit()
            base_key = jax.random.key(self._seed)
            self._seed += 1
            feats, labels = dataset_iterator.stacked_batches()
            stacked = {}
            for name, arr in list(zip(tc.data_set_feature_mapping, feats)) + \
                    list(zip(tc.data_set_label_mapping, labels)):
                dt = self._vars[name].dtype if name in self._vars else None
                stacked[name] = _to_jnp(arr, dt)
        n_steps = next(iter(stacked.values())).shape[0]
        fit_span.set(steps=n_steps)
        # memory-plan capture + OOM forensics for the scanned tier: one
        # signature per fit, promoted to an AOT compile when capture is
        # armed so /report can show the whole-epoch program's footprint
        scan_label = f"scanned_epoch_{n_steps}"
        scan_sig = ph_shape_sig(stacked)
        memstats.promote_dispatch(
            epoch_step, (params, svars, state, it_dev, constants,
                         stacked, base_key), scan_sig, scan_label,
            steps=n_steps, graph=self)
        memstats.note_dispatch(scan_sig, steps=n_steps)
        history = History()
        epoch_means = []
        last_fp = None                 # device uint32, fetched at fit end
        panic = self._nan_panic_active(tc)
        # the dispatch that traces, lowers and compiles or loads this
        # program is the last phase of the start; every one after it
        # opens the plain span
        dispatch_span = _steady_dispatch if scan_sig in epoch_step.ran \
            else _first_dispatch
        for epoch in range(epochs):
            try:
                with dispatch_span(epoch):
                    res = epoch_step(params, svars, state, it_dev,
                                     constants, stacked, base_key)
            except Exception as e:
                memstats.reraise_oom(e, program=scan_label,
                                     step=iteration, epoch=epoch)
                raise
            dispatch_span = _steady_dispatch
            # positional layout (make_train_window): p, sv, st, it,
            # losses [, bad] [, fp]
            params, svars, state, it_dev, losses = res[:5]
            r = 5
            if use_sentinel:
                with _tracer.span("fit.sync", cat="train"):
                    bad = int(res[r])  # one scalar sync per scanned epoch
                r += 1
                if bad >= 0:
                    from deeplearning4j_tpu.faults.sentinels import \
                        raise_diverged
                    # epoch = this fit's loop index, matching the
                    # per-step and windowed tiers' provenance
                    raise_diverged(bad, epoch, iteration)
            if fp_on:
                last_fp = res[r]
                r += 1
            m = jnp.mean(losses)
            if panic:
                with _tracer.span("fit.sync", cat="train"):
                    mean = float(m)
                if not np.isfinite(mean):
                    raise NumericsException(
                        f"non-finite mean loss {mean} in scanned epoch "
                        f"(nan_panic); localize with sd.exec_debug()")
            epoch_means.append(m)
            iteration += n_steps
            self.last_fit_stats = {
                "tier": "scanned_epoch", "fused_steps": n_steps,
                "accum_steps": 1, "steps_per_epoch": n_steps,
                "dispatches_per_epoch": 1, "window_sizes": {n_steps: 1},
                "window_compiles": 0, **staged}
        epoch_step.ran.add(scan_sig)
        # ONE device fetch for all epoch means at fit end
        with _tracer.span("fit.sync", cat="train"):
            fetched = np.asarray(jnp.stack(epoch_means))
            if last_fp is not None:
                last_fp = int(last_fp)
        with _tracer.span("fit.commit", cat="train"):
            for e in range(epochs):
                history.add_epoch(e, float(fetched[e]))
            for n, p in {**params, **svars}.items():
                self._arrays[n] = p
            self._updater_state = state
            tc.iteration_count = iteration
            tc.epoch_count = getattr(tc, "epoch_count", 0) + epochs
            if last_fp is not None:
                # the boundary digest a checkpoint capture after this fit
                # verifies against (integrity/fingerprint.py)
                self._device_fingerprint = {"iteration": int(iteration),
                                            "fp": last_fp}
        return history

    # ------------------------------------------------------------------
    # serde (reference: SameDiff.save/fromFlatBuffers, SameDiff.java:1583)
    def save(self, path, include_updater_state: bool = True):
        from deeplearning4j_tpu.autodiff import serde
        serde.save(self, path, include_updater_state)

    @staticmethod
    def load(path) -> "SameDiff":
        from deeplearning4j_tpu.autodiff import serde
        return serde.load(path)

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    def summary(self) -> str:
        lines = [f"SameDiff: {len(self._vars)} variables, {len(self._ops)} ops"]
        for n, v in self._vars.items():
            if v.var_type != VariableType.ARRAY:
                lines.append(f"  {v.var_type.value:<11} {n:<24} {v._shape}")
        for node in self.ops():
            lines.append(f"  OP {node.op:<20} {node.inputs} -> {node.outputs}")
        return "\n".join(lines)


def _split_batch(batch):
    """Accept DataSet-like or (features, labels) batches (dict batches are
    handled in fit() — their keys are placeholder names directly)."""
    if hasattr(batch, "features") and hasattr(batch, "labels"):
        f, l = batch.features, batch.labels
        feats = f if isinstance(f, (list, tuple)) else [f]
        labels = l if isinstance(l, (list, tuple)) else [l]
        return feats, labels
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        f, l = batch
        feats = f if isinstance(f, (list, tuple)) else [f]
        labels = l if isinstance(l, (list, tuple)) else [l]
        return feats, labels
    raise TypeError(f"cannot interpret batch of type {type(batch)}")
