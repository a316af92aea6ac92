"""Static ndarray factory — the ``Nd4j`` equivalent.

Reference parity: org.nd4j.linalg.factory.Nd4j (nd4j-api
.../linalg/factory/Nd4j.java — create/zeros/ones/rand/randn/linspace/eye/
concat/stack/...). The reference routes creation through a backend-selected
NDArrayFactory; here every constructor materialises a ``jax.Array`` on the
default device, and the global RNG mirrors ``Nd4j.getRandom()``'s settable
seed via a counter-based (threefry) key that splits per draw.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ndarray.dtype import DataType, default_float
from deeplearning4j_tpu.ndarray.ndarray import NDArray, _as_jax


# ----------------------------------------------------------------------
# global RNG (reference: Nd4j.getRandom(), nd4j NativeRandom/RandomGenerator —
# libnd4j graph/RandomGenerator.h is counter-based; threefry is the TPU-native
# counter-based equivalent)
# ----------------------------------------------------------------------
class Random:
    """Stateful wrapper over jax's splittable PRNG.

    The key is built on the first ``next_key()``, not here:
    ``jax.random.key`` initialises the backend, and the module-level
    instance below must not take the chip from a process that only
    imports the package (a chip belongs to one process).
    """

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = int(seed)
        self._key: Optional[jax.Array] = None

    def set_seed(self, seed: int) -> None:
        with self._lock:
            self._seed = int(seed)
            self._key = None

    setSeed = set_seed

    def next_key(self) -> jax.Array:
        with self._lock:
            if self._key is None:
                self._key = jax.random.key(self._seed)
            self._key, sub = jax.random.split(self._key)
            return sub


_RANDOM = Random(np.random.SeedSequence().entropy % (2**31))


def get_random() -> Random:
    return _RANDOM


getRandom = get_random


def _dt(dtype) -> jnp.dtype:
    return DataType.from_any(dtype).jnp if dtype is not None else default_float().jnp


# ----------------------------------------------------------------------
# creation
# ----------------------------------------------------------------------
def create(data=None, shape=None, dtype=None) -> NDArray:
    """Nd4j.create(...) — from nested lists/numpy, or uninitialised by shape."""
    if data is None:
        if shape is None:
            raise ValueError("create() needs data or shape")
        return NDArray(jnp.zeros(tuple(shape), dtype=_dt(dtype)))
    if shape is not None:
        arr = jnp.asarray(data, dtype=DataType.from_any(dtype).jnp if dtype is not None else None)
        if dtype is None and arr.dtype == jnp.float64:
            arr = arr.astype(default_float().jnp)
        return NDArray(arr.reshape(tuple(shape)))
    arr = _as_jax(data)
    if dtype is not None:
        arr = arr.astype(_dt(dtype))
    elif arr.dtype == jnp.float64:
        arr = arr.astype(default_float().jnp)
    return NDArray(arr)


def zeros(*shape, dtype=None) -> NDArray:
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    return NDArray(jnp.zeros(shape, dtype=_dt(dtype)))


def ones(*shape, dtype=None) -> NDArray:
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    return NDArray(jnp.ones(shape, dtype=_dt(dtype)))


def zeros_like(arr) -> NDArray:
    return NDArray(jnp.zeros_like(_as_jax(arr)))


def ones_like(arr) -> NDArray:
    return NDArray(jnp.ones_like(_as_jax(arr)))


def value_array_of(shape, value, dtype=None) -> NDArray:
    return NDArray(jnp.full(tuple(shape), value, dtype=_dt(dtype)))


valueArrayOf = value_array_of


def scalar(value, dtype=None) -> NDArray:
    return NDArray(jnp.asarray(value, dtype=_dt(dtype) if dtype is not None or not isinstance(value, (bool, int)) else None))


def eye(n: int, dtype=None) -> NDArray:
    return NDArray(jnp.eye(n, dtype=_dt(dtype)))


def linspace(start, stop, num, dtype=None) -> NDArray:
    return NDArray(jnp.linspace(start, stop, int(num), dtype=_dt(dtype)))


def arange(*args, dtype=None) -> NDArray:
    return NDArray(jnp.arange(*args, dtype=DataType.from_any(dtype).jnp if dtype else None))


def empty(dtype=None) -> NDArray:
    return NDArray(jnp.zeros((0,), dtype=_dt(dtype)))


# ----------------------------------------------------------------------
# random  (reference: Nd4j.rand / randn / Nd4j.getExecutioner random ops)
# ----------------------------------------------------------------------
def rand(*shape, dtype=None, seed: Optional[int] = None) -> NDArray:
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    key = jax.random.key(seed) if seed is not None else _RANDOM.next_key()
    return NDArray(jax.random.uniform(key, shape, dtype=_dt(dtype)))


def randn(*shape, dtype=None, seed: Optional[int] = None) -> NDArray:
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    key = jax.random.key(seed) if seed is not None else _RANDOM.next_key()
    return NDArray(jax.random.normal(key, shape, dtype=_dt(dtype)))


def rand_int(maxval, shape, minval=0, seed: Optional[int] = None) -> NDArray:
    key = jax.random.key(seed) if seed is not None else _RANDOM.next_key()
    return NDArray(jax.random.randint(key, tuple(shape), minval, maxval, dtype=jnp.int32))


def bernoulli(p, shape, dtype=None, seed: Optional[int] = None) -> NDArray:
    key = jax.random.key(seed) if seed is not None else _RANDOM.next_key()
    return NDArray(jax.random.bernoulli(key, p, tuple(shape)).astype(_dt(dtype)))


def shuffle(arr: NDArray, seed: Optional[int] = None) -> NDArray:
    """In-place first-axis shuffle (reference: Nd4j.shuffle mutates its arg)."""
    key = jax.random.key(seed) if seed is not None else _RANDOM.next_key()
    shuffled = jax.random.permutation(key, _as_jax(arr), axis=0)
    if isinstance(arr, NDArray):
        arr._set_data(shuffled)
        return arr
    return NDArray(shuffled)


# ----------------------------------------------------------------------
# combination / splitting
# ----------------------------------------------------------------------
def concat(dimension: int, *arrs) -> NDArray:
    if len(arrs) == 1 and isinstance(arrs[0], (list, tuple)):
        arrs = tuple(arrs[0])
    return NDArray(jnp.concatenate([_as_jax(a) for a in arrs], axis=dimension))


def hstack(*arrs) -> NDArray:
    if len(arrs) == 1 and isinstance(arrs[0], (list, tuple)):
        arrs = tuple(arrs[0])
    return NDArray(jnp.hstack([_as_jax(a) for a in arrs]))


def vstack(*arrs) -> NDArray:
    if len(arrs) == 1 and isinstance(arrs[0], (list, tuple)):
        arrs = tuple(arrs[0])
    return NDArray(jnp.vstack([_as_jax(a) for a in arrs]))


def stack(dimension: int, *arrs) -> NDArray:
    if len(arrs) == 1 and isinstance(arrs[0], (list, tuple)):
        arrs = tuple(arrs[0])
    return NDArray(jnp.stack([_as_jax(a) for a in arrs], axis=dimension))


def split(arr, num_or_sections, axis=0):
    return [NDArray(a) for a in jnp.split(_as_jax(arr), num_or_sections, axis=axis)]


def tile(arr, reps) -> NDArray:
    return NDArray(jnp.tile(_as_jax(arr), reps))


def repeat(arr, repeats, axis=None) -> NDArray:
    return NDArray(jnp.repeat(_as_jax(arr), repeats, axis=axis))


def where(cond, x=None, y=None):
    if x is None:
        return [NDArray(w) for w in jnp.where(_as_jax(cond))]
    return NDArray(jnp.where(_as_jax(cond), _as_jax(x), _as_jax(y)))


def sort(arr, axis=-1, descending=False) -> NDArray:
    s = jnp.sort(_as_jax(arr), axis=axis)
    return NDArray(jnp.flip(s, axis=axis) if descending else s)


def argsort(arr, axis=-1) -> NDArray:
    return NDArray(jnp.argsort(_as_jax(arr), axis=axis))


# ----------------------------------------------------------------------
# linalg conveniences (reference: Nd4j.gemm / matmul)
# ----------------------------------------------------------------------
def gemm(a, b, transpose_a=False, transpose_b=False, alpha=1.0, beta=0.0, c=None) -> NDArray:
    A = _as_jax(a).T if transpose_a else _as_jax(a)
    B = _as_jax(b).T if transpose_b else _as_jax(b)
    r = alpha * jnp.matmul(A, B)
    if c is not None and beta != 0.0:
        r = r + beta * _as_jax(c)
    return NDArray(r)


def matmul(a, b) -> NDArray:
    return NDArray(jnp.matmul(_as_jax(a), _as_jax(b)))


def exec_op(op_name: str, *args, **kwargs):
    """Execute a registered named op (reference: Nd4j.exec(DynamicCustomOp))."""
    try:
        from deeplearning4j_tpu.ops.registry import exec_op as _exec
    except ImportError as e:  # pragma: no cover
        raise NotImplementedError(
            "the ops registry is not available in this build") from e
    return _exec(op_name, *args, **kwargs)


# camelCase aliases
zerosLike = zeros_like
onesLike = ones_like
randInt = rand_int
execOp = exec_op
