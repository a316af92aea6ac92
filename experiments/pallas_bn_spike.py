"""Spike: Pallas fused BN(+ReLU) backward vs XLA's jax.grad fusions.

"A Pallas fused conv-epilogue/BN kernel" was once named as the next
lever for ResNet-50. This measures whether a hand-written two-phase
Pallas backward (the pass-count-optimal schedule: reduction pass over
(x, dy) then dx pass over (x, dy)) beats the fusions XLA derives from
jax.grad of the same chain, on the real chip at ResNet stage shapes.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl


def bn_relu_ref(x, gamma, beta, eps=1e-5):
    """The exact forward the framework runs (batchnorm_train + relu),
    NHWC, f32 stats, bf16 tensor math."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2))
    m2 = jnp.mean(xf * xf, axis=(0, 1, 2))
    var = jnp.maximum(m2 - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    a = (gamma * inv).astype(x.dtype)
    b = (beta - gamma * inv * mean).astype(x.dtype)
    return jax.nn.relu(x * a + b)


def loss_ref(x, gamma, beta, dy):
    return jnp.sum(bn_relu_ref(x, gamma, beta) * dy)


# ---------------------------------------------------------------------------
# Pallas two-phase backward
# ---------------------------------------------------------------------------

def _phase1_kernel(x_ref, dy_ref, a_ref, b_ref, s1_ref, s2_ref):
    """Partial sums per row-tile: s1 = sum(dz), s2 = sum(dz * x) with
    dz = dy * (a*x+b > 0). (Reduction over x directly; the xhat algebra
    folds into the combine step on the host side.)"""
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    z = x * a_ref[:] + b_ref[:]
    dz = jnp.where(z > 0, dy, 0.0)
    # (8, C) output block to satisfy TPU tiling; row 0 carries the sum
    s1_ref[:] = jnp.broadcast_to(jnp.sum(dz, axis=0, keepdims=True),
                                 s1_ref.shape)
    s2_ref[:] = jnp.broadcast_to(jnp.sum(dz * x, axis=0, keepdims=True),
                                 s2_ref.shape)


def _phase2_kernel(x_ref, dy_ref, a_ref, b_ref, c1_ref, c2_ref, g_ref,
                   dx_ref):
    """dx = g * (dz - c1 - x * c2) per row-tile (c1/c2 precombined)."""
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    z = x * a_ref[:] + b_ref[:]
    dz = jnp.where(z > 0, dy, 0.0)
    dx_ref[:] = (g_ref[:] * (dz - c1_ref[:] - x * c2_ref[:])
                 ).astype(dx_ref.dtype)


def bn_relu_bwd_pallas(x2d, dy2d, gamma, beta, mean, inv, eps=1e-5,
                       row_tile=2048):
    """x2d, dy2d: (R, C) bf16 flattened NHWC. Returns (dx, dgamma, dbeta).

    Derivation: with xhat=(x-mean)*inv, dgamma=sum(dz*xhat),
    dbeta=sum(dz), dx = gamma*inv*(dz - E[dz] - xhat*E[dz*xhat]).
    Rewriting sums over x (not xhat): sum(dz*xhat) = inv*(sum(dz*x) -
    mean*sum(dz)), and dx = g*(dz - c1 - x*c2) with
    g = gamma*inv, c2 = inv^2 * E[dz*xhat-ish] ... expanded below.
    """
    R, C = x2d.shape
    # tile must divide R exactly — a floor division would silently drop
    # tail rows from the reductions and leave dx's tail uninitialized
    while R % row_tile and row_tile > 8:
        row_tile //= 2
    if R % row_tile:
        raise ValueError(f"R={R} has no power-of-two row tile >= 8")
    n_tiles = R // row_tile
    a = (gamma * inv).astype(jnp.float32)[None, :]
    b = (beta - gamma * inv * mean).astype(jnp.float32)[None, :]

    s1, s2 = pl.pallas_call(
        _phase1_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((row_tile, C), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((8, C), lambda i: (i, 0)),
            pl.BlockSpec((8, C), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * 8, C), jnp.float32),
            jax.ShapeDtypeStruct((n_tiles * 8, C), jnp.float32),
        ],
    )(x2d, dy2d, a, b)
    sum_dz = s1[::8].sum(0)                     # (C,)
    sum_dzx = s2[::8].sum(0)
    sum_dzxhat = inv * (sum_dzx - mean * sum_dz)
    dgamma = sum_dzxhat
    dbeta = sum_dz
    # dx = gamma*inv*(dz - sum_dz/R - xhat * sum_dzxhat/R)
    #    = g*dz - g*(sum_dz/R - mean*inv*sum_dzxhat/R) - g*inv*sum_dzxhat/R * x
    g = (gamma * inv).astype(jnp.float32)
    c2 = (inv * sum_dzxhat / R)
    c1 = (sum_dz / R - mean * c2)
    dx = pl.pallas_call(
        _phase2_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((row_tile, C), lambda i: (i, 0)),
            pl.BlockSpec((row_tile, C), lambda i: (i, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), x2d.dtype),
    )(x2d, dy2d, a, b, c1[None, :], c2[None, :], g[None, :])
    return dx, dgamma, dbeta


def main():
    shapes = [
        (128, 56, 56, 256),
        (128, 28, 28, 512),
        (128, 56, 56, 64),
    ]
    for (N, H, W, C) in shapes:
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(N, H, W, C)), jnp.bfloat16)
        dy = jnp.asarray(rng.normal(size=(N, H, W, C)), jnp.bfloat16)
        gamma = jnp.asarray(rng.normal(size=(C,)) * 0.1 + 1.0, jnp.float32)
        beta = jnp.asarray(rng.normal(size=(C,)) * 0.1, jnp.float32)

        # XLA backward-only via vjp (residuals precomputed)
        @jax.jit
        def xla_bwd(x, gamma, beta, dy):
            _, f_vjp = jax.vjp(lambda xx, g, b: bn_relu_ref(xx, g, b),
                               x, gamma, beta)
            return f_vjp(dy)
        grad_fn = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))
        dx_ref, dg_ref, db_ref = grad_fn(x, gamma, beta, dy)
        jax.block_until_ready(dx_ref)

        # pallas
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=(0, 1, 2))
        var = jnp.maximum(jnp.mean(xf * xf, (0, 1, 2)) - mean ** 2, 0.0)
        inv = lax.rsqrt(var + 1e-5)
        R = N * H * W
        x2d = x.reshape(R, C)
        dy2d = dy.reshape(R, C)
        pal = jax.jit(functools.partial(bn_relu_bwd_pallas))
        dx_p, dg_p, db_p = pal(x2d, dy2d, gamma, beta, mean, inv)
        jax.block_until_ready(dx_p)

        d_ref = np.asarray(dx_ref, np.float32).reshape(-1)
        d_pal = np.asarray(dx_p, np.float32).reshape(-1)
        mismatch = np.mean(np.abs(d_ref - d_pal) > 0.05)
        err_g = np.max(np.abs(np.asarray(dg_p) - np.asarray(dg_ref))
                       / (np.abs(np.asarray(dg_ref)) + 1.0))
        print(f"shape {N}x{H}x{W}x{C}: dx mismatch frac={mismatch:.5f} "
              f"(bf16 relu-mask edges) rel|dgamma err|={err_g:.4f}")

        def t(f, *args):
            jax.block_until_ready(f(*args))
            best = 1e9
            for _ in range(5):
                t0 = time.perf_counter()
                r = f(*args)
                jax.block_until_ready(r)
                best = min(best, time.perf_counter() - t0)
            return best * 1000

        ms_full = t(grad_fn, x, gamma, beta, dy)
        ms_xla_bwd = t(xla_bwd, x, gamma, beta, dy)
        ms_pal = t(pal, x2d, dy2d, gamma, beta, mean, inv)
        gb = (5 * R * C * 2) / 1e9        # 4 reads + 1 write, bf16
        print(f"  XLA fwd+bwd: {ms_full:.2f} ms | XLA bwd-only: "
              f"{ms_xla_bwd:.2f} ms | pallas bwd-only: {ms_pal:.2f} ms | "
              f"bwd roofline {1000*gb/819:.2f} ms ({gb:.2f} GB @819GB/s)")


if __name__ == "__main__":
    main()
