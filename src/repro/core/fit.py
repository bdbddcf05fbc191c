"""Batched nonlinear least squares for the exponential model — pure JAX.

scipy is unavailable offline, so Alg 2's ``Optimize`` step is a
Levenberg–Marquardt solver written against jnp and *vmapped over
workload groups*: the hundreds of per-(ii,oo) fits execute as one XLA
call instead of a Python loop of scipy ``curve_fit``s — a beyond-paper
speedup measured in benchmarks/run.py.

Bounds (a, b >= 0; c >= 0) are enforced by projection after each LM step,
matching the paper's "bounded constraints" note.  Masked padding rows
make ragged groups rectangular.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_LM_ITERS = 60
_MU0 = 1e-2
# float32 matmuls run as one bf16 pass on the TPU by default; the solve
# needs full float32 there to agree with the CPU (a no-op on the CPU)
_HIGHEST = jax.lax.Precision.HIGHEST


def _residuals(theta, x, y, w):
    a, b, c = theta[0], theta[1], theta[2]
    pred = c - a * jnp.exp(-b * x)
    return (pred - y) * w


def _solve3(A, b):
    """Closed-form 3x3 solve (adjugate / Cramer).

    Elementwise arithmetic only, so — unlike ``jnp.linalg.solve``, whose
    batched LU kernel rounds differently for different batch sizes — the
    result for one system is bit-identical whatever else shares the
    vmapped batch.  That invariance is what lets the incremental
    database refit (``update_exponential_database``) reproduce the full
    fit exactly while solving only a subset of the groups.
    """
    c00 = A[1, 1] * A[2, 2] - A[1, 2] * A[2, 1]
    c01 = A[1, 2] * A[2, 0] - A[1, 0] * A[2, 2]
    c02 = A[1, 0] * A[2, 1] - A[1, 1] * A[2, 0]
    det = A[0, 0] * c00 + A[0, 1] * c01 + A[0, 2] * c02
    c10 = A[0, 2] * A[2, 1] - A[0, 1] * A[2, 2]
    c11 = A[0, 0] * A[2, 2] - A[0, 2] * A[2, 0]
    c12 = A[0, 1] * A[2, 0] - A[0, 0] * A[2, 1]
    c20 = A[0, 1] * A[1, 2] - A[0, 2] * A[1, 1]
    c21 = A[0, 2] * A[1, 0] - A[0, 0] * A[1, 2]
    c22 = A[0, 0] * A[1, 1] - A[0, 1] * A[1, 0]
    adj = jnp.array([[c00, c10, c20], [c01, c11, c21], [c02, c12, c22]])
    safe = jnp.where(det == 0, 1.0, det)
    return jnp.where(det == 0, jnp.zeros(3),
                     jnp.matmul(adj, b, precision=_HIGHEST) / safe)


def _lm_step(theta, mu, x, y, w):
    r = _residuals(theta, x, y, w)
    # analytic Jacobian of residuals wrt (a, b, c)
    a, b = theta[0], theta[1]
    e = jnp.exp(-b * x)
    J = jnp.stack([-e * w, a * x * e * w, jnp.ones_like(x) * w], axis=1)
    JtJ = jnp.matmul(J.T, J, precision=_HIGHEST)
    Jtr = jnp.matmul(J.T, r, precision=_HIGHEST)
    loss = jnp.sum(r * r)

    def solve(m):
        A = JtJ + m * jnp.eye(3, dtype=JtJ.dtype)
        return _solve3(A, -Jtr)

    delta = solve(mu)
    new_theta = theta + delta
    # projected bounds: a,b,c >= tiny (b also capped to avoid overflow)
    new_theta = jnp.stack([
        jnp.maximum(new_theta[0], 1e-8),
        jnp.clip(new_theta[1], 1e-8, 50.0),
        jnp.maximum(new_theta[2], 0.0)])
    new_loss = jnp.sum(_residuals(new_theta, x, y, w) ** 2)
    improved = new_loss < loss
    theta = jnp.where(improved, new_theta, theta)
    mu = jnp.where(improved, mu * 0.5, mu * 2.5)
    mu = jnp.clip(mu, 1e-10, 1e8)
    return theta, mu


@functools.partial(jax.jit, static_argnames=())
def _fit_one(theta0, x, y, w):
    def body(carry, _):
        theta, mu = carry
        theta, mu = _lm_step(theta, mu, x, y, w)
        return (theta, mu), None

    (theta, _), _ = jax.lax.scan(
        body, (theta0, jnp.asarray(_MU0, theta0.dtype)), None,
        length=_LM_ITERS)
    return theta


_fit_batch = jax.jit(jax.vmap(_fit_one))


def _pow2(n: int, lo: int = 1) -> int:
    """Next power of two >= n — the shape-bucketing the solvers use so
    growing online datasets reuse compiles instead of triggering a fresh
    XLA build every epoch."""
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


def fit_exponential_groups(groups, pad_to: int = 0):
    """Fit (a,b,c) for a list of (bb, thpt, theta0) ragged groups.

    Returns (G, 3) float64 array.  Groups are padded to the max length and
    solved in one vmapped LM call.

    Shapes are bucketed: the group dimension pads to the next power of
    two with all-zero dummy groups (bit-exact no-ops — the per-group
    solve is batch-invariant, see ``_solve3``) and the row dimension to
    the next power of two above ``max(group sizes, pad_to)``, so
    repeated fits over growing data hit the jit cache instead of
    recompiling.  ``pad_to`` additionally lets an incremental refit of a
    *subset* of groups (``update_exponential_database``) reproduce the
    full batch's row padding — and therefore its float32 reduction order
    — bit-for-bit.
    """
    if not groups:
        return np.zeros((0, 3))
    maxn = _pow2(max(max(len(g[0]) for g in groups), pad_to, 1))
    G = len(groups)
    Gp = _pow2(G, lo=2)     # lo=2: a batch of one fuses differently
    X = np.zeros((Gp, maxn), np.float32)
    Y = np.zeros((Gp, maxn), np.float32)
    W = np.zeros((Gp, maxn), np.float32)
    T0 = np.zeros((Gp, 3), np.float32)
    scale = np.zeros(G, np.float64)
    for i, (bb, thpt, theta0) in enumerate(groups):
        n = len(bb)
        # normalize thpt per group for conditioning; rescale after
        s = max(float(np.max(np.abs(thpt))), 1e-9)
        X[i, :n] = bb
        Y[i, :n] = np.asarray(thpt, np.float64) / s
        W[i, :n] = 1.0
        T0[i] = theta0 * np.array([1 / s, 1.0, 1 / s])
        scale[i] = s
    theta = np.asarray(_fit_batch(jnp.asarray(T0), jnp.asarray(X),
                                  jnp.asarray(Y), jnp.asarray(W)),
                       np.float64)[:G]
    theta[:, 0] *= scale
    theta[:, 2] *= scale
    return theta


def fit_exponential_masked(theta0, X, Y, W):
    """Fixed-shape batched LM: (G, maxn) rectangles with 0/1 row weights.

    The batched annealing engine calls this with the *same* (G, maxn)
    every evaluation — subset membership only flips weights — so the
    vmapped solver compiles exactly once per process, where the ragged
    ``fit_exponential_groups`` path recompiles for every new padded
    shape.  Zero-weight rows contribute nothing to the residuals (they
    are scaled by w inside the solver), and all-zero groups take no LM
    step (J = 0 => delta = 0), returning theta0 for the caller to mask.

    theta0: (G, 3); X/Y/W: (G, maxn).  Returns float64 (G, 3).

    Both dimensions bucket to powers of two (all-zero padding, exact
    no-ops) before the jitted solve, so SA evaluators over growing
    online datasets reuse the compiled kernel across epochs.
    """
    X = np.asarray(X, np.float64)
    Y = np.asarray(Y, np.float64)
    W = np.asarray(W, np.float64)
    G, maxn = X.shape
    s = np.maximum(np.max(np.abs(Y) * (W > 0), axis=1), 1e-9)
    T0 = np.asarray(theta0, np.float64) \
        * np.stack([1.0 / s, np.ones_like(s), 1.0 / s], axis=1)
    Gp, Mp = _pow2(G, lo=2), _pow2(maxn)
    T0p = np.zeros((Gp, 3), np.float32)
    Xp = np.zeros((Gp, Mp), np.float32)
    Yp = np.zeros((Gp, Mp), np.float32)
    Wp = np.zeros((Gp, Mp), np.float32)
    T0p[:G] = T0
    Xp[:G, :maxn] = X
    Yp[:G, :maxn] = Y / s[:, None]
    Wp[:G, :maxn] = W
    theta = np.asarray(_fit_batch(jnp.asarray(T0p), jnp.asarray(Xp),
                                  jnp.asarray(Yp), jnp.asarray(Wp)),
                       np.float64)[:G]
    theta[:, 0] *= s
    theta[:, 2] *= s
    return theta


def fit_exponential_numpy(bb, thpt, theta0, iters: int = 200):
    """Reference scalar LM in numpy (oracle for property tests)."""
    theta = np.asarray(theta0, np.float64).copy()
    mu = _MU0
    x = np.asarray(bb, np.float64)
    y = np.asarray(thpt, np.float64)
    s = max(float(np.max(np.abs(y))), 1e-9)
    y = y / s
    theta[0] /= s
    theta[2] /= s

    def resid(t):
        return (t[2] - t[0] * np.exp(-t[1] * x)) - y

    for _ in range(iters):
        r = resid(theta)
        e = np.exp(-theta[1] * x)
        J = np.stack([-e, theta[0] * x * e, np.ones_like(x)], axis=1)
        A = J.T @ J + mu * np.eye(3)
        delta = np.linalg.solve(A, -(J.T @ r))
        cand = theta + delta
        cand[0] = max(cand[0], 1e-8)
        cand[1] = min(max(cand[1], 1e-8), 50.0)
        cand[2] = max(cand[2], 0.0)
        if np.sum(resid(cand) ** 2) < np.sum(r ** 2):
            theta, mu = cand, mu * 0.5
        else:
            mu *= 2.5
        mu = float(np.clip(mu, 1e-10, 1e8))
    theta[0] *= s
    theta[2] *= s
    return theta
