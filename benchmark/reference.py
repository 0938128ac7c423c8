"""What every plain reference shares, and no architecture owns.

The yardstick ``correct`` is decided against imports nothing from
``ml_trainer_tpu`` and takes nothing the program has made.  An
architecture's own arithmetic (its sizes, its weights from the seed, its
forward pass, its served-token gaps and its first training steps) is
``references/<name>.py``, named by the configuration's ``"reference"`` key;
this file holds what such a module may import: the key from the seed, the
two products a pass is computed in, AdamW as published and the tree
arithmetic the training comparison is made of.

Every matrix product of a reference goes through one ``mm`` argument:
``mm_highest`` is the reference (float32 operands, ``Precision.HIGHEST``:
six bf16 passes on the TPU); ``mm_fp8`` is the control, a precision below
the bfloat16 that the configurations state (both operands rounded to float8
e4m3, scaled per row of the activations and per column of the weights,
straight-through gradients).  The other step below bfloat16, int8, is the
program's own ``Server(quant_int8=True)``, which ``calibrate.py`` switches
on.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (PRNGKey wraps above 2**32)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % (1 << 32)), seed >> 32
    )


# ------------------------------------------------------------- arithmetic
def mm_highest(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fp8_grid(t, axis):
    """Round to float8 (e4m3: three bits of mantissa) after scaling the
    absolute maximum along ``axis`` to the format's largest value, as fp8
    products are run; the gradient passes straight through."""
    scale = jnp.max(jnp.abs(t), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return t + jax.lax.stop_gradient(q - t)


def mm_fp8(x, w):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    return jnp.matmul(_fp8_grid(x, -1), _fp8_grid(w, 0),
                      precision=jax.lax.Precision.HIGHEST)


# The products a pass is computed in: the reference and the control.
MM = {None: mm_highest, "fp8": mm_fp8}


@functools.partial(jax.jit, static_argnames=("lr", "weight_decay"))
def adamw_update(params, mu, nu, grads, count, *, lr, weight_decay):
    """One AdamW step as published (Loshchilov & Hutter 2019), decay
    decoupled and applied to every leaf."""
    count = count + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      nu, grads)
    c1 = 1 - ADAM_B1 ** count
    c2 = 1 - ADAM_B2 ** count

    def new(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)
        return p - lr * (step + weight_decay * p)

    return jax.tree.map(new, params, mu, nu), mu, nu, count


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(
        lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32)))), tree)


@jax.jit
def tree_scale(tree, factor):
    return jax.tree.map(lambda t: t.astype(jnp.float32) * factor, tree)


@jax.jit
def tree_sub(after, before):
    return jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        after, before)


def moved_threshold(first_grads) -> float:
    """A thousandth of the median leaf's root-mean-square gradient: an
    element whose reference gradient is under it is nought to rounding (a
    key's bias under softmax) and moves under Adam by round-off alone."""
    rms = [float(n) / math.sqrt(g.size) for n, g in zip(
        jax.tree.leaves(leaf_norms(first_grads)),
        jax.tree.leaves(first_grads))]
    return 1e-3 * float(np.median(rms))


@jax.jit
def moved_change_norms(delta, first_grads, threshold):
    """Per-leaf norm of the parameters' change over the elements whose
    reference gradient is at or over the threshold."""
    return jax.tree.map(
        lambda d, g: jnp.sqrt(jnp.sum(jnp.square(
            jnp.where(jnp.abs(g) >= threshold, d, 0.0)))),
        delta, first_grads)
