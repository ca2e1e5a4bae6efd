"""The control that shows the check can fail: the reference algorithm
put in the program's place and computed one precision below the
configuration's float32, in bfloat16.

For ``expm`` that is Pade-13 scaling and squaring (Higham 2005, the
algorithm the reference and the program both follow); for a steady
state it is the reference's direct solve of pi (P - I) = 0. Every array
is held in bfloat16; products accumulate in f32 on the MXU and are
rounded to bfloat16, and the LU solve runs in f32 on bfloat16 inputs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mfbench import roofline

_PADE13 = (
    64764752532480000.0, 32382376266240000.0, 7771770303897600.0,
    1187353796428800.0, 129060195264000.0, 10559470521600.0, 670442572800.0,
    33522128640.0, 1323241920.0, 40840800.0, 960960.0, 16380.0, 182.0, 1.0,
)
_DTYPE = jnp.bfloat16


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(_DTYPE)


def _solve(lhs, rhs):
    lhs, rhs = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    return jnp.linalg.solve(lhs, rhs).astype(_DTYPE)


@functools.partial(jax.jit, static_argnames=("squarings",))
def expm(a, *, squarings: int):
    """e^A by Pade-13 scaling and squaring with ``squarings`` squarings."""
    b = _PADE13
    x = (a.astype(jnp.float32) / (2.0 ** squarings)).astype(_DTYPE)
    ident = jnp.eye(a.shape[-1], dtype=_DTYPE)
    x2 = _dot(x, x)
    x4 = _dot(x2, x2)
    x6 = _dot(x2, x4)
    u = _dot(x, _dot(x6, b[13] * x6 + b[11] * x4 + b[9] * x2)
             + b[7] * x6 + b[5] * x4 + b[3] * x2 + b[1] * ident)
    v = (_dot(x6, b[12] * x6 + b[10] * x4 + b[8] * x2)
         + b[6] * x6 + b[4] * x4 + b[2] * x2 + b[0] * ident)
    r = _solve(v - u, v + u)
    for _ in range(squarings):
        r = _dot(r, r)
    return r.astype(jnp.float32)


@jax.jit
def stationary(p):
    """pi with pi (P - I) = 0 and sum(pi) = 1, by a direct solve whose
    inputs are held in bfloat16."""
    n = p.shape[-1]
    lhs = (p.astype(jnp.float32) - jnp.eye(n)).T
    lhs = lhs.at[-1].set(1.0)
    rhs = jnp.zeros(n).at[-1].set(1.0)
    return _solve(lhs.astype(_DTYPE), rhs).astype(jnp.float32)


def answer(op: str, operand):
    """The control's answer for one request, as the check reads it."""
    a = jnp.asarray(operand, jnp.float32)
    if op == "expm":
        s = roofline.expm_squarings(roofline.norm1(operand))
        return expm(a, squarings=s)
    return stationary(a)
