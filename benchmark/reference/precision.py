"""Lower precisions that a control puts in the plain reference's place.

The control of a cell is the reference computed in the nearest precision
below the one its configuration states (``CONTROL_BELOW``), the step that
would tempt a later PR.  A precision is a pair ``(operand, cotangent)``:
``operand`` rounds a product's operand on the way forward and passes the
gradient straight through; ``cotangent`` is the identity forward and rounds
the gradient that flows back into the product, so that the backward products
have low-precision operands too, as a step computed in that precision has.
Everything stays float32 between the roundings.
"""
import jax
import jax.numpy as jnp
from jax import lax


def round_int8(x):
    """Symmetric per-tensor int8 rounding, kept in float32: the nearest
    precision below bfloat16 that a later PR could be tempted by."""
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def round_fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding, kept in float32: the other
    precision below bfloat16."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def round_bf16(x):
    """bfloat16 rounding, kept in float32: the precision below float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _same(x):
    return x


def lower_precision(rounding):
    def operand(x):
        return x + lax.stop_gradient(rounding(x) - x)

    @jax.custom_vjp
    def cotangent(y):
        return y

    cotangent.defvjp(lambda y: (y, None), lambda _res, g: (rounding(g),))
    return operand, cotangent


EXACT = (_same, _same)
CONTROLS = {"int8": lower_precision(round_int8), "fp8": lower_precision(round_fp8),
            "bf16": lower_precision(round_bf16)}
# the nearest precision below the one a configuration computes in
CONTROL_BELOW = {"bfloat16": "int8", "float16": "int8", "float32": "bf16"}
