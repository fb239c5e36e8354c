"""Pure-jnp oracles for the blocked / lane-batched Gram kernels.

Both contract with the same ``dot_general`` dimension numbers the kernels
use, so interpret-mode runs agree BIT-EXACTLY with these refs (asserted in
tests/test_kernels.py).
"""
import jax
import jax.numpy as jnp


def gram_ref(x):
    """Gram matrix X X^T of a (n, d) stack (or any (n, ...) one, a leaf
    view), accumulated in fp32."""
    xf = x.reshape(x.shape[0], -1).astype(jnp.float32)
    return jax.lax.dot_general(xf, xf, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def gram_batched_ref(x):
    """Per-lane Gram matrices of a (B, n, d) stack, fp32."""
    return jax.vmap(gram_ref)(x)
