"""Pure-jnp oracle for the streamed combine kernel."""
import jax
import jax.numpy as jnp


def combine_ref(x, coeff):
    """R = coeff @ X of a (n, d) stack; contraction in X's dtype with fp32
    accumulation (the ``tree_combine`` bf16-transport contract).  Any
    (n, ...) stack (a leaf view) gives an x.shape[1:] result."""
    c = coeff.astype(x.dtype).reshape(1, -1)
    out = jax.lax.dot_general(c, x.reshape(x.shape[0], -1),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return out[0].reshape(x.shape[1:])
