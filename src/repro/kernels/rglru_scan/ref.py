"""Pure-jnp oracle for the RG-LRU linear-recurrence scan kernel."""
import jax
import jax.numpy as jnp

__all__ = ["rglru_ref"]


def rglru_ref(a, b, h0=None):
    """h_t = a_t ⊙ h_{t-1} + b_t, one step at a time in f32.

    a, b: (B, S, D); h0: (B, D) or None. Returns (h (B,S,D), h_last (B,D)).
    """
    B, S, D = a.shape
    h = jnp.zeros((B, D), jnp.float32) if h0 is None else h0.astype(
        jnp.float32)

    def step(h, ab):
        h = ab[0].astype(jnp.float32) * h + ab[1].astype(jnp.float32)
        return h, h

    h, out = jax.lax.scan(step, h, (a.swapaxes(0, 1), b.swapaxes(0, 1)))
    return out.swapaxes(0, 1).astype(a.dtype), h
