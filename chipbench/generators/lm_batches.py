"""Training batches: token rows drawn from the seed, made on the device.

Parameters (traffic file): ``batch``, ``seq`` (tokens predicted per row; a
row holds ``seq + 1`` tokens, as ``repro.launch.train`` feeds them),
``pool`` (batches made in set-up; the window cycles through them) and
``zipf`` (exponent of the unigram distribution ids are drawn from).
"""
from __future__ import annotations


def make(params, seed: int, vocab: int):
    """(pool, batch, seq + 1) int32 token ids; every row differs."""
    import jax
    import jax.numpy as jnp
    from chipbench.harness import jax_key

    n, b, s = params["pool"], params["batch"], params["seq"] + 1
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    p = ranks ** -float(params["zipf"])
    cdf = jnp.cumsum(p / jnp.sum(p))

    def draw(key):
        u = jax.random.uniform(key, (n, b, s), jnp.float32)
        tok = jnp.searchsorted(cdf, u, side="right")
        return jnp.minimum(tok, vocab - 1).astype(jnp.int32)

    return jax.jit(draw)(jax_key(seed, 2))
