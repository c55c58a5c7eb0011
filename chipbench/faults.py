"""Faults planted under the timed path, for the tests that show the check
catches them. The benchmark's own runs never plant one.

``state_unchanged``  the step returns the state it was given;
``half_batch``       the second half of every batch is replaced by the
                     first, so the mean is taken over half.
"""
from __future__ import annotations


def wrap_train_step(step, fault):
    if fault is None:
        return step
    if fault == "state_unchanged":
        def unchanged(state, batch, rng):
            return state, step(state, batch, rng)[1]
        return unchanged
    if fault == "half_batch":
        import jax.numpy as jnp

        def half(state, batch, rng):
            tok = batch["tokens"]
            keep = tok[: tok.shape[0] // 2]
            return step(state, dict(batch, tokens=jnp.concatenate(
                [keep, keep], axis=0)), rng)
        return half
    raise ValueError(f"unknown train fault {fault!r}")
