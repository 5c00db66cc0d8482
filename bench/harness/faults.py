"""Faults planted under the timed path, to see the check come out false.

Each takes the engine the window drives and breaks one of its programs
in place. ``bench/tests/test_harness.py`` plants them at a toy size on
the CPU; ``bench/control.py --fault`` at a cell's own size on the chip.
The benchmark's own runs never plant one.
"""
import jax.numpy as jnp


def alter_token(eng):
    """A token altered where it is produced: the sampler's output for
    slot 0 is shifted by one on every step."""
    sample = eng._sample

    def bad(logits, *rest):
        tok, gen = sample(logits, *rest)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1]), gen
    bad._cache_size = sample._cache_size
    eng._sample = bad


def state_unchanged(eng):
    """A step that returns its state unchanged: reuse steps hand back
    the cache length they were given, so the token each one appended is
    overwritten by the next (no copy of the cache, so that it fits
    beside a long-context cell's state)."""
    step = eng._dec_reuse

    def bad(params, state, *rest):
        length = jnp.copy(state["length"])
        logits, new = step(params, state, *rest)
        return logits, dict(new, length=length)
    bad._cache_size = step._cache_size
    eng._dec_reuse = bad


FAULTS = {"alter_token": alter_token, "state_unchanged": state_unchanged}
