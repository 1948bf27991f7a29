"""Faults planted under a training cell's timed path, to show that the
check reads them as not correct. Each wraps the compiled step
``step(params, state, batch, i) -> (params, state, loss)``."""
import jax
import jax.numpy as jnp


def stale_state(step):
    """The step computes but hands back its input state unchanged."""
    def broken(params, state, batch, i):
        keep = jax.tree.map(jnp.copy, (params, state))
        _, _, loss = step(params, state, batch, i)
        return keep[0], keep[1], loss
    return broken


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest (the
    first half, twice)."""
    def broken(params, state, batch, i):
        half = {k: jnp.concatenate([v[:v.shape[0] // 2]] * 2)
                for k, v in batch.items()}
        return step(params, state, half, i)
    return broken


def negated_update(step):
    """The step's update applied the wrong way round: the parameters
    move by minus what the step would move them (gradient ascent)."""
    def broken(params, state, batch, i):
        before = jax.tree.map(jnp.copy, params)
        after, state, loss = step(params, state, batch, i)
        return jax.tree.map(lambda b, a: 2 * b - a, before, after), state, loss
    return broken
