"""DT102 good: outputs stay on device through the loop; ONE batched
pull per step (the engine/core.py decode-path pattern)."""

import jax
import jax.numpy as jnp


def decode_tokens(step_outputs):
    stacked = jnp.stack(step_outputs)
    return jax.device_get(stacked)


def loop_stays_on_device(step_fn, state, n):
    outs = []
    for _ in range(n):
        state, out = step_fn(state)
        outs.append(out)
    return tuple(jax.device_get(jnp.stack(outs)))


def describe_batch(stats):
    # host callback outside any loop and outside compiled code: a
    # one-shot debug path, not a per-step sync
    jax.debug.print("batch stats {}", stats)
    return stats


def burst_decode(step_fn, state, rng_keys):
    # the fused-burst idiom: k device turns accumulate under one scan,
    # the host sees ONE trailing pull for the whole burst
    state, samples = jax.lax.scan(step_fn, state, rng_keys)
    return state, jax.device_get(samples)
