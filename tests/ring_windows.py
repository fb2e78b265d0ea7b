"""What the cores' tests share about attention windows (models/mla_moe.py):
a lane's window is a ring, so what two states hold is compared with every
window turned into age order (oldest slot first), where a sequence's window
and a ring that wrapped round read alike."""

import jax.numpy as jnp


def aged(state):
    """`state` with each attention window's slots in age order and its head
    left out; the other (pass, layer) states as they are."""
    def turn(s):
        if "valid" not in s:
            return s
        n = s["valid"].shape[1]
        head = s["head"].astype(jnp.int32)[:, None]
        order = (head + jnp.arange(n)) % max(n, 1)
        lanes = jnp.arange(order.shape[0])[:, None]
        return {name: leaf[lanes, order] for name, leaf in s.items()
                if name != "head"}
    return {key: turn(s) for key, s in state.items()}


def live(state):
    """`aged(state)` with what the void slots hold left out (times the slots'
    validity): a lane reset by validity keeps stale keys where a sequence
    pass keeps an ended segment's, and neither weighs anything."""
    def mask(s):
        if "valid" not in s:
            return s
        return {name: leaf * s["valid"].reshape(
            s["valid"].shape + (1,) * (leaf.ndim - 2))
            for name, leaf in s.items()}
    return {key: mask(s) for key, s in aged(state).items()}


def window_slots(state):
    """The lengths of the state's attention windows: one, or the windows
    disagree."""
    return {leaf.shape[1] for s in state.values() if "valid" in s
            for name, leaf in s.items() if name != "head"}
