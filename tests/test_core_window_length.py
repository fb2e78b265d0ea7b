"""An attention window is as long as its state's shape says (models/mla_moe.py):
a sequence the learner unrolls starts from zero slots (`from_stored`) and its
windows grow by the steps written, up to the configuration's `window`; a lane
that acts holds `window` slots from the start (`initial_state`), a ring
(tests/test_core_window_ring.py).  One case a family of
tests/core_families.py's table, over its tiny core; and the learn step's
jaxpr, which holds no score array over slots that no step of the sequence
wrote."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    SequenceBatch,
    build_r2d2_learn_step,
    init_r2d2_state,
)

import core_families as cf
from core_families import close, grads_close
from ring_windows import aged, window_slots

families = pytest.mark.parametrize("family", sorted(cf.FAMILIES))


@families
def test_a_sequence_from_zero_slots_equals_one_from_the_empty_window(family):
    """Burn-in (stop-gradient) then the trained slice from `from_stored`'s
    zero slots against the same from `initial_state`'s `window` empty ones:
    outputs, parameter gradients and what the final windows hold, with a cut
    inside the burn-in and one inside the slice, and a window shorter than
    the sequence, so the mask's span cuts too."""
    window, burn, steps = 16, 6, 20
    core, stack, params, x, resets, lane_state = cf.make(
        family, cf.tiny_cc(family, window=window), steps=steps,
        reset_at=((0, 2), (1, 9)))
    w = jax.random.normal(
        jax.random.PRNGKey(5), (x.shape[0], steps - burn, core.kc.hidden))

    def prog(p, start):
        _, st = stack.apply({"params": p}, x[:, :burn], start, resets[:, :burn])
        st = jax.lax.stop_gradient(st)
        return stack.apply({"params": p}, x[:, burn:], st, resets[:, burn:])

    def loss(p, start):
        y, st = prog(p, start)
        return jnp.sum(y * w), (y, st)

    run = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (_, (y0, st0)), g0 = run(params, cf.sequence_start(core, x.shape[0]))
    (_, (yw, stw)), gw = run(params, lane_state)
    close(y0, yw)
    grads_close(g0, gw)
    assert window_slots(st0) == window_slots(stw) == {window}
    # the grown windows stand in position order, the rings' heads at 20 mod 16
    cf.states_close(st0, stw, aged)


@families
def test_the_window_grows_by_the_steps_written_up_to_its_length(family):
    """0 -> burn -> burn + T slots while that is under `window`, `window`
    from then on; everything else in the start state is `initial_state`'s.
    And one pass of T = 2.5 x `window` steps from the zero-slot start is the
    reference's, told the window's length (the Kimi-Linear reference knows no
    window: there against the program's own pass from `window` empty slots,
    which its ticks are held to)."""
    window = 12
    steps = int(2.5 * window)
    cc = cf.tiny_cc(family, window=window)
    core, stack, params, x, resets, lane_state = cf.make(
        family, cc, batch=2, steps=steps, reset_at=((0, 7), (1, 19), (1, 20)))
    start = cf.sequence_start(core, 2)
    assert window_slots(start) == {0} and window_slots(lane_state) == {window}
    assert start.keys() == lane_state.keys()
    for key, s in start.items():
        for name, leaf in s.items():
            if "valid" not in s or name == "head":
                np.testing.assert_array_equal(leaf, lane_state[key][name])
            else:
                assert leaf.shape == (2, 0) + lane_state[key][name].shape[2:]
                assert leaf.dtype == jnp.float32

    def after(state, lo, hi):
        return jax.eval_shape(
            lambda st: stack.apply(
                {"params": params}, x[:, lo:hi], st, resets[:, lo:hi])[1],
            state)

    burned = after(start, 0, 5)
    assert window_slots(burned) == {5}
    assert window_slots(after(burned, 5, 9)) == {9}
    assert window_slots(after(burned, 5, 20)) == {window}
    assert window_slots(after(lane_state, 0, 1)) == {window}

    run, plain = cf.jitted(family, cc)
    y, final = run(params, x, start, resets)
    assert window_slots(final) == {window}
    if family == "kimi_linear":
        expected = run(params, x, lane_state, resets)[0]
    else:
        expected = plain(params, x, resets, window=window)
    close(y, expected)


@families
def test_the_learn_step_holds_no_key_axis_longer_than_the_sequence(
        family, tmp_path):
    """The learn step of the agent with a tiny core (ops/r2d2.py, forward and
    backward, both nets): the scores' key axes are the burn-in's steps and the
    sequence's, and no array of the step has an axis of `window` + burn-in or
    `window` + slice slots, what `[window; new]` was from a full empty
    window."""
    window, burn, train = 29, 4, 8
    cc = cf.tiny_cc(family, window=window)
    with open(cf.FAMILIES[family].tiny) as f:  # the trunk feeds the Kimi core
        cc["hidden_size"] = json.load(f)["hidden_size"]
    core_file = tmp_path / "core.json"
    core_file.write_text(json.dumps(cc))
    cfg = Config(
        env_id="jaxgame:freeway", architecture="r2d2", role="anakin",
        core_config=str(core_file), compute_dtype="float32", history_length=4,
        hidden_size=32, r2d2_burn_in=burn, r2d2_seq_len=train, batch_size=2,
        multi_step=2, learner_devices=1)
    b, length, actions = 2, burn + train, 3
    ts = jax.eval_shape(
        lambda k: init_r2d2_state(cfg, actions, k, (80, 80)),
        jax.random.PRNGKey(0))
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    seq = SequenceBatch(
        obs=shaped((b, length, 80, 80, 1), jnp.uint8),
        action=shaped((b, length), jnp.int32),
        reward=shaped((b, length), jnp.float32),
        done=shaped((b, length), bool), valid=shaped((b, length), bool),
        init_c=shaped((b, 0), jnp.float32), init_h=shaped((b, 0), jnp.float32),
        weight=shaped((b,), jnp.float32))
    jaxpr = jax.make_jaxpr(build_r2d2_learn_step(cfg, actions))(
        ts, seq, jax.random.PRNGKey(1)).jaxpr
    scores = cf.score_shapes(jaxpr)
    assert {s[-2:] for s in scores} == {(burn, burn), (train, burn + train)}
    old = {window + burn, window + train}
    assert not [s for s in cf.all_shapes(jaxpr) if old & set(s)]
