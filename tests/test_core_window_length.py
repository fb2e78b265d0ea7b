"""An attention window is as long as its state's shape says (models/mla_moe.py):
a sequence the learner unrolls starts from zero slots (`from_stored`) and its
windows grow by the steps written, up to the configuration's `window`; a lane
that acts holds `window` slots from the start (`initial_state`) and its tick
is the program it was.  One case a family, over the tiny cores of the five
families' own test files; and the learn step's jaxpr, which holds no score
array over slots that no step of the sequence wrote."""

import json

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.ops.r2d2 import (
    SequenceBatch,
    build_r2d2_learn_step,
    init_r2d2_state,
)

import test_deepseek_v3_core
import test_kimi_linear_core
import test_lfm2_core
import test_ouro_core
import test_qwen3_next_core
from test_deepseek_v3_core import close, grads_close  # the five files' one

# family -> its test file: `TINY`, `tiny_cc`, `make` and the plain reference
FAMILIES = {"deepseek_v3": test_deepseek_v3_core,
            "kimi_linear": test_kimi_linear_core,
            "qwen3_next": test_qwen3_next_core,
            "ouro": test_ouro_core,
            "lfm2_moe": test_lfm2_core}
families = pytest.mark.parametrize("family", sorted(FAMILIES))


def sequence_start(core, batch):
    none = jnp.zeros((batch, 0), jnp.float32)
    return core.from_stored(none, none)


def window_slots(state):
    """The lengths of the state's attention windows: one, or the windows
    disagree."""
    return {leaf.shape[1] for s in state.values() if "valid" in s
            for leaf in s.values()}


def sub_jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from sub_jaxprs(v)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (remat,
    cond, scan, custom derivatives)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in sub_jaxprs(value):
                yield from equations(sub)


def score_shapes(jaxpr):
    """The shapes of the attention scores: what a softmax takes its largest
    over, [B, heads.., T, slots] (the only `reduce_max` of four axes or
    more)."""
    return {eqn.invars[0].aval.shape for eqn in equations(jaxpr)
            if eqn.primitive.name == "reduce_max"
            and len(eqn.invars[0].aval.shape) >= 4}


def all_shapes(jaxpr):
    return {v.aval.shape for eqn in equations(jaxpr) for v in eqn.outvars
            if hasattr(v.aval, "shape")}


@families
def test_a_sequence_from_zero_slots_equals_one_from_the_empty_window(family):
    """Burn-in (stop-gradient) then the trained slice from `from_stored`'s
    zero slots against the same from `initial_state`'s `window` empty ones:
    outputs, parameter gradients and what the final windows hold, with a cut
    inside the burn-in and one inside the slice, and a window shorter than
    the sequence, so the mask's span cuts too."""
    t = FAMILIES[family]
    window, burn, steps = 16, 6, 20
    core, stack, params, x, resets, lane_state = t.make(
        t.tiny_cc(window=window), steps=steps, reset_at=((0, 2), (1, 9)))
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
    (_, (y0, st0)), g0 = run(params, sequence_start(core, x.shape[0]))
    (_, (yw, stw)), gw = run(params, lane_state)
    close(y0, yw)
    grads_close(g0, gw)
    assert window_slots(st0) == window_slots(stw) == {window}
    for a, b in zip(jax.tree.leaves(st0), jax.tree.leaves(stw)):
        close(a, b)


@families
def test_the_window_grows_by_the_steps_written_up_to_its_length(family):
    """0 -> burn -> burn + T slots while that is under `window`, `window`
    from then on; everything else in the start state is `initial_state`'s.
    And one pass of T = 2.5 x `window` steps from the zero-slot start is the
    reference's, told the window's length (the Kimi-Linear reference knows no
    window: there against the program's own pass from `window` empty slots,
    which its ticks are held to)."""
    t = FAMILIES[family]
    window = 12
    steps = int(2.5 * window)
    cc = t.tiny_cc(window=window)
    core, stack, params, x, resets, lane_state = t.make(
        cc, batch=2, steps=steps, reset_at=((0, 7), (1, 19), (1, 20)))
    start = sequence_start(core, 2)
    assert window_slots(start) == {0} and window_slots(lane_state) == {window}
    assert start.keys() == lane_state.keys()
    for key, s in start.items():
        for name, leaf in s.items():
            if "valid" not in s:
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

    apply = jax.jit(lambda st: stack.apply({"params": params}, x, st, resets))
    y, final = apply(start)
    assert window_slots(final) == {window}
    if family == "kimi_linear":
        expected = apply(lane_state)[0]
    else:
        expected = t.ref.core_forward(params, cc, x, resets, window=window)
    close(y, expected)


@families
def test_a_tick_attends_over_the_window_and_the_step_and_hands_on_the_window(
        family):
    """The act path: the one-step call from `initial_state` scores exactly
    `window` + 1 slots in every attention layer and hands on `window`."""
    t = FAMILIES[family]
    window = 19
    core, stack, params, x, resets, state = t.make(
        t.tiny_cc(window=window), batch=2, steps=1, reset_at=())
    tick = lambda st: stack.apply({"params": params}, x, st, resets)  # noqa: E731
    scores = score_shapes(jax.make_jaxpr(tick)(state).jaxpr)
    assert scores and {s[-2:] for s in scores} == {(1, window + 1)}
    handed_on = jax.eval_shape(tick, state)[1]
    assert jax.tree.map(lambda a: (a.shape, a.dtype), handed_on) == jax.tree.map(
        lambda a: (a.shape, a.dtype), state)


@families
def test_the_learn_step_holds_no_key_axis_longer_than_the_sequence(
        family, tmp_path):
    """The learn step of the agent with a tiny core (ops/r2d2.py, forward and
    backward, both nets): the scores' key axes are the burn-in's steps and the
    sequence's, and no array of the step has an axis of `window` + burn-in or
    `window` + slice slots, what `[window; new]` was from a full empty
    window."""
    t = FAMILIES[family]
    window, burn, train = 29, 4, 8
    cc = t.tiny_cc(window=window)
    with open(t.TINY) as f:  # the file's own: the trunk feeds the Kimi core
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
    scores = score_shapes(jaxpr)
    assert {s[-2:] for s in scores} == {(burn, burn), (train, burn + train)}
    old = {window + burn, window + train}
    assert not [s for s in all_shapes(jaxpr) if old & set(s)]
