"""An attention window is as long as its state's shape says (models/mla_moe.py):
a sequence the learner unrolls starts from zero slots (`from_stored`) and its
windows grow by the steps written, up to the configuration's `window`; a lane
that acts holds `window` slots from the start (`initial_state`), a RING: a
tick writes one slot of it in place, a call of several steps the last of
them, and a cut lane is reset by its slots' validity.  One case a family,
over the tiny cores of the five families' own test files; the learn step's
jaxpr, which holds no score array over slots that no step of the sequence
wrote, and the tick's, which holds no copy of a window."""

import json

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.config import Config
from rainbow_iqn_apex_tpu.models import mla_moe
from rainbow_iqn_apex_tpu.models.cores import (
    CORE_STATS,
    LSTMCore,
    reduce_stats,
    zero_lanes,
)
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
from ring_windows import aged, live, window_slots
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
    # the grown windows stand in position order, the rings' heads at 20 mod 16
    for a, b in zip(jax.tree.leaves(aged(st0)), jax.tree.leaves(aged(stw))):
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
    """The act path: the one-step call from `initial_state` writes the step
    into its ring first and scores exactly `window` slots in every attention
    layer (the `window` + 1 of `[window; new]` less the oldest, which the mask
    shut out), and hands on `window`."""
    t = FAMILIES[family]
    window = 19
    core, stack, params, x, resets, state = t.make(
        t.tiny_cc(window=window), batch=2, steps=1, reset_at=())
    tick = lambda st: stack.apply({"params": params}, x, st, resets)  # noqa: E731
    scores = score_shapes(jax.make_jaxpr(tick)(state).jaxpr)
    assert scores and {s[-2:] for s in scores} == {(1, window)}
    handed_on = jax.eval_shape(tick, state)[1]
    assert jax.tree.map(lambda a: (a.shape, a.dtype), handed_on) == jax.tree.map(
        lambda a: (a.shape, a.dtype), state)


def ticks_from(run, params, x, resets, state, cut=None):
    """One-step calls over x [B, T, .] from `state`; `cut(state, t)` stands
    between tick t - 1 and tick t, where the trainers reset a lane."""
    ys = []
    for i in range(x.shape[1]):
        if cut is not None:
            state = cut(state, i)
        y, state = run(params, x[:, i:i + 1], state, resets[:, i:i + 1])
        ys.append(y)
    return jnp.concatenate(ys, axis=1), state


def runner(stack):
    return jax.jit(lambda p, x, st, r: stack.apply({"params": p}, x, st, r))


@families
def test_ticks_with_lanes_cut_at_different_ticks_equal_the_sequence_pass(
        family):
    """2.5 `window` ticks from `initial_state`, every ring written round
    twice, lane 0 cut before tick 7 and lane 1 before ticks 19 and 20 THROUGH
    THE CORE'S RESET (what the slots held stays in them), against the
    program's pass over the sequence with those steps marked as resets, and
    against the reference's absolute positions (the Kimi-Linear reference
    knows no window)."""
    t = FAMILIES[family]
    window = 12
    steps = int(2.5 * window)
    cuts = ((0, 7), (1, 19), (1, 20))
    cc = t.tiny_cc(window=window)
    core, stack, params, x, resets, state = t.make(
        cc, batch=2, steps=steps, reset_at=cuts)
    run = runner(stack)
    none = jnp.zeros_like(resets)

    reset = jax.jit(core.reset_lanes)

    def cut(st, i):
        return reset(st, jnp.asarray(
            [(b, i) not in cuts for b in range(2)], jnp.uint8))

    ticks, st = ticks_from(run, params, x, none, state, cut)
    seq, seq_state = run(params, x, state, resets)
    close(ticks, seq)
    if family != "kimi_linear":
        close(ticks, t.ref.core_forward(params, cc, x, resets, window=window))
    for a, c in zip(jax.tree.leaves(live(st)),
                    jax.tree.leaves(live(seq_state))):
        close(a, c)
    # the rings went round: a head stands as many slots past its lane's last
    # cut as ticks have run since, less the ring's length
    for s in st.values():
        if "valid" in s:
            np.testing.assert_array_equal(
                s["head"], [(steps - 7) % window, steps - 20])


@families
def test_several_steps_on_a_ring_are_the_ticks_of_those_steps(family):
    """A call of T > 1 steps on a ring that has wrapped round (an eval
    rollout) is the parent's `[window; new][:, -window:]`: the outputs and
    the windows in age order are those of the same steps taken one tick at a
    time, for T under `window` (the write wraps round the ring's end), T =
    `window` and T over it (only the last `window` steps are written)."""
    t = FAMILIES[family]
    window = 8
    warm = window + 5  # the rings' heads at 5
    lens = (5, window, window + 3)
    cc = t.tiny_cc(window=window)
    core, stack, params, x, resets, state = t.make(
        cc, batch=2, steps=warm + sum(lens),
        reset_at=((0, 3), (1, warm + 2), (0, warm + 6), (1, warm + 20)))
    run = runner(stack)
    _, ring = ticks_from(run, params, x[:, :warm], resets[:, :warm], state)
    at, by_ticks = warm, ring
    for n in lens:
        xs, rs = x[:, at:at + n], resets[:, at:at + n]
        y, ring = run(params, xs, ring, rs)
        want, by_ticks = ticks_from(run, params, xs, rs, by_ticks)
        close(y, want)
        assert window_slots(ring) == {window}
        for a, c in zip(jax.tree.leaves(aged(ring)),
                        jax.tree.leaves(aged(by_ticks))):
            close(a, c)
        at += n


@pytest.mark.parametrize("steps", [1, 5, 8, 11])
def test_a_ring_hands_on_the_last_slots_of_window_and_new(steps):
    """`window_open` on a ring against the definition written out: the ring's
    slots in age order, the new steps behind them, the last `window` slots
    handed on, a slot of a segment that has ended void; and what the steps
    attend over holds the same slots at the same distances."""
    w, b = 8, 3
    keys = jax.random.split(jax.random.PRNGKey(steps), 4)
    state = {"k": jax.random.normal(keys[0], (b, w, 2, 4)),
             "valid": (jax.random.uniform(keys[1], (b, w)) > 0.3).astype(
                 jnp.float32),
             "head": jnp.asarray([0.0, 3.0, 7.0])}
    new = jax.random.normal(keys[2], (b, steps, 2, 4))
    seg = jnp.cumsum(jax.random.uniform(keys[3], (b, steps)) > 0.8, axis=1)
    win = mla_moe.window_open(state, {"k": new}, seg, w)
    old = aged({"m": state})["m"]
    k_all = jnp.concatenate([old["k"], new], axis=1)
    seg_all = jnp.concatenate([jnp.zeros((b, w), seg.dtype), seg], axis=1)
    valid_all = jnp.concatenate([old["valid"], jnp.ones((b, steps))], axis=1)
    got = aged({"m": win.state})["m"]
    np.testing.assert_array_equal(got["k"], k_all[:, -w:])
    np.testing.assert_array_equal(
        got["valid"], (valid_all * (seg_all == seg[:, -1:]))[:, -w:])
    np.testing.assert_array_equal(
        win.state["head"], (state["head"] + steps) % w)
    # the mask against the definition's, slot for slot: by age where the
    # steps attend over `[ring; new]`, and for one step, written first, over
    # the ring that holds it
    pos_q = w + jnp.arange(steps)[:, None]
    pos_k = jnp.arange(w + steps)[None]
    want = ((pos_k <= pos_q) & (pos_k > pos_q - w))[None] & (
        valid_all[:, None] > 0) & (seg_all[:, None] == seg[:, :, None])
    mask = mla_moe.window_mask(win, seg, w)
    lanes = jnp.arange(b)[:, None]
    if steps == 1:
        order = (win.state["head"].astype(jnp.int32)[:, None]
                 + jnp.arange(w)) % w
        np.testing.assert_array_equal(mask[lanes, 0, order], want[:, 0, 1:])
        np.testing.assert_array_equal(win.pos_k[lanes, order],
                                      jnp.broadcast_to(jnp.arange(w), (b, w)))
        assert int(win.pos_q[0]) == w - 1
    else:
        order = jnp.concatenate(
            [(state["head"].astype(jnp.int32)[:, None] + jnp.arange(w)) % w,
             jnp.broadcast_to(w + jnp.arange(steps), (b, steps))], axis=1)
        np.testing.assert_array_equal(
            jnp.take_along_axis(mask, order[:, None, :], axis=2), want)
        np.testing.assert_array_equal(
            win.pos_k[lanes, order],
            jnp.broadcast_to(jnp.arange(w + steps), (b, w + steps)))


@families
def test_a_lane_reset_with_stale_keys_in_its_slots_starts_over(family):
    """A lane cut through the core's reset keeps what its windows held and
    loses their validity and heads: for the next 2 `window` ticks (the stale
    slots all written over by the end) its outputs are those of a lane started
    from `initial_state`, and those after `zero_lanes`' reset; the lane that
    was not cut goes on as it was."""
    t = FAMILIES[family]
    window = 8
    warm, after = window + 3, 2 * window
    core, stack, params, x, resets, state = t.make(
        t.tiny_cc(window=window), batch=2, steps=warm + after, reset_at=())
    run = runner(stack)
    xa, ra = x[:, warm:], resets[:, warm:]
    _, warmed = ticks_from(run, params, x[:, :warm], resets[:, :warm], state)
    keep = jnp.asarray([1, 0], jnp.uint8)
    cut = core.reset_lanes(warmed, keep)
    for key, s in cut.items():
        if "valid" in s:  # the payload stays, stale; validity and head go
            for name, leaf in s.items():
                if name in ("valid", "head"):
                    assert not np.any(np.asarray(leaf[1]))
                else:
                    np.testing.assert_array_equal(leaf, warmed[key][name])
                    assert np.abs(np.asarray(leaf[1])).min(axis=-1).max() > 0
        else:
            assert not any(np.any(np.asarray(leaf[1])) for leaf in s.values())
        for name, leaf in s.items():
            np.testing.assert_array_equal(leaf[0], warmed[key][name][0])
    y_cut, _ = ticks_from(run, params, xa, ra, cut)
    y_zero, _ = ticks_from(run, params, xa, ra, zero_lanes(warmed, keep))
    y_fresh, _ = ticks_from(run, params, xa, ra, state)
    y_on, _ = ticks_from(run, params, xa, ra, warmed)
    close(y_cut[1], y_fresh[1], 1e-6)
    close(y_cut, y_zero, 1e-6)
    close(y_cut[0], y_on[0], 1e-6)
    assert float(jnp.abs(y_on[1] - y_fresh[1]).max()) > 1e-3


@families
def test_the_tick_copies_no_window(family):
    """The tick with the core's reset behind it, as the fused trainer runs
    them: an array as large as a window's keys, values or latents is put out
    by the write (a scatter into the carried buffer, one a leaf) and by the
    rotation's joining of a head's halves alone; none by a `slice` (the
    roll), a `mul` (the reset) or a `select_n`, and no array holds `window` +
    1 slots (`[window; new]`)."""
    t = FAMILIES[family]
    window, batch = 19, 3
    core, stack, params, x, resets, state = t.make(
        t.tiny_cc(window=window), batch=batch, steps=1, reset_at=())
    keep = jnp.asarray([1, 0, 1], jnp.uint8)
    tick = lambda st: core.reset_lanes(  # noqa: E731
        stack.apply({"params": params}, x, st, resets)[1], keep)
    jaxpr = jax.make_jaxpr(tick)(state).jaxpr
    payloads = {leaf.shape for s in state.values() if "valid" in s
                for name, leaf in s.items() if leaf.ndim > 2}
    assert payloads and all(p[:2] == (batch, window) for p in payloads)
    leaves = sum(leaf.ndim > 2 for s in state.values() if "valid" in s
                 for leaf in s.values())
    made = [(eqn.primitive.name, eqn.params.get("dimension"))
            for eqn in equations(jaxpr) for v in eqn.outvars
            if getattr(v.aval, "shape", None) in payloads
            # (an equation that holds a jaxpr, remat's, hands its body's on)
            and not any(list(sub_jaxprs(p)) for p in eqn.params.values())]
    assert sum(name == "scatter" for name, _ in made) == leaves
    assert {m for m in made if m[0] != "scatter"} <= {
        ("concatenate", len(p) - 1) for p in payloads}
    assert not [s for s in all_shapes(jaxpr)
                if len(s) > 1 and s[1] == window + 1]


@families
def test_a_call_on_a_ring_sows_the_share_of_its_slots_it_writes(family):
    """`attn_act_window_written_share`: 1 / `window` on a tick, min(T,
    `window`) / `window` for T steps on a ring, the mean over the attention
    layers; a window that still grows (the learn path) sows none, and a core
    names it among its tick's counters where it has attention windows."""
    t = FAMILIES[family]
    window = 8
    core, stack, params, x, resets, ring = t.make(
        t.tiny_cc(window=window), batch=2, steps=12, reset_at=())
    sown = lambda st, n: reduce_stats(stack.apply(  # noqa: E731
        {"params": params}, x[:, :n], st, resets[:, :n],
        mutable=[CORE_STATS])[1])
    name = "attn_act_window_written_share"
    assert float(sown(ring, 1)[name]) == pytest.approx(1 / window)
    assert float(sown(ring, 5)[name]) == pytest.approx(5 / window)
    assert float(sown(ring, 12)[name]) == 1.0
    assert name not in sown(sequence_start(core, 2), 5)
    assert name in core.act_stat_names and name not in core.stat_names
    assert name not in LSTMCore().act_stat_names


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
