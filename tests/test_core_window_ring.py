"""A lane that acts holds its attention windows as rings of `window` slots
(models/mla_moe.py, `initial_state`): a tick writes one slot of each in place,
a call of several steps the last of them, and a cut lane is reset by its
slots' validity (the windows a sequence grows from zero slots are
tests/test_core_window_length.py's).  One case a family of
tests/core_families.py's table, over its tiny core; and the tick's jaxpr,
which holds no copy of a window."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.models import mla_moe
from rainbow_iqn_apex_tpu.models.cores import (
    LSTMCore,
    reduce_stats,
    zero_lanes,
)

import core_families as cf
from core_families import close
from ring_windows import aged, live, window_slots

families = pytest.mark.parametrize("family", sorted(cf.FAMILIES))


@families
def test_a_tick_attends_over_the_window_and_the_step_and_hands_on_the_window(
        family):
    """The act path: the one-step call from `initial_state` writes the step
    into its ring first and scores exactly `window` slots in every attention
    layer (the `window` + 1 of `[window; new]` less the oldest, which the mask
    shut out), and hands on `window`."""
    window = 19
    core, stack, params, x, resets, state = cf.make(
        family, cf.tiny_cc(family, window=window), batch=2, steps=1,
        reset_at=())
    tick = lambda st: stack.apply({"params": params}, x, st, resets)  # noqa: E731
    scores = cf.score_shapes(jax.make_jaxpr(tick)(state).jaxpr)
    assert scores and {s[-2:] for s in scores} == {(1, window)}
    handed_on = jax.eval_shape(tick, state)[1]
    assert jax.tree.map(lambda a: (a.shape, a.dtype), handed_on) == jax.tree.map(
        lambda a: (a.shape, a.dtype), state)


@families
def test_ticks_with_lanes_cut_at_different_ticks_equal_the_sequence_pass(
        family):
    """2.5 `window` ticks from `initial_state`, every ring written round
    twice, lane 0 cut before tick 7 and lane 1 before ticks 19 and 20 THROUGH
    THE CORE'S RESET (what the slots held stays in them), against the
    program's pass over the sequence with those steps marked as resets, and
    against the reference's absolute positions (the Kimi-Linear reference
    knows no window)."""
    window = 12
    steps = int(2.5 * window)
    cuts = ((0, 7), (1, 19), (1, 20))
    cc = cf.tiny_cc(family, window=window)
    core, stack, params, x, resets, state = cf.make(
        family, cc, batch=2, steps=steps, reset_at=cuts)
    run, plain = cf.jitted(family, cc)
    none = jnp.zeros_like(resets)

    reset = jax.jit(core.reset_lanes)

    def cut(st, i):
        return reset(st, jnp.asarray(
            [(b, i) not in cuts for b in range(2)], jnp.uint8))

    ticks, st = cf.ticks_from(run, params, x, none, state, cut)
    seq, seq_state = run(params, x, state, resets)
    close(ticks, seq)
    if family != "kimi_linear":
        close(ticks, plain(params, x, resets, window=window))
    cf.states_close(st, seq_state, live)
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
    window = 8
    warm = window + 5  # the rings' heads at 5
    lens = (5, window, window + 3)
    cc = cf.tiny_cc(family, window=window)
    core, stack, params, x, resets, state = cf.make(
        family, cc, batch=2, steps=warm + sum(lens),
        reset_at=((0, 3), (1, warm + 2), (0, warm + 6), (1, warm + 20)))
    run, _ = cf.jitted(family, cc)
    _, ring = cf.ticks_from(run, params, x[:, :warm], resets[:, :warm], state)
    at, by_ticks = warm, ring
    for n in lens:
        xs, rs = x[:, at:at + n], resets[:, at:at + n]
        y, ring = run(params, xs, ring, rs)
        want, by_ticks = cf.ticks_from(run, params, xs, rs, by_ticks)
        close(y, want)
        assert window_slots(ring) == {window}
        cf.states_close(ring, by_ticks, aged)
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
    window = 8
    warm, after = window + 3, 2 * window
    cc = cf.tiny_cc(family, window=window)
    core, stack, params, x, resets, state = cf.make(
        family, cc, batch=2, steps=warm + after, reset_at=())
    run, _ = cf.jitted(family, cc)
    xa, ra = x[:, warm:], resets[:, warm:]
    _, warmed = cf.ticks_from(
        run, params, x[:, :warm], resets[:, :warm], state)
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
    y_cut, _ = cf.ticks_from(run, params, xa, ra, cut)
    y_zero, _ = cf.ticks_from(run, params, xa, ra, zero_lanes(warmed, keep))
    y_fresh, _ = cf.ticks_from(run, params, xa, ra, state)
    y_on, _ = cf.ticks_from(run, params, xa, ra, warmed)
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
    window, batch = 19, 3
    core, stack, params, x, resets, state = cf.make(
        family, cf.tiny_cc(family, window=window), batch=batch, steps=1,
        reset_at=())
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
            for eqn in cf.equations(jaxpr) for v in eqn.outvars
            if getattr(v.aval, "shape", None) in payloads
            # (an equation that holds a jaxpr, remat's, hands its body's on)
            and not any(list(cf.sub_jaxprs(p)) for p in eqn.params.values())]
    assert sum(name == "scatter" for name, _ in made) == leaves
    assert {m for m in made if m[0] != "scatter"} <= {
        ("concatenate", len(p) - 1) for p in payloads}
    assert not [s for s in cf.all_shapes(jaxpr)
                if len(s) > 1 and s[1] == window + 1]


@families
def test_a_call_on_a_ring_sows_the_share_of_its_slots_it_writes(family):
    """`attn_act_window_written_share`: 1 / `window` on a tick, min(T,
    `window`) / `window` for T steps on a ring, the mean over the attention
    layers; a window that still grows (the learn path) sows none, and a core
    names it among its tick's counters where it has attention windows."""
    window = 8
    cc = cf.tiny_cc(family, window=window)
    core, stack, params, x, resets, ring = cf.make(
        family, cc, batch=2, steps=12, reset_at=())
    run = cf.jitted_sown(family, cc)
    sown = lambda st, n: reduce_stats(  # noqa: E731
        run(params, x[:, :n], st, resets[:, :n])[1])
    name = "attn_act_window_written_share"
    assert float(sown(ring, 1)[name]) == pytest.approx(1 / window)
    assert float(sown(ring, 5)[name]) == pytest.approx(5 / window)
    assert float(sown(ring, 12)[name]) == 1.0
    assert name not in sown(cf.sequence_start(core, 2), 5)
    assert name in core.act_stat_names and name not in core.stat_names
    assert name not in LSTMCore().act_stat_names
