"""Device-resident sequence replay (replay/device_sequence.py) vs the host
SequenceReplay: same trace in, same ring/priorities/batches out.

The host buffer (replay/sequence.py) is the semantics oracle — these tests
pin the in-graph mirror to it tick by tick: ring rows (zero-padding,
two-channel cuts, overlap carry-over with exact stored LSTM states),
max-priority insertion order, assemble weights, and eta-mix write-back."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rainbow_iqn_apex_tpu.replay.device_sequence import (
    ROW_FIELDS,
    DeviceSequenceReplay,
    build_device_r2d2_learn,
)
from rainbow_iqn_apex_tpu.replay.sequence import SequenceReplay

LANES, L, STRIDE, CAP = 3, 6, 3, 16
H = W = 8
LSTM = 4
OMEGA, EPS = 0.9, 1e-6


def _make_pair():
    host = SequenceReplay(
        capacity=CAP, seq_len=L, frame_shape=(H, W), lstm_size=LSTM,
        lanes=LANES, stride=STRIDE, priority_exponent=OMEGA,
        priority_eps=EPS, seed=0,
    )
    dev = DeviceSequenceReplay(
        capacity=CAP, seq_len=L, frame_shape=(H, W), lstm_size=LSTM,
        lanes=LANES, stride=STRIDE, priority_exponent=OMEGA, priority_eps=EPS,
    )
    return host, dev


def _trace(rng, ticks, p_term=0.1, p_trunc=0.07, trunc_all_at=(),
           lanes=LANES):
    """Per-tick append inputs, in append's argument order.  Random cuts per
    lane, plus a truncation of every lane on the (1-based) ticks of
    `trunc_all_at`."""
    for t in range(1, ticks + 1):
        term = rng.random(lanes) < p_term
        trunc = (rng.random(lanes) < p_trunc) & ~term
        yield dict(
            frames=rng.integers(0, 255, (lanes, H, W), dtype=np.uint8),
            actions=rng.integers(0, 4, lanes).astype(np.int32),
            rewards=rng.normal(size=lanes).astype(np.float32),
            terminals=term,
            truncations=trunc | (t in trunc_all_at),
            lstm_c=rng.normal(size=(lanes, LSTM)).astype(np.float32),
            lstm_h=rng.normal(size=(lanes, LSTM)).astype(np.float32),
        )


def _feed_host(host, x, shard):
    """One tick `x` of `_trace` into a host replay: the lanes of `shard`."""
    mine = {k: v[shard * LANES:(shard + 1) * LANES] for k, v in x.items()}
    host.append_batch(
        mine["frames"], mine["actions"], mine["rewards"], mine["terminals"],
        mine["lstm_c"], mine["lstm_h"], truncations=mine["truncations"])


def _drive(host, dev, ticks, seed=0, p_term=0.1, p_trunc=0.07):
    append = jax.jit(dev.append)
    ds = dev.init_state()
    rng = np.random.default_rng(seed)
    for t in _trace(rng, ticks, p_term, p_trunc):
        host.append_batch(
            t["frames"], t["actions"], t["rewards"], t["terminals"],
            t["lstm_c"], t["lstm_h"], truncations=t["truncations"],
        )
        ds = append(
            ds, jnp.asarray(t["frames"]), jnp.asarray(t["actions"]),
            jnp.asarray(t["rewards"]), jnp.asarray(t["terminals"]),
            jnp.asarray(t["truncations"]), jnp.asarray(t["lstm_c"]),
            jnp.asarray(t["lstm_h"]),
        )
    return ds


def _ring_frames(dev, ds):
    """Ring rows [0, C) of the frames in the host's shape [C, L, H, W]: the
    device stores them flat and `read_rows` gives the logical shape."""
    return np.asarray(dev.read_rows(ds, 0, dev.capacity)["frames"])


@pytest.mark.parametrize("ticks", [4, 17, 60])
def test_ring_matches_host(ticks):
    host, dev = _make_pair()
    ds = _drive(host, dev, ticks)
    assert int(ds.filled) == host.filled
    assert int(ds.pos) == host.pos
    n = host.filled
    sl = np.arange(n) if n < CAP else np.arange(CAP)
    np.testing.assert_array_equal(_ring_frames(dev, ds)[sl], host.frames[sl])
    np.testing.assert_array_equal(np.asarray(ds.actions)[sl], host.actions[sl])
    np.testing.assert_allclose(
        np.asarray(ds.rewards)[sl], host.rewards[sl], rtol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(ds.dones)[sl], host.dones[sl])
    np.testing.assert_array_equal(np.asarray(ds.valids)[sl], host.valids[sl])
    np.testing.assert_allclose(
        np.asarray(ds.init_c)[sl], host.init_c[sl], rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ds.init_h)[sl], host.init_h[sl], rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ds.priority), host.tree.get(np.arange(CAP)), rtol=1e-5
    )
    assert float(ds.max_priority) == pytest.approx(host.max_priority, rel=1e-6)


def test_ring_matches_host_no_cuts():
    """Pure overlap regime: every sequence comes from the stride carry-over,
    exercising the stored-state-at-window-start bookkeeping."""
    host, dev = _make_pair()
    ds = _drive(host, dev, 40, seed=3, p_term=0.0, p_trunc=0.0)
    n = min(host.filled, CAP)
    sl = np.arange(n)
    np.testing.assert_array_equal(_ring_frames(dev, ds)[sl], host.frames[sl])
    np.testing.assert_allclose(
        np.asarray(ds.init_c)[sl], host.init_c[sl], rtol=1e-6
    )
    assert np.asarray(ds.valids)[sl].all()  # full windows only


# --------------------------------------------------------------------------
# the stored shape (frames flat) and whole rows in and out of it
# --------------------------------------------------------------------------


def test_frames_are_stored_flat():
    """The ring holds frames [C+1, L, h*w] and the builders [lanes, L, h*w]:
    the pixels minor, so that the device's default layout serves every row
    gather and scatter (module docstring); no other field's shape changed."""
    _, dev = _make_pair()
    s = dev.init_state()
    assert s.frames.shape == (CAP + 1, L, H * W) and s.frames.dtype == jnp.uint8
    assert s.buf_frames.shape == (LANES, L, H * W)
    assert s.buf_frames.dtype == jnp.uint8
    assert s.actions.shape == s.valids.shape == (CAP + 1, L)
    assert s.init_c.shape == s.init_h.shape == (CAP + 1, LSTM)
    assert s.buf_c.shape == (LANES, L, LSTM) and s.priority.shape == (CAP,)


def _seeded_rows(rng, n, lstm):
    return dict(
        frames=rng.integers(0, 255, (n, L, H, W), dtype=np.uint8),
        actions=rng.integers(0, 4, (n, L)).astype(np.int32),
        rewards=rng.normal(size=(n, L)).astype(np.float32),
        dones=rng.random((n, L)) < 0.2,
        valids=rng.random((n, L)) < 0.8,
        init_c=rng.normal(size=(n, lstm)).astype(np.float32),
        init_h=rng.normal(size=(n, lstm)).astype(np.float32),
    )


@pytest.mark.parametrize("lstm", [4, 0], ids=["state4", "state0"])
def test_rows_written_are_the_rows_read(lstm):
    """`write_rows` under jit with a traced start, then `read_rows`: bit for
    bit the rows that went in, in their logical shapes, on a ring with and
    without stored state (a core that stores none has `init_c` [n, 0]); every
    ring row outside [start, start + n) and every field that is not a row
    (priority, cursors, the builders, the counter) as it was."""
    dev = DeviceSequenceReplay(
        capacity=CAP, seq_len=L, frame_shape=(H, W), lstm_size=lstm,
        lanes=LANES, stride=STRIDE)
    n, start = 5, 7
    s0 = jax.tree.map(  # every entry its own value, so a stray write shows
        lambda x: (jnp.arange(x.size) % 251).reshape(x.shape).astype(x.dtype),
        dev.init_state())
    rows = _seeded_rows(np.random.default_rng(3), n, lstm)
    # float64 and int64 in: cast to the stored dtypes
    rows["rewards"] = rows["rewards"].astype(np.float64)
    rows["actions"] = rows["actions"].astype(np.int64)
    s1 = jax.jit(dev.write_rows)(s0, rows, jnp.int32(start))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), s1) == jax.tree.map(
        lambda x: (x.shape, x.dtype), s0)
    back = jax.jit(dev.read_rows, static_argnums=(1, 2))(s1, start, start + n)
    assert set(back) == set(ROW_FIELDS)
    for name in ROW_FIELDS:
        stored = getattr(s0, name).dtype
        assert back[name].shape == rows[name].shape, name
        assert back[name].dtype == stored, name
        np.testing.assert_array_equal(
            np.asarray(back[name]), rows[name].astype(stored), err_msg=name)
    assert back["frames"].shape == (n, L, H, W)
    assert back["init_c"].shape == (n, lstm)
    for name in s0._fields:
        was, now = np.asarray(getattr(s0, name)), np.asarray(getattr(s1, name))
        if name in ROW_FIELDS:  # the scratch row CAP among the untouched
            keep = np.r_[0:start, start + n:CAP + 1]
            np.testing.assert_array_equal(now[keep], was[keep], err_msg=name)
        else:
            np.testing.assert_array_equal(now, was, err_msg=name)


def test_rows_written_are_drawn_as_the_host_gives_them():
    """Rows that entered through `write_rows` come out of `assemble` as the
    host replay's `obs` [B, L, H, W, 1] of the same rows."""
    host, dev = _make_pair()
    ds = _drive(host, dev, 60)
    assert int(ds.filled) == CAP
    fresh = dev.write_rows(dev.init_state(), dev.read_rows(ds, 0, CAP), 0)
    fresh = fresh._replace(priority=ds.priority, filled=ds.filled)
    hs = host.sample(8, 0.6)
    idx = jnp.asarray(hs.idx, jnp.int32)
    batch, _ = jax.jit(dev.assemble)(fresh, idx, jnp.float32(0.6))
    assert batch.obs.shape == (8, L, H, W, 1)
    for name, theirs in (("obs", hs.obs), ("action", hs.action),
                         ("reward", hs.reward), ("done", hs.done),
                         ("valid", hs.valid), ("init_c", hs.init_c),
                         ("init_h", hs.init_h)):
        np.testing.assert_array_equal(
            np.asarray(getattr(batch, name)), theirs, err_msg=name)


def test_stacked_shards_keep_the_stored_shape():
    """`stack_seq_shards` puts a device dim in front of whatever rank a leaf
    has: two shards' rings [2, C+1, L, h*w] go through the sharded append and
    the sharded draw + assemble, each shard reading the rows a host replay of
    its own lanes holds and giving `obs` [b, L, H, W, 1] of its own ring."""
    from jax.sharding import Mesh

    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.replay.device_sequence import (
        build_device_r2d2_learn_sharded,
        build_sharded_seq_append,
        device_seq_shardings,
        stack_seq_shards,
    )

    n_dev = 2
    if len(jax.devices()) < n_dev:
        pytest.skip("needs 2 devices")
    hosts = [_make_pair()[0] for _ in range(n_dev)]
    dev = _make_pair()[1]
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("dp",))
    gs = jax.device_put(stack_seq_shards(dev.init_state(), n_dev),
                        device_seq_shardings(mesh))
    assert gs.frames.shape == (n_dev, CAP + 1, L, H * W)
    assert gs.buf_frames.shape == (n_dev, LANES, L, H * W)
    append = jax.jit(build_sharded_seq_append(dev, mesh))
    for x in _trace(np.random.default_rng(21), 30, lanes=n_dev * LANES):
        gs = append(gs, *(jnp.asarray(v) for v in x.values()))
        for d, host in enumerate(hosts):
            _feed_host(host, x, d)
    assert gs.frames.shape == (n_dev, CAP + 1, L, H * W)
    shards = [jax.tree.map(lambda a: jnp.asarray(np.asarray(a)[d]), gs)
              for d in range(n_dev)]
    for d, host in enumerate(hosts):
        _assert_equals_host(dev, shards[d], host, f"shard {d}")
    cfg = Config(
        compute_dtype="float32", history_length=1, hidden_size=32,
        num_cosines=8, lstm_size=LSTM, r2d2_burn_in=2, r2d2_seq_len=L - 2,
        batch_size=4 * n_dev, multi_step=1, gamma=0.9,
    )
    fused = build_device_r2d2_learn_sharded(cfg, 4, dev, mesh)
    idx, batch = jax.jit(fused.draw_assemble)(
        gs, jax.random.PRNGKey(9), jnp.float32(0.6))
    assert batch.obs.shape == (cfg.batch_size, L, H, W, 1)
    idx, obs = np.asarray(idx).reshape(n_dev, -1), np.asarray(batch.obs)
    for d, host in enumerate(hosts):
        np.testing.assert_array_equal(
            obs.reshape(n_dev, -1, L, H, W)[d], host.frames[idx[d]],
            err_msg=f"shard {d}")


# --------------------------------------------------------------------------
# the conditional emit: every tick against the host, on the traffics the
# fused trainers produce
# --------------------------------------------------------------------------

TICKS = 40
# _trace arguments for the traffics the fused trainers produce
TRAFFIC = {
    # freeway between time limits: all lanes emit on the same ticks (6, 9,
    # 12, ...), every other tick is quiet
    "lockstep": dict(p_term=0.0, p_trunc=0.0),
    # freeway's time limit: every lane truncated on ticks 8 and 29 (mid-window
    # and on a tick that would have emitted anyway)
    "lockstep_truncation": dict(p_term=0.0, p_trunc=0.0,
                                trunc_all_at=(8, 29)),
    # catch, breakout: lanes fall out of lockstep
    "random_cuts": dict(),
}


def _assert_equals_host(dev, ds, host, where):
    """Bit for bit: ring rows [0, C) (the scratch row C is the device's own
    business) read in their logical shapes, priorities, cursors, and each
    lane's live builder prefix (its frames flat, as the builder stores
    them)."""
    rows = dev.read_rows(ds, 0, CAP)
    assert set(rows) == set(ROW_FIELDS)
    for name in ROW_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(rows[name]), getattr(host, name)[:CAP],
            err_msg=f"{name} {where}")
    np.testing.assert_array_equal(
        np.asarray(ds.priority),
        host.tree.get(np.arange(CAP)).astype(np.float32),
        err_msg=f"priority {where}")
    assert int(ds.pos) == host.pos, where
    assert int(ds.filled) == host.filled, where
    assert float(ds.max_priority) == np.float32(host.max_priority), where
    lens = np.asarray(ds.buf_len)
    np.testing.assert_array_equal(lens, host._buf_len, err_msg=where)
    for lane, n in enumerate(lens):
        for name in ("frames", "actions", "rewards", "dones", "c", "h"):
            theirs = getattr(host, "_buf_" + name)[lane, :n]
            np.testing.assert_array_equal(
                np.asarray(getattr(ds, "buf_" + name))[lane, :n],
                theirs.reshape(n, H * W) if name == "frames" else theirs,
                err_msg=f"builder {name} lane {lane} {where}")


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_device", "shard_map"])
@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
def test_every_tick_matches_host(traffic, sharded):
    """After EVERY tick, quiet or emitting, the device ring equals the host
    SequenceReplay; under build_sharded_seq_append each shard equals a host
    replay of its own fed that shard's lanes.  `emit_ticks` counts the ticks
    on which some lane (of the shard) emitted."""
    from rainbow_iqn_apex_tpu.replay.device_sequence import (
        build_sharded_seq_append,
        device_seq_shardings,
        stack_seq_shards,
    )

    n_dev = 2 if sharded else 1
    if len(jax.devices()) < n_dev:
        pytest.skip("needs 2 devices")
    hosts = [_make_pair()[0] for _ in range(n_dev)]
    dev = _make_pair()[1]
    if sharded:
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:n_dev]), ("dp",))
        append = jax.jit(build_sharded_seq_append(dev, mesh))
        ds = jax.device_put(stack_seq_shards(dev.init_state(), n_dev),
                            device_seq_shardings(mesh))
    else:
        append = jax.jit(dev.append)
        ds = dev.init_state()
    rng = np.random.default_rng(13)
    emit_ticks = np.zeros(n_dev, np.int64)
    trace = _trace(rng, TICKS, lanes=n_dev * LANES, **TRAFFIC[traffic])
    for t, x in enumerate(trace, 1):
        ds = append(ds, *(jnp.asarray(v) for v in x.values()))
        for d, host in enumerate(hosts):
            mine = {k: v[d * LANES:(d + 1) * LANES] for k, v in x.items()}
            emit_ticks[d] += (mine["terminals"] | mine["truncations"]
                              | (host._buf_len + 1 == L)).any()
            host.append_batch(
                mine["frames"], mine["actions"], mine["rewards"],
                mine["terminals"], mine["lstm_c"], mine["lstm_h"],
                truncations=mine["truncations"],
            )
            shard = jax.tree.map(lambda a: np.asarray(a)[d], ds) \
                if sharded else ds
            _assert_equals_host(dev, shard, host, f"tick {t} shard {d}")
    np.testing.assert_array_equal(
        np.asarray(ds.emit_ticks).reshape(n_dev), emit_ticks)
    assert 0 < emit_ticks.min() and emit_ticks.max() < TICKS  # both branches


def test_emit_ticks_counts_ticks_not_sequences():
    """Three lanes in lockstep emit on ticks 6, 9 and 12: three emitting
    ticks, nine sequences."""
    host, dev = _make_pair()
    ds = _drive(host, dev, 12, p_term=0.0, p_trunc=0.0)
    assert int(ds.emit_ticks) == 3
    assert int(ds.filled) == 9


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def test_append_is_one_conditional_with_an_empty_skip():
    """The emit work sits under ONE cond whose skip branch is empty, and
    nothing outside it makes a builder-sized array but the one-step
    scatters."""
    _, dev = _make_pair()
    x = next(_trace(np.random.default_rng(0), 1))
    jaxpr = jax.make_jaxpr(dev.append)(dev.init_state(), *x.values()).jaxpr
    builder = LANES * L * H * W
    conds, big = [], []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "cond":
                conds.append(eqn)
                continue  # what is inside runs on emitting ticks only
            if eqn.primitive.name != "scatter":
                big.extend(
                    (eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                    if np.prod(v.aval.shape, dtype=np.int64) >= builder)
            for sub in _sub_jaxprs(eqn):
                walk(sub)

    walk(jaxpr)
    assert len(conds) == 1
    assert big == []
    skip, do_emit = conds[0].params["branches"]
    assert len(skip.jaxpr.eqns) == 0
    assert any(e.primitive.name == "scatter" and
               e.outvars[0].aval.shape == (CAP + 1, L, H * W)
               for e in do_emit.jaxpr.eqns)


def test_restore_accepts_a_snapshot_without_emit_ticks(tmp_path):
    """A snapshot written before `emit_ticks` existed restores every field it
    has and leaves the counter at its fresh value."""
    from rainbow_iqn_apex_tpu import train_anakin_r2d2 as prog
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.replay import snapshot_io

    host, dev = _make_pair()
    ds = _drive(host, dev, 17)
    cfg = Config(checkpoint_dir=str(tmp_path), run_id="r", snapshot_replay=True)
    old = {f: np.asarray(v) for f, v in ds._asdict().items()
           if f != "emit_ticks"}
    os.makedirs(os.path.dirname(prog._replay_snapshot_path(cfg)))
    snapshot_io.atomic_savez(prog._replay_snapshot_path(cfg), **old)
    got = prog._maybe_restore_replay(cfg, dev.init_state())
    assert int(got.emit_ticks) == 0
    for f, v in old.items():
        np.testing.assert_array_equal(np.asarray(getattr(got, f)), v, f)
    # and the round trip of a current snapshot keeps the counter
    prog._save_replay(cfg, ds)
    got = prog._maybe_restore_replay(cfg, dev.init_state())
    assert int(got.emit_ticks) == int(ds.emit_ticks) > 0


def _assert_same_state(got, want):
    """Leaf for leaf: shape, dtype and bytes."""
    for f in want._fields:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert a.shape == b.shape and a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def _as_before_pr31(ds):
    """A snapshot's arrays as the trainer wrote them until PR 31: `frames`
    [..., L, H, W] and `buf_frames` [..., L, H, W], every other field as now."""
    snap = {f: np.asarray(v) for f, v in ds._asdict().items()}
    for f in ("frames", "buf_frames"):
        snap[f] = snap[f].reshape(snap[f].shape[:-1] + (H, W))
    return snap


def _snapshot_cfg(tmp_path):
    from rainbow_iqn_apex_tpu import train_anakin_r2d2 as prog
    from rainbow_iqn_apex_tpu.config import Config

    cfg = Config(checkpoint_dir=str(tmp_path), run_id="r", snapshot_replay=True)
    os.makedirs(os.path.dirname(prog._replay_snapshot_path(cfg)))
    return prog, cfg


def test_a_snapshot_restores_bit_for_bit(tmp_path):
    """What `_save_replay` writes (frames in the stored, flat shape) comes
    back leaf for leaf, shape, dtype and bytes."""
    prog, cfg = _snapshot_cfg(tmp_path)
    host, dev = _make_pair()
    ds = _drive(host, dev, 17)
    prog._save_replay(cfg, ds)
    got = prog._maybe_restore_replay(cfg, dev.init_state())
    _assert_same_state(got, ds)
    assert got.frames.shape == (CAP + 1, L, H * W)


@pytest.mark.parametrize("stacked", [False, True], ids=["one_ring", "shards"])
def test_restore_takes_a_snapshot_from_before_frames_were_flat(tmp_path,
                                                               stacked):
    """A snapshot that holds `frames` [C+1, L, H, W] and `buf_frames`
    [lanes, L, H, W] (the stored shape until PR 31; with a device dim in
    front for a dp run) is the same bytes in the same order: it is reshaped
    and taken, not dropped to a cold replay."""
    from rainbow_iqn_apex_tpu.replay import snapshot_io
    from rainbow_iqn_apex_tpu.replay.device_sequence import stack_seq_shards

    prog, cfg = _snapshot_cfg(tmp_path)
    host, dev = _make_pair()
    ds = _drive(host, dev, 17)
    fresh = dev.init_state()
    if stacked:
        ds, fresh = stack_seq_shards(ds, 2), stack_seq_shards(fresh, 2)
    old = _as_before_pr31(ds)
    np.testing.assert_array_equal(  # the old shape is the host's
        old["frames"].reshape(-1, CAP + 1, L, H, W)[0, :host.filled],
        host.frames[:host.filled])
    snapshot_io.atomic_savez(prog._replay_snapshot_path(cfg), **old)
    got = prog._maybe_restore_replay(cfg, fresh)
    assert int(np.asarray(got.filled).max()) == host.filled > 0
    _assert_same_state(got, ds)


@pytest.mark.parametrize("change", ["capacity", "seq_len", "pixels", "lanes"])
def test_restore_degrades_to_cold_on_a_geometry_change(tmp_path, change):
    """A snapshot of another ring (rows, sequence length, pixels a frame, or
    lanes) is not taken: the replay starts cold, whichever shape the
    snapshot's frames have."""
    from rainbow_iqn_apex_tpu.replay import snapshot_io

    prog, cfg = _snapshot_cfg(tmp_path)
    host, dev = _make_pair()
    ds = _drive(host, dev, 17)
    geo = dict(capacity=CAP, seq_len=L, frame_shape=(H, W), lanes=LANES)
    geo.update({"capacity": dict(capacity=CAP + 1),
                "seq_len": dict(seq_len=L + 2),
                "pixels": dict(frame_shape=(H, W + 1)),
                "lanes": dict(lanes=LANES - 1)}[change])
    other = DeviceSequenceReplay(lstm_size=LSTM, stride=STRIDE, **geo)
    fresh = other.init_state()
    for snap in (ds._asdict(), _as_before_pr31(ds)):
        snapshot_io.atomic_savez(prog._replay_snapshot_path(cfg), **snap)
        got = prog._maybe_restore_replay(cfg, fresh)
        assert int(got.filled) == 0 and int(got.emit_ticks) == 0
        _assert_same_state(got, fresh)


def test_assemble_matches_host_sample_fields():
    host, dev = _make_pair()
    ds = _drive(host, dev, 50, seed=5)
    beta = 0.6
    hs = host.sample(8, beta)
    batch, prob = jax.jit(dev.assemble)(
        ds, jnp.asarray(hs.idx, jnp.int32), jnp.float32(beta)
    )
    assert batch.obs.shape == (8, L, H, W, 1) and batch.obs.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(batch.obs), hs.obs)
    np.testing.assert_array_equal(np.asarray(batch.action), hs.action)
    np.testing.assert_allclose(np.asarray(batch.reward), hs.reward, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(batch.done), hs.done)
    np.testing.assert_array_equal(np.asarray(batch.valid), hs.valid)
    np.testing.assert_allclose(np.asarray(batch.init_c), hs.init_c, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(batch.weight), hs.weight, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(prob), hs.prob, rtol=1e-4)


def test_update_priorities_matches_host():
    host, dev = _make_pair()
    ds = _drive(host, dev, 30, seed=7)
    idx = np.array([0, 2, 5], np.int64)
    td = np.array([0.5, 2.0, 0.01], np.float32)
    host.update_priorities(idx, td)
    ds2 = jax.jit(dev.update_priorities)(
        ds, jnp.asarray(idx, jnp.int32), jnp.asarray(td)
    )
    np.testing.assert_allclose(
        np.asarray(ds2.priority), host.tree.get(np.arange(CAP)), rtol=1e-5
    )
    assert float(ds2.max_priority) == pytest.approx(host.max_priority, rel=1e-6)


def test_draw_tracks_priorities():
    host, dev = _make_pair()
    ds = _drive(host, dev, 40, seed=9)
    hot = 3
    pri = np.asarray(ds.priority)
    ds = ds._replace(priority=ds.priority.at[hot].set(pri.sum() * 20))
    idx = jax.jit(dev.draw, static_argnums=2)(ds, jax.random.PRNGKey(0), 64)
    share = float((np.asarray(idx) == hot).mean())
    expected = float(ds.priority[hot] / ds.priority.sum())
    assert share == pytest.approx(expected, abs=0.15)


def test_fused_r2d2_learn_runs():
    """draw -> assemble -> R2D2 learn -> eta-mix write-back as one jitted
    call: finite loss, priorities change at the sampled slots.  44x44
    frames: the conv trunk's three VALID convs need >= ~44 pixels."""
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.r2d2 import init_r2d2_state

    hw = 44
    host = SequenceReplay(
        capacity=CAP, seq_len=L, frame_shape=(hw, hw), lstm_size=LSTM,
        lanes=LANES, stride=STRIDE, seed=0,
    )
    dev = DeviceSequenceReplay(
        capacity=CAP, seq_len=L, frame_shape=(hw, hw), lstm_size=LSTM,
        lanes=LANES, stride=STRIDE,
    )
    append = jax.jit(dev.append)
    ds = dev.init_state()
    rng = np.random.default_rng(11)
    for _ in range(40):
        term = rng.random(LANES) < 0.1
        ds = append(
            ds,
            jnp.asarray(rng.integers(0, 255, (LANES, hw, hw), dtype=np.uint8)),
            jnp.asarray(rng.integers(0, 4, LANES).astype(np.int32)),
            jnp.asarray(rng.normal(size=LANES).astype(np.float32)),
            jnp.asarray(term),
            jnp.asarray((rng.random(LANES) < 0.07) & ~term),
            jnp.asarray(rng.normal(size=(LANES, LSTM)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(LANES, LSTM)).astype(np.float32)),
        )
    cfg = Config(
        compute_dtype="float32", history_length=1, hidden_size=32,
        num_cosines=8, lstm_size=LSTM, r2d2_burn_in=2, r2d2_seq_len=L - 2,
        batch_size=4, multi_step=1, gamma=0.9,
    )
    ts = init_r2d2_state(cfg, 4, jax.random.PRNGKey(0), (hw, hw), channels=1)
    fused = jax.jit(build_device_r2d2_learn(cfg, 4, dev), donate_argnums=(0, 1))
    before = np.asarray(ds.priority).copy()
    ts, ds, info = fused(ts, ds, jax.random.PRNGKey(1), jnp.float32(0.5))
    assert np.isfinite(float(info["loss"]))
    assert (np.asarray(ds.priority) != before).any()
    assert int(ts.step) == 1


# --------------------------------------------------------------------------
# cold-ring guard + dp-sharded variant (per-shard rings under shard_map)
# --------------------------------------------------------------------------


def test_cold_ring_draw_degrades_to_uniform():
    """Zero-priority rings must not collapse every draw to slot 0: with a
    filled prefix the guard draws uniformly over it; dead-empty rings keep
    returning slot 0 but with finite weights (the trainers' warm gate is
    the real protection — this bounds the damage if one forgets it)."""
    _, dev = _make_pair()
    ds = dev.init_state()
    # dead-empty: slot 0, finite IS weights
    idx = dev.draw(ds, jax.random.PRNGKey(0), 32)
    assert set(np.asarray(idx).tolist()) == {0}
    batch, prob = dev.assemble(ds, idx, jnp.float32(0.5))
    assert np.isfinite(np.asarray(batch.weight)).all()
    # filled prefix with zeroed priorities: uniform over the prefix
    ds = ds._replace(filled=jnp.int32(5))
    idx = np.asarray(dev.draw(ds, jax.random.PRNGKey(1), 64))
    assert idx.max() < 5
    assert len(set(idx.tolist())) > 1


@pytest.mark.slow
class TestShardedSequenceLearn:
    """Per-shard sequence rings: the stacked-shard append equals independent
    per-shard rings, and IS weights follow the psum/pmax mixture math."""

    N_DEV = 4
    LANES_PER = 2

    def _mesh(self):
        from jax.sharding import Mesh

        return Mesh(np.array(jax.devices()[: self.N_DEV]), ("dp",))

    def _local(self):
        return DeviceSequenceReplay(
            capacity=CAP, seq_len=L, frame_shape=(H, W), lstm_size=LSTM,
            lanes=self.LANES_PER, stride=STRIDE, priority_exponent=OMEGA,
            priority_eps=EPS,
        )

    def _fill(self, ticks=40, seed=3):
        """Drive the shard_map'd append and, in parallel, N independent
        local rings fed the same lane slices — they must agree."""
        import jax as _jax

        from rainbow_iqn_apex_tpu.replay.device_sequence import (
            build_sharded_seq_append,
            device_seq_shardings,
            stack_seq_shards,
        )

        if len(_jax.devices()) < self.N_DEV:
            pytest.skip("needs 4 devices")
        mesh = self._mesh()
        local = self._local()
        append_sh = _jax.jit(build_sharded_seq_append(local, mesh))
        gs = _jax.device_put(
            stack_seq_shards(local.init_state(), self.N_DEV),
            device_seq_shardings(mesh),
        )
        refs = [local.init_state() for _ in range(self.N_DEV)]
        ref_append = _jax.jit(local.append)
        rng = np.random.default_rng(seed)
        Lt = self.N_DEV * self.LANES_PER
        for _ in range(ticks):
            term = rng.random(Lt) < 0.1
            t = dict(
                frames=rng.integers(0, 255, (Lt, H, W), dtype=np.uint8),
                actions=rng.integers(0, 4, Lt).astype(np.int32),
                rewards=rng.normal(size=Lt).astype(np.float32),
                terminals=term,
                truncations=(rng.random(Lt) < 0.07) & ~term,
                lstm_c=rng.normal(size=(Lt, LSTM)).astype(np.float32),
                lstm_h=rng.normal(size=(Lt, LSTM)).astype(np.float32),
            )
            gs = append_sh(gs, *(jnp.asarray(v) for v in t.values()))
            for d in range(self.N_DEV):
                sl = slice(d * self.LANES_PER, (d + 1) * self.LANES_PER)
                refs[d] = ref_append(
                    refs[d], *(jnp.asarray(v[sl]) for v in t.values())
                )
        return mesh, local, gs, refs

    def test_stacked_append_equals_independent_shards(self):
        _, _, gs, refs = self._fill()
        for d, ref in enumerate(refs):
            got = jax.tree.map(lambda x: np.asarray(x)[d], gs)
            for field in ("frames", "actions", "priority", "pos", "filled",
                          "init_c", "valids"):
                assert np.allclose(
                    np.asarray(getattr(got, field)),
                    np.asarray(getattr(ref, field)),
                ), (d, field)

    def test_sharded_is_weights_match_mixture_math(self):
        from rainbow_iqn_apex_tpu.config import Config
        from rainbow_iqn_apex_tpu.replay.device_sequence import (
            build_device_r2d2_learn_sharded,
        )

        mesh, local, gs, refs = self._fill()
        cfg = Config(
            compute_dtype="float32", history_length=1, hidden_size=32,
            num_cosines=8, lstm_size=LSTM, r2d2_burn_in=2,
            r2d2_seq_len=L - 2, batch_size=8, multi_step=1, gamma=0.9,
        )
        fused = build_device_r2d2_learn_sharded(cfg, 4, local, mesh)
        beta = jnp.float32(0.6)
        idx, batch = jax.jit(fused.draw_assemble)(
            gs, jax.random.PRNGKey(9), beta
        )
        idx = np.asarray(idx)
        w = np.asarray(batch.weight)
        # host recomputation of the mixture formula from the shard states
        b_loc = cfg.batch_size // self.N_DEV
        n_global = sum(int(r.filled) for r in refs)
        want = []
        for d, ref in enumerate(refs):
            p = np.asarray(ref.priority)
            # cold shards would use the uniform guard; these are warm
            assert p.sum() > 0
            prob = np.maximum(p[idx[d * b_loc:(d + 1) * b_loc]] / p.sum(),
                              1e-12)
            nq = np.maximum(n_global * prob / self.N_DEV, 1e-12)
            want.append(nq ** (-float(beta)))
        want = np.concatenate(want)
        want = want / want.max()
        assert np.allclose(w, want, rtol=1e-5), (w, want)


def test_grouped_sequence_sample_matches_sequential_semantics():
    """sample_grouped on the sequence ring: each group's draw, gathered
    batch and max-normalised IS weights equal an independent batch-sized
    sample at the same key (G groups == G sequential reference steps), and
    grouped write-back applies groups in order."""
    host, dev = _make_pair()
    ds = _drive(host, dev, 60)
    B, G = 3, 2
    beta = jnp.float32(0.6)
    key = jax.random.PRNGKey(5)
    idx, batch, prob = dev.sample_grouped(ds, key, B, G, beta)
    assert idx.shape == (G, B)
    assert batch.obs.shape[0] == G * B

    keys = jax.random.split(key, G)
    for g in range(G):
        idx_g = dev.draw(ds, keys[g], B)
        np.testing.assert_array_equal(np.asarray(idx[g]), np.asarray(idx_g))
        batch_g, prob_g = dev.assemble(ds, idx_g, beta)
        sl = slice(g * B, (g + 1) * B)
        np.testing.assert_allclose(np.asarray(batch.weight[sl]),
                                   np.asarray(batch_g.weight), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(batch.obs[sl]),
                                   np.asarray(batch_g.obs))
        np.testing.assert_allclose(np.asarray(prob[sl]),
                                   np.asarray(prob_g), rtol=1e-6)

    eligible = np.flatnonzero(np.asarray(ds.priority) > 0)
    slot = int(eligible[0])
    dup = jnp.asarray(np.tile(np.array([slot], np.int32), (G, 1)))
    tds = jnp.asarray(np.array([0.8, 0.2], np.float32))
    out = dev.update_priorities_grouped(ds, dup, tds)
    want = (0.2 + dev.eps) ** dev.omega  # last group wins
    assert float(out.priority[slot]) == pytest.approx(want, rel=1e-6)


def test_fused_r2d2_learn_grouped_runs():
    """build_device_r2d2_learn honors cfg.sample_groups: [G*B] sequence
    batch, priorities back for every group, finite loss."""
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.ops.r2d2 import init_r2d2_state

    hw = 44
    dev = DeviceSequenceReplay(
        capacity=CAP, seq_len=L, frame_shape=(hw, hw), lstm_size=LSTM,
        lanes=LANES, stride=STRIDE,
    )
    append = jax.jit(dev.append)
    ds = dev.init_state()
    rng = np.random.default_rng(12)
    for _ in range(40):
        term = rng.random(LANES) < 0.1
        ds = append(
            ds,
            jnp.asarray(rng.integers(0, 255, (LANES, hw, hw), dtype=np.uint8)),
            jnp.asarray(rng.integers(0, 4, LANES).astype(np.int32)),
            jnp.asarray(rng.normal(size=LANES).astype(np.float32)),
            jnp.asarray(term),
            jnp.asarray((rng.random(LANES) < 0.07) & ~term),
            jnp.asarray(rng.normal(size=(LANES, LSTM)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(LANES, LSTM)).astype(np.float32)),
        )
    cfg = Config(
        compute_dtype="float32", history_length=1, hidden_size=32,
        num_cosines=8, lstm_size=LSTM, r2d2_burn_in=2, r2d2_seq_len=L - 2,
        batch_size=2, sample_groups=2, multi_step=1, gamma=0.9,
    )
    ts = init_r2d2_state(cfg, 4, jax.random.PRNGKey(0), (hw, hw), channels=1)
    fused = jax.jit(build_device_r2d2_learn(cfg, 4, dev),
                    donate_argnums=(0, 1))
    before = np.asarray(ds.priority).copy()
    ts, ds, info = fused(ts, ds, jax.random.PRNGKey(1), jnp.float32(0.5))
    assert np.isfinite(float(info["loss"]))
    assert info["priorities"].shape == (4,)  # G*B
    assert (np.asarray(ds.priority) != before).any()


def test_sharded_sequence_grouped_weights_normalise_per_group():
    """cfg.sample_groups on the SHARDED sequence learner: [n_dev * G * b_loc]
    batch, per-group global max weight == 1 (pmax across shards within each
    group), write-back lands."""
    from rainbow_iqn_apex_tpu.config import Config
    from rainbow_iqn_apex_tpu.replay.device_sequence import (
        build_device_r2d2_learn_sharded,
    )

    tc = TestShardedSequenceLearn()
    mesh, local, gs, _refs = tc._fill()
    G = 2
    cfg = Config(
        compute_dtype="float32", history_length=1, hidden_size=32,
        num_cosines=8, lstm_size=LSTM, r2d2_burn_in=2, r2d2_seq_len=L - 2,
        batch_size=tc.N_DEV * 2, sample_groups=G, multi_step=1, gamma=0.9,
    )
    builder = build_device_r2d2_learn_sharded(cfg, 4, local, mesh)
    idx, batch = builder.draw_assemble(gs, jax.random.PRNGKey(7),
                                       jnp.float32(0.5))
    b_loc = cfg.batch_size // tc.N_DEV
    assert batch.obs.shape[0] == tc.N_DEV * G * b_loc
    w = np.asarray(batch.weight).reshape(tc.N_DEV, G, b_loc)
    for g in range(G):
        assert w[:, g].max() == pytest.approx(1.0, rel=1e-5), f"group {g}"
    assert np.all(w > 0)
