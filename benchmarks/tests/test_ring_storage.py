"""How the ring stores a row is the program's business alone: the benchmark
fills, snapshots and re-makes ring rows in their logical shapes through
`benchmarks/ringrows.py`, which calls the replay's own `write_rows` /
`read_rows` where it has them.

The proof is a replay that stores frames another way (`FlatRing`: the ring
`[rows, L, h*w]`, the builder `[lanes, L, h*w]`, the shape ROADMAP S1 asks
for) under the tiny `r2d2-fused` cell: the cell is `correct` under its own
limits and learns the same first loss as over the program's ring, and its
fp8 control still fails.  The cases round it pin the two row functions on both
rings, and one static case holds every benchmark source but `ringrows.py` to
never reaching into a replay state's row arrays.
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from rainbow_iqn_apex_tpu.replay import device_sequence
from rainbow_iqn_apex_tpu.replay.device_sequence import DeviceSequenceReplay
from rainbow_iqn_apex_tpu.replay.sequence import SequenceReplay

from benchmarks import check, ringfill, ringrows
from benchmarks.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
LANES, L, STRIDE, CAP, H, W = 3, 6, 3, 16, 8, 8


class FlatRing(DeviceSequenceReplay):
    """The program's ring with frames stored `[rows, L, h*w]` and the builder
    `[lanes, L, h*w]`.  The program has no hook for a stored shape yet, so
    `append` and `assemble` run the parent's code on a `[..., h*w, 1]` view of
    the same bytes."""

    def _flat(self, x):
        return x.reshape(x.shape[:2] + (-1,))

    def _view(self, s, fn):
        return s._replace(frames=fn(s.frames), buf_frames=fn(s.buf_frames))

    def init_state(self):
        return self._view(super().init_state(), self._flat)

    def append(self, s, frames, *rest):
        s = super().append(self._view(s, lambda x: x[..., None]),
                           frames.reshape(frames.shape[0], -1, 1), *rest)
        return self._view(s, lambda x: x[..., 0])

    def assemble(self, s, idx, beta, **kw):
        batch, prob = super().assemble(s, idx, beta, **kw)
        obs = batch.obs.reshape(batch.obs.shape[:2] + self.frame_shape + (1,))
        return batch.replace(obs=obs), prob

    def write_rows(self, s, rows, start):
        stored = dict(rows, frames=self._flat(rows["frames"]))
        return s._replace(**{
            name: jax.lax.dynamic_update_slice_in_dim(
                getattr(s, name), stored[name].astype(getattr(s, name).dtype),
                start, 0) for name in ringrows.FIELDS})

    def read_rows(self, s, start, stop):
        rows = {name: getattr(s, name)[start:stop] for name in ringrows.FIELDS}
        rows["frames"] = rows["frames"].reshape(
            rows["frames"].shape[:2] + self.frame_shape)
        return rows


RINGS = {"as-made": DeviceSequenceReplay, "flat": FlatRing}


def _replay(ring, lstm=4, cls=None):
    return (cls or RINGS[ring])(
        capacity=CAP, seq_len=L, frame_shape=(H, W), lstm_size=lstm,
        lanes=LANES, stride=STRIDE)


# ------------------------------------------------------- the row functions
@pytest.mark.parametrize("lstm", [4, 0], ids=["state4", "state0"])
@pytest.mark.parametrize("ring", sorted(RINGS))
def test_rows_written_are_the_rows_read(ring, lstm):
    """Round trip under jit with a traced start, bit for bit, on a ring with
    and without stored state; nothing but rows [start, start + n) moves."""
    replay, n, start = _replay(ring, lstm), 5, 7
    s0 = jax.tree.map(  # every entry its own value, so a stray write shows
        lambda x: (jnp.arange(x.size) % 251).reshape(x.shape).astype(x.dtype),
        replay.init_state())
    rows = ringfill.rows(jax.random.PRNGKey(3), 100 + np.arange(n), L, (H, W),
                         lstm, 3)
    write = jax.jit(lambda s, r, at: ringrows.write_rows(replay, s, r, at))
    s1 = write(s0, rows, jnp.int32(start))
    back = ringrows.read_rows(replay, s1, start, start + n)
    assert set(back) == set(ringrows.FIELDS)
    for name in ringrows.FIELDS:
        assert back[name].shape == rows[name].shape, name
        np.testing.assert_array_equal(
            np.asarray(back[name]),
            np.asarray(rows[name]).astype(back[name].dtype), err_msg=name)
    assert back["frames"].dtype == np.uint8 and back["valids"].dtype == bool
    assert back["init_c"].shape == (n, lstm)
    # the rest of the ring (the scratch row CAP among it), read logically
    for lo, hi in ((0, start), (start + n, CAP + 1)):
        was = ringrows.read_rows(replay, s0, lo, hi)
        now = ringrows.read_rows(replay, s1, lo, hi)
        for name in ringrows.FIELDS:
            np.testing.assert_array_equal(
                np.asarray(now[name]), np.asarray(was[name]), err_msg=name)
    for name in s0._fields:
        if name not in ringrows.FIELDS:  # priority, pos, filled, builders
            np.testing.assert_array_equal(
                np.asarray(getattr(s1, name)), np.asarray(getattr(s0, name)),
                err_msg=name)


@pytest.mark.parametrize("ring", sorted(RINGS))
def test_a_filled_ring_reads_what_the_host_replay_holds(ring):
    """After the same appends, `read_rows` gives row for row what the host
    `SequenceReplay` (the program's own reference) stores."""
    lstm = 4
    host = SequenceReplay(capacity=CAP, seq_len=L, frame_shape=(H, W),
                          lstm_size=lstm, lanes=LANES, stride=STRIDE, seed=0)
    replay = _replay(ring, lstm)
    append, ds = jax.jit(replay.append), replay.init_state()
    rng = np.random.default_rng(1)
    for _ in range(60):
        term = rng.random(LANES) < 0.1
        trunc = (rng.random(LANES) < 0.07) & ~term
        frames = rng.integers(0, 255, (LANES, H, W), dtype=np.uint8)
        actions = rng.integers(0, 4, LANES).astype(np.int32)
        rewards = rng.normal(size=LANES).astype(np.float32)
        c, h = rng.normal(size=(2, LANES, lstm)).astype(np.float32)
        host.append_batch(frames, actions, rewards, term, c, h,
                          truncations=trunc)
        ds = append(ds, frames, actions, rewards, term, trunc, c, h)
    assert int(ds.filled) == host.filled == CAP
    rows = ringrows.read_rows(replay, ds, 0, CAP)
    for name in ringrows.FIELDS:
        np.testing.assert_array_equal(
            np.asarray(rows[name]), getattr(host, name)[:CAP], err_msg=name)


def test_fill_writes_the_seeded_rows_on_either_ring():
    """`ringfill.fill` (a loop of whole chunks, then the rest) leaves on both
    rings the rows `ringfill.rows` makes, and the rows past them as born."""
    key, n, cap = jax.random.PRNGKey(5), ringfill.CHUNK + 9, ringfill.CHUNK + 12
    want = ringfill.rows(key, np.arange(n), L, (H, W), 4, 3)
    for ring, cls in RINGS.items():
        replay = cls(capacity=cap, seq_len=L, frame_shape=(H, W), lstm_size=4,
                     lanes=LANES, stride=STRIDE)
        state = jax.jit(lambda k: ringfill.fill(
            replay, replay.init_state(), k, n, 3))(key)
        got = ringrows.read_rows(replay, state, 0, cap + 1)
        for name in ringrows.FIELDS:
            np.testing.assert_array_equal(
                np.asarray(got[name][:n]),
                np.asarray(want[name]).astype(got[name].dtype),
                err_msg=f"{ring} {name}")
            assert not np.asarray(got[name][n:]).any(), (ring, name)
        assert int(state.filled) == 0 and int(state.pos) == 0


# ------------------------------------------------------------- the tiny cell
def _tiny_driver(seed):
    from benchmarks.drivers.fused_r2d2 import Driver

    return Driver(tiny.r2d2_fields(), tiny.traffic("freeway-16lanes"), seed, 1)


def test_tiny_cell_is_correct_over_a_ring_stored_flat(monkeypatch):
    limits = tiny.load("workloads", "r2d2-fused")["limits"]
    exact = {"window_steps_missing": 0.0, "first_steps_missing": 0.0}
    plain = _tiny_driver(7)
    plain.warm_up()
    monkeypatch.setattr(device_sequence, "DeviceSequenceReplay", FlatRing)
    drv = _tiny_driver(7)
    h, w = drv.replay.frame_shape
    assert isinstance(drv.replay, FlatRing)
    assert drv.carry[1].frames.shape == (drv.capacity + 1, drv.replay.L, h * w)
    assert drv.carry[1].buf_frames.shape == (drv.lanes, drv.replay.L, h * w)
    drv.warm_up()
    assert drv.snap["frames"].shape == (drv.lanes, drv.replay.L, h, w)
    # the same bytes in the same rows: the same first learning dispatch
    np.testing.assert_array_equal(drv.first_learning["loss"],
                                  plain.first_learning["loss"])
    for name in ringrows.FIELDS:
        np.testing.assert_array_equal(drv.snap[name], plain.snap[name])
    prog = drv.program_side()
    ref = drv.reference_side(None, prog["priority_after"] != drv.priority0())
    sound, rows = check.verdict(
        {**check.compare(prog, ref, drv.params0), **exact}, limits)
    assert sound, rows
    control = drv.reference_side("fp8", None)
    ok, rows = check.verdict(
        {**check.compare(control, ref, drv.params0), **exact}, limits)
    assert not ok, rows


# ------------------------------------------------------------ the static case
STATE_ROW_FIELDS = set(ringrows.FIELDS) | {
    f for f in device_sequence.DeviceSeqState._fields if f.startswith("buf_")}


def _reaches_into_rows(source: str):
    """Lines of `source` that name a replay state's row array: an attribute
    `.frames`, `.buf_c`, ...; a `getattr` by a name that is one of them or is
    not a literal; `_replace(frames=...)`."""
    hits = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Attribute) and node.attr in STATE_ROW_FIELDS:
            hits.append(node.lineno)
        elif isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id == "getattr":
                name = node.args[1] if len(node.args) > 1 else None
                if not (isinstance(name, ast.Constant)
                        and name.value not in STATE_ROW_FIELDS):
                    hits.append(node.lineno)
            elif isinstance(fn, ast.Attribute) and fn.attr == "_replace":
                if {k.arg for k in node.keywords} & STATE_ROW_FIELDS:
                    hits.append(node.lineno)
    return sorted(set(hits))


def _sources():
    for dirpath, dirnames, filenames in os.walk(BENCH):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__"
                             and os.path.join(dirpath, d) != HERE)
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def test_only_ringrows_reaches_into_a_replay_states_rows():
    seen = {}
    for path in _sources():
        with open(path, encoding="utf-8") as f:
            seen[os.path.relpath(path, BENCH)] = _reaches_into_rows(f.read())
    assert len(seen) > 40 and "drivers/fused_r2d2.py" in seen
    # the detector sees the one file that may (and the parent's two that did:
    # `getattr(ss, name)` in the driver, `arrays["frames"].shape` apart)
    assert seen.pop("ringrows.py")
    assert {k: v for k, v in seen.items() if v} == {}
    assert _reaches_into_rows("x = getattr(ss, name)[n:c]") == [1]
    assert _reaches_into_rows("h = ss.frames.shape[2]") == [1]
    assert _reaches_into_rows("ss = ss._replace(buf_frames=b)") == [1]
    assert _reaches_into_rows("ss = ss._replace(priority=p, pos=n)") == []
