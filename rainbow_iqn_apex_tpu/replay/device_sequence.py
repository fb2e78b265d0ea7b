"""Device-resident stored-state sequence replay (the R2D2 twin of
replay/device.py).

Same semantics as the host SequenceReplay (replay/sequence.py) — per-lane
builders chopping episode streams into overlapping fixed-length sequences
with the actor's LSTM state at each window start, two-channel cuts (flush on
terminal OR truncation, `done` only for true terminals), max-priority
insertion, eta-mix write-back — but the ring, the builders, and prioritized
sampling all live in HBM as one pytree, so the fused R2D2 Anakin tick
(act -> env.step -> append -> learn) compiles into a single XLA graph.

The one structural difference from the host version: the number of sequences
EMITTED per tick is data-dependent (a lane emits when its builder fills or
its episode cuts), which XLA cannot express as a dynamic store count.  The
ring therefore carries ONE scratch row (index C): on a tick where any lane
emits, every lane scatters its builder window somewhere — emitting lanes to
`(pos + rank) % C` (rank = that lane's position among this tick's emitters),
non-emitting lanes to the scratch row — so shapes stay static and the write
is one batched scatter.  A tick on which no lane emits skips all of that
under one `lax.cond` and leaves the scratch row as it was.  Sampling,
priorities, snapshots and the benchmark's `correct` only ever see rows
[0, C).

Frames are stored FLAT: the ring `[C+1, L, h*w]`, the builders
`[lanes, L, h*w]`.  The device's default layout of a 4-D `[rows, L, h, w]`
uint8 array puts the rows in the 128 lanes, which no row gather or scatter
can use, so a loop over it converts the whole ring on the way in and out of
every dispatch; with the pixels minor the default layout is the one every
gather, scatter and slice here wants.  `append` takes `[lanes, h, w]` frames
and `assemble` gives `[B, L, h, w, 1]` observations all the same, and whole
rows pass in and out in their logical shapes through `write_rows` /
`read_rows`.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import chex
import jax
import jax.numpy as jnp

from rainbow_iqn_apex_tpu.obs import device_scopes
from rainbow_iqn_apex_tpu.ops.r2d2 import SequenceBatch


class DeviceSeqState(NamedTuple):
    # sequence ring, one scratch row at index C
    frames: jnp.ndarray  # [C+1, L, H*W] uint8 (stored flat: module docstring)
    actions: jnp.ndarray  # [C+1, L] int32
    rewards: jnp.ndarray  # [C+1, L] f32
    dones: jnp.ndarray  # [C+1, L] bool
    valids: jnp.ndarray  # [C+1, L] bool
    init_c: jnp.ndarray  # [C+1, lstm] f32
    init_h: jnp.ndarray  # [C+1, lstm] f32
    priority: jnp.ndarray  # [C] f32 (already ^omega, like the host tree)
    pos: jnp.ndarray  # scalar i32 — next ring slot
    filled: jnp.ndarray  # scalar i32
    max_priority: jnp.ndarray  # scalar f32
    # per-lane builders
    buf_frames: jnp.ndarray  # [lanes, L, H*W] uint8
    buf_actions: jnp.ndarray  # [lanes, L] i32
    buf_rewards: jnp.ndarray  # [lanes, L] f32
    buf_dones: jnp.ndarray  # [lanes, L] bool
    buf_c: jnp.ndarray  # [lanes, L, lstm] f32
    buf_h: jnp.ndarray  # [lanes, L, lstm] f32
    buf_len: jnp.ndarray  # [lanes] i32
    # ticks on which some lane emitted (append's conditional took do_emit)
    emit_ticks: jnp.ndarray  # scalar i32


# the fields `write_rows` / `read_rows` pass: one entry a ring row
ROW_FIELDS = ("frames", "actions", "rewards", "dones", "valids",
              "init_c", "init_h")


class DeviceSequenceReplay:
    """Pure-functional sequence replay: all methods are jit-safe
    (state, ...) -> state transforms over a DeviceSeqState pytree.

    The scratch row (ring index C) is rewritten only on ticks where some
    lane emits; sampling, priorities, snapshots' readers and the benchmark's
    `correct` see rows [0, C) alone."""

    def __init__(
        self,
        capacity: int,
        seq_len: int,
        frame_shape: Tuple[int, int],
        lstm_size: int,
        lanes: int,
        stride: Optional[int] = None,
        priority_exponent: float = 0.9,
        priority_eps: float = 1e-6,
    ):
        if stride is not None and not (0 < stride <= seq_len):
            raise ValueError("stride must be in (0, seq_len]")
        if capacity < lanes:
            raise ValueError(
                f"capacity ({capacity}) must be >= lanes ({lanes}): every "
                "lane can emit a sequence on the same tick"
            )
        self.capacity = capacity
        self.L = seq_len
        self.lanes = lanes
        self.stride = stride or max(seq_len // 2, 1)
        self.omega = priority_exponent
        self.eps = priority_eps
        self.frame_shape = frame_shape
        self.lstm_size = lstm_size

    def init_state(self) -> DeviceSeqState:
        C, L, (h, w), m, lanes = (
            self.capacity, self.L, self.frame_shape, self.lstm_size, self.lanes,
        )
        return DeviceSeqState(
            frames=jnp.zeros((C + 1, L, h * w), jnp.uint8),
            actions=jnp.zeros((C + 1, L), jnp.int32),
            rewards=jnp.zeros((C + 1, L), jnp.float32),
            dones=jnp.zeros((C + 1, L), bool),
            valids=jnp.zeros((C + 1, L), bool),
            init_c=jnp.zeros((C + 1, m), jnp.float32),
            init_h=jnp.zeros((C + 1, m), jnp.float32),
            priority=jnp.zeros((C,), jnp.float32),
            pos=jnp.int32(0),
            filled=jnp.int32(0),
            max_priority=jnp.float32(1.0),
            buf_frames=jnp.zeros((lanes, L, h * w), jnp.uint8),
            buf_actions=jnp.zeros((lanes, L), jnp.int32),
            buf_rewards=jnp.zeros((lanes, L), jnp.float32),
            buf_dones=jnp.zeros((lanes, L), bool),
            buf_c=jnp.zeros((lanes, L, m), jnp.float32),
            buf_h=jnp.zeros((lanes, L, m), jnp.float32),
            buf_len=jnp.zeros((lanes,), jnp.int32),
            emit_ticks=jnp.int32(0),
        )

    # ------------------------------------------------------------- appending
    def append(
        self,
        s: DeviceSeqState,
        frames: jnp.ndarray,  # [lanes, H, W] uint8 — frame the action saw
        actions: jnp.ndarray,  # [lanes] i32
        rewards: jnp.ndarray,  # [lanes] f32
        terminals: jnp.ndarray,  # [lanes] bool — TRUE terminals only
        truncations: jnp.ndarray,  # [lanes] bool — time-limit cuts
        lstm_c: jnp.ndarray,  # [lanes, lstm] actor state BEFORE this step
        lstm_h: jnp.ndarray,
    ) -> DeviceSeqState:
        """One lockstep tick of all lanes (mirror of _append_locked,
        replay/sequence.py): the one-step builder writes on every tick; the
        emit work (zero-pad, ring scatter, max-priority insertion, overlap
        carry-over) only on a tick where some lane emits."""
        lanes, L, C, stride = self.lanes, self.L, self.capacity, self.stride
        lane = jnp.arange(lanes)
        k = s.buf_len  # [lanes] write offsets, in [0, L-1]
        klen = k + 1  # post-write lengths

        cut = terminals | truncations
        emit = cut | (klen == L)
        # flush (cut): restart empty.  full (no cut): keep last L-stride
        # steps.  neither: just the incremented length.
        new_len = jnp.where(cut, 0, jnp.where(emit, L - stride, klen))
        st = s._replace(
            # the frame in the builder's stored per-step shape, read off it
            buf_frames=s.buf_frames.at[lane, k].set(
                frames.reshape((lanes,) + s.buf_frames.shape[2:])),
            buf_actions=s.buf_actions.at[lane, k].set(
                actions.astype(jnp.int32)),
            buf_rewards=s.buf_rewards.at[lane, k].set(
                rewards.astype(jnp.float32)),
            buf_dones=s.buf_dones.at[lane, k].set(terminals),
            buf_c=s.buf_c.at[lane, k].set(lstm_c.astype(jnp.float32)),
            buf_h=s.buf_h.at[lane, k].set(lstm_h.astype(jnp.float32)),
            buf_len=new_len.astype(jnp.int32),
        )

        def do_emit(st: DeviceSeqState) -> DeviceSeqState:
            # ring slots: emitters take pos+rank (mod C), the rest the
            # scratch row
            rank = jnp.cumsum(emit.astype(jnp.int32)) - 1
            n_emit = emit.sum().astype(jnp.int32)
            slots = jnp.where(emit, (st.pos + rank) % C, C)

            vm = jnp.arange(L)[None, :] < klen[:, None]  # [lanes, L] valid

            def zpad(buf):  # steps past a lane's length read zero
                mask = vm.reshape(vm.shape + (1,) * (buf.ndim - 2))
                return jnp.where(mask, buf, jnp.zeros_like(buf))

            # max-priority insertion for emitted slots (clip scratch writes
            # away by scattering into a length-C+1 view and dropping the
            # tail)
            pri_ext = jnp.concatenate(
                [st.priority, jnp.zeros((1,), jnp.float32)])
            pri_ext = pri_ext.at[slots].set(
                jnp.where(emit, st.max_priority, pri_ext[slots])
            )

            # overlap carry-over: a full, uncut lane keeps its last
            # L-stride steps at the front of its builder
            keep_tail = emit & ~cut

            def carry_over(buf):
                sel = jnp.reshape(keep_tail, (lanes,) + (1,) * (buf.ndim - 1))
                return jnp.where(sel, jnp.roll(buf, -stride, axis=1), buf)

            return st._replace(
                frames=st.frames.at[slots].set(zpad(st.buf_frames)),
                actions=st.actions.at[slots].set(zpad(st.buf_actions)),
                rewards=st.rewards.at[slots].set(zpad(st.buf_rewards)),
                dones=st.dones.at[slots].set(zpad(st.buf_dones)),
                valids=st.valids.at[slots].set(vm),
                init_c=st.init_c.at[slots].set(st.buf_c[:, 0]),
                init_h=st.init_h.at[slots].set(st.buf_h[:, 0]),
                priority=pri_ext[:C],
                pos=(st.pos + n_emit) % C,
                filled=jnp.minimum(st.filled + n_emit, C),
                emit_ticks=st.emit_ticks + 1,
                buf_frames=carry_over(st.buf_frames),
                buf_actions=carry_over(st.buf_actions),
                buf_rewards=carry_over(st.buf_rewards),
                buf_dones=carry_over(st.buf_dones),
                buf_c=carry_over(st.buf_c),
                buf_h=carry_over(st.buf_h),
            )

        return jax.lax.cond(emit.any(), do_emit, lambda st: st, st)

    # ------------------------------------------------------------ whole rows
    def write_rows(self, s: DeviceSeqState, rows, start) -> DeviceSeqState:
        """Ring rows [start, start + n) from `rows` in their logical shapes
        (`frames [n, L, h, w]`, `actions`/`rewards`/`dones`/`valids` [n, L],
        `init_c`/`init_h` [n, lstm]), cast to the stored dtypes.  n is
        static, `start` may be traced; priority, pos, filled and the builders
        are untouched."""
        stored = dict(rows, frames=jnp.reshape(
            rows["frames"], rows["frames"].shape[:2] + s.frames.shape[2:]))
        return s._replace(**{
            name: jax.lax.dynamic_update_slice_in_dim(
                getattr(s, name), stored[name].astype(getattr(s, name).dtype),
                start, 0)
            for name in ROW_FIELDS})

    def read_rows(self, s: DeviceSeqState, start: int, stop: int):
        """Ring rows [start, stop) (static bounds) in the logical shapes
        `write_rows` takes."""
        rows = {name: getattr(s, name)[start:stop] for name in ROW_FIELDS}
        return dict(rows, frames=self._unflat(rows["frames"]))

    def _unflat(self, frames: jnp.ndarray) -> jnp.ndarray:
        """[n, L, h*w] stored frames in their logical shape [n, L, h, w]."""
        return frames.reshape(frames.shape[:2] + tuple(self.frame_shape))

    # -------------------------------------------------------------- sampling
    def _effective_priority(self, s: DeviceSeqState) -> jnp.ndarray:
        """Cold-ring guard: when every priority is zero (empty ring, or a
        ring whose only writes were scratch-row misses), degrade to a uniform
        draw over the filled prefix — never the degenerate always-slot-0 draw
        a zero cdf would produce.  Trainers still must warm-gate learning
        (see build_device_r2d2_learn); this guard bounds the damage if one
        doesn't."""
        p = s.priority
        uniform = (
            jnp.arange(p.shape[0]) < jnp.maximum(s.filled, 1)
        ).astype(jnp.float32)
        return jnp.where(p.sum() > 0.0, p, uniform)

    @jax.named_scope(device_scopes.REPLAY_DRAW)
    def draw(self, s: DeviceSeqState, key: chex.PRNGKey,
             batch_size: int) -> jnp.ndarray:
        """Stratified proportional draw over ring priorities (mirror of
        SumTree.sample_stratified)."""
        p = self._effective_priority(s)
        total = p.sum()
        cdf = jnp.cumsum(p)
        u = (jnp.arange(batch_size) + jax.random.uniform(key, (batch_size,)))
        u = u / batch_size * total
        return jnp.clip(
            jnp.searchsorted(cdf, u, side="right"), 0, p.shape[0] - 1
        ).astype(jnp.int32)

    @jax.named_scope(device_scopes.REPLAY_GATHER)
    def assemble(
        self, s: DeviceSeqState, idx: jnp.ndarray, beta: jnp.ndarray,
        *, with_weight: bool = True,
    ) -> Tuple[SequenceBatch, jnp.ndarray]:
        """Gather sequences + IS weights at slot ids.  Returns
        (SequenceBatch with [B, L, H, W, 1] obs, prob [B]).

        ``with_weight=False`` returns batch.weight as ones for callers that
        derive a globally consistent weight from ``prob`` instead (the
        sharded learner's psum/pmax mixture formula)."""
        p = self._effective_priority(s)
        total = p.sum()
        prob = jnp.maximum(p[idx] / jnp.maximum(total, 1e-12), 1e-12)
        if with_weight:
            w = (jnp.maximum(s.filled, 1).astype(jnp.float32) * prob) ** (-beta)
            weight = w / w.max()
        else:
            weight = jnp.ones_like(prob)
        # Whole rows, one slice a row (each is contiguous in the flat ring).
        # `s.frames[idx]` asks the TPU for gather slices of L*h*w bytes, more
        # than its gather takes, and the compiler then cuts the whole RING
        # into column strips first, on every learn step.
        frames = jax.lax.map(
            lambda i: jax.lax.dynamic_index_in_dim(s.frames, i, keepdims=False),
            idx)
        batch = SequenceBatch(
            # the gathered batch takes its logical shape, never the ring
            obs=self._unflat(frames)[..., None],
            action=s.actions[idx],
            reward=s.rewards[idx],
            done=s.dones[idx],
            valid=s.valids[idx],
            init_c=s.init_c[idx],
            init_h=s.init_h[idx],
            weight=weight,
        )
        return batch, prob

    def sample_grouped(
        self, s: DeviceSeqState, key: chex.PRNGKey, batch_size: int,
        groups: int, beta: jnp.ndarray,
    ) -> Tuple[jnp.ndarray, SequenceBatch, jnp.ndarray]:
        """``groups`` independent stratified draws of ``batch_size``
        sequences concatenated into one [G*B] learn batch — the sequence
        twin of replay/device.DeviceReplay.sample_grouped (cfg.sample_groups,
        the TPU batch-scaling knob): per-group stratum width and per-group
        max-normalised IS weights, exactly as G sequential reference steps.

        Returns (idx [G, B], SequenceBatch over [G*B], prob [G*B])."""
        keys = jax.random.split(key, groups)
        idx = jax.vmap(lambda k: self.draw(s, k, batch_size))(keys)
        batch, prob = self.assemble(s, idx.reshape(-1), beta,
                                    with_weight=False)
        with jax.named_scope(device_scopes.REPLAY_GATHER):
            w = (jnp.maximum(s.filled, 1).astype(jnp.float32) * prob) ** (-beta)
            w = w.reshape(groups, batch_size)
            w = w / w.max(axis=1, keepdims=True)
        return idx, batch.replace(weight=w.reshape(-1)), prob

    # ------------------------------------------------------------- priorities
    @jax.named_scope(device_scopes.REPLAY_WRITEBACK)
    def update_priorities(
        self, s: DeviceSeqState, idx: jnp.ndarray, td_mix: jnp.ndarray
    ) -> DeviceSeqState:
        """Learner eta-mix write-back (mirror of SequenceReplay
        .update_priorities: direct set, running max)."""
        pri = (td_mix.astype(jnp.float32) + self.eps) ** self.omega
        return s._replace(
            priority=s.priority.at[idx].set(pri),
            max_priority=jnp.maximum(s.max_priority, pri.max()),
        )

    def update_priorities_grouped(
        self, s: DeviceSeqState, idx: jnp.ndarray, td_mix: jnp.ndarray
    ) -> DeviceSeqState:
        """Write-back for sample_grouped's [G, B] indices in group order
        (last group wins on duplicates, as G sequential steps would)."""
        G = idx.shape[0]
        td = td_mix.reshape(G, -1)
        for g in range(G):
            s = self.update_priorities(s, idx[g], td[g])
        return s


def build_device_r2d2_learn(cfg, num_actions: int,
                            replay: DeviceSequenceReplay):
    """The fused R2D2 learner tick: draw -> assemble -> sequence learn step
    -> eta-mix priority write-back, one jittable pure function
    (train_state, replay_state, key, beta) -> (train_state, replay_state,
    info) — the recurrent twin of replay/device.build_device_learn.

    WARM-GATE CONTRACT: callers must not invoke this until the ring holds a
    meaningful population (the trainers gate on
    ``filled >= max(learn_start // seq_total, 8)``, train_anakin_r2d2.py /
    train_r2d2.py parity).  A cold ring degrades draw() to uniform-over-
    filled (see _effective_priority) rather than corrupting training, but
    the early gradients would still be on near-empty windows."""
    from rainbow_iqn_apex_tpu.ops.r2d2 import build_r2d2_learn_step

    learn_step = build_r2d2_learn_step(cfg, num_actions)
    groups = getattr(cfg, "sample_groups", 1)

    def fused(train_state, replay_state, key, beta):
        k_sample, k_learn = jax.random.split(key)
        if groups > 1:
            idx, batch, _prob = replay.sample_grouped(
                replay_state, k_sample, cfg.batch_size, groups, beta
            )
            train_state, info = learn_step(train_state, batch, k_learn)
            replay_state = replay.update_priorities_grouped(
                replay_state, idx, info["priorities"]
            )
        else:
            idx = replay.draw(replay_state, k_sample, cfg.batch_size)
            batch, _prob = replay.assemble(replay_state, idx, beta)
            train_state, info = learn_step(train_state, batch, k_learn)
            replay_state = replay.update_priorities(
                replay_state, idx, info["priorities"]
            )
        return train_state, replay_state, info

    return fused


# ---------------------------------------------------------------------------
# dp-sharded variant: per-shard rings under shard_map (the sequence twin of
# replay/device.build_device_learn_sharded)
# ---------------------------------------------------------------------------


def stack_seq_shards(local_state: DeviceSeqState, n_dev: int) -> DeviceSeqState:
    """The sharded-sequence state layout: every leaf of the per-shard
    DeviceSeqState gains a leading device dim of size n_dev ("stacked
    shards"), sharded P(axis) on dim 0.  Unlike the transition replay —
    whose lockstep appends keep one REPLICATED cursor valid for all lanes —
    sequence emission counts are data-dependent per lane group, so every
    shard needs its own pos/filled/max_priority; stacking makes those
    per-shard scalars one [n_dev] array like everything else."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_dev, *x.shape)), local_state
    )


def device_seq_specs(axis: str = "dp"):
    """PartitionSpecs for a stacked-shard DeviceSeqState (see
    stack_seq_shards): every leaf sharded over its leading device dim."""
    P = jax.sharding.PartitionSpec
    return jax.tree.map(lambda _: P(axis), DeviceSeqState(*DeviceSeqState._fields))


def device_seq_shardings(mesh, axis: str = "dp"):
    P = jax.sharding.PartitionSpec
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        device_seq_specs(axis),
        is_leaf=lambda x: isinstance(x, P),
    )


def _unstack(gs: DeviceSeqState) -> DeviceSeqState:
    return jax.tree.map(lambda x: x[0], gs)


def _restack(s: DeviceSeqState) -> DeviceSeqState:
    return jax.tree.map(lambda x: x[None], s)


def build_sharded_seq_append(replay: DeviceSequenceReplay, mesh,
                             axis: str = "dp"):
    """shard_map'd append over stacked-shard state: each device's lane group
    emits into ITS OWN ring (rank/cumsum/pos all shard-local), so the
    batched scatter never crosses devices.  Inputs are [total_lanes, ...]
    arrays lane-sharded over `axis`; `replay` is configured with the
    PER-DEVICE lane count and capacity."""
    P = jax.sharding.PartitionSpec
    state_spec = device_seq_specs(axis)

    def _append(gs, frames, actions, rewards, terms, truncs, c, h):
        s = replay.append(_unstack(gs), frames, actions, rewards, terms,
                          truncs, c, h)
        return _restack(s)

    return jax.shard_map(
        _append, mesh=mesh,
        in_specs=(state_spec, P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis)),
        out_specs=state_spec,
    )


def build_device_r2d2_learn_sharded(cfg, num_actions: int,
                                    local_replay: DeviceSequenceReplay, mesh,
                                    axis: str = "dp"):
    """Multi-chip fused R2D2 learner: per-shard sequence rings, per-shard
    draws of batch/n sequences, one dp-sharded recurrent learn step.

    Because each shard contributes exactly batch/n draws regardless of how
    full it is, global sampling is a uniform mixture over shards:
    q(i) = prob_local(i) / n_dev.  Sequence emission is data-dependent, so
    shard fills genuinely differ — N_global is a real psum over per-shard
    fills (not the transition replay's symmetric filled * n shortcut) and IS
    weights are pmax-normalised across shards.  The gradient all-reduce
    stays GSPMD-inserted from the batch sharding."""
    from rainbow_iqn_apex_tpu.ops.r2d2 import SequenceBatch, build_r2d2_learn_step
    from rainbow_iqn_apex_tpu.parallel.mesh import traced_under

    P = jax.sharding.PartitionSpec
    n_dev = mesh.shape[axis]
    if cfg.batch_size % n_dev:
        raise ValueError(
            f"batch {cfg.batch_size} not divisible by {n_dev} devices"
        )
    b_loc = cfg.batch_size // n_dev
    groups = getattr(cfg, "sample_groups", 1)
    # the batch is split over `axis`: the model sees the mesh as it is traced
    learn_step = traced_under(mesh, build_r2d2_learn_step(cfg, num_actions))
    state_spec = device_seq_specs(axis)
    batch_spec = SequenceBatch(
        obs=P(axis), action=P(axis), reward=P(axis), done=P(axis),
        valid=P(axis), init_c=P(axis), init_h=P(axis), weight=P(axis),
    )

    def _draw_assemble(gs, key, beta):
        """Per-shard fixed-quota draw; cfg.sample_groups > 1 draws G groups
        of b_loc per shard (flattened, group g contiguous) with IS weights
        pmax-normalised PER GROUP — the grouped pattern of
        replay/device.build_device_learn_sharded over the psum'd sequence
        fill counts."""
        s = _unstack(gs)
        k = jax.random.fold_in(key, jax.lax.axis_index(axis))
        if groups > 1:
            keys = jax.random.split(k, groups)
            idx = jax.vmap(
                lambda kk: local_replay.draw(s, kk, b_loc)
            )(keys).reshape(-1)
        else:
            idx = local_replay.draw(s, k, b_loc)
        batch, prob = local_replay.assemble(s, idx, beta, with_weight=False)
        with jax.named_scope(device_scopes.REPLAY_GATHER):
            with jax.named_scope(device_scopes.GRAD_ALLREDUCE):
                n_global = jax.lax.psum(s.filled, axis).astype(jnp.float32)
            nq = jnp.maximum(jnp.maximum(n_global, 1.0) * prob / n_dev, 1e-12)
            w = nq ** (-beta)
            wg = w.reshape(groups, b_loc)
            with jax.named_scope(device_scopes.GRAD_ALLREDUCE):
                wmax = jax.lax.pmax(wg.max(axis=1), axis)
            w = (wg / wmax[:, None]).reshape(-1)
        return idx, batch.replace(weight=w)

    def _write_back(gs, idx, td_mix):
        s = _unstack(gs)
        if groups > 1:
            s = local_replay.update_priorities_grouped(
                s, idx.reshape(groups, b_loc), td_mix
            )
        else:
            s = local_replay.update_priorities(s, idx, td_mix)
        return _restack(s)

    draw_assemble = jax.shard_map(
        _draw_assemble, mesh=mesh,
        in_specs=(state_spec, P(), P()),
        out_specs=(P(axis), batch_spec),
    )
    write_back = jax.shard_map(
        _write_back, mesh=mesh,
        in_specs=(state_spec, P(axis), P(axis)),
        out_specs=state_spec,
    )

    def fused(train_state, replay_state, key, beta):
        k_sample, k_learn = jax.random.split(key)
        idx, batch = draw_assemble(replay_state, k_sample, beta)
        train_state, info = learn_step(train_state, batch, k_learn)
        replay_state = write_back(replay_state, idx, info["priorities"])
        return train_state, replay_state, info

    fused.draw_assemble = draw_assemble  # exposed for tests
    return fused
