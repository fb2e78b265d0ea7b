"""Device-resident prioritized replay: the ring, the priorities, and every
sample/update in HBM, so a learner step needs ZERO per-step host transfer.

Why this exists: feeding the learn step a host-sampled batch costs a
host->device transfer every step (its share of the step on a directly
attached chip is not measured; ROADMAP S1/S2).  The reference
solves replay with a NETWORK hop (Redis, SURVEY.md §2 row 6); the host-DRAM
shards (replay/buffer.py) replace that hop with a PCIe hop; this module
removes the hop entirely for the capacity that fits in HBM: an Atari-shaped
1M-frame ring is ~7 GB uint8 — comfortable on one modern TPU chip.  This is
the Podracer/"Anakin" arrangement (PAPERS.md): experience, priorities and
the learner state co-resident on device, the whole sample->learn->priority
cycle one XLA graph, and the only host traffic the obligatory fresh frames
(one [L, H, W] uint8 tick, ~7 KB/lane).

Semantics: bit-faithful mirror of the host PrioritizedReplay
(replay/buffer.py) — multi-lane ring with per-lane episode adjacency,
frame-dedup stack reconstruction with cut-zeroing, n-step assembly stopping
at terminals, two-channel terminal/truncation cuts with the unbiased
time-limit rule (a window whose first cut is a truncation is ineligible),
write-cursor dead zone, proportional stratified sampling over p^omega, IS
weights (N P)^-beta max-normalised, and never-resurrect priority
write-back.  tests/test_device_replay.py drives both replays through the
same trace and asserts equality of eligibility, assembly, and weights.

No sum-tree on device: sampling is an O(N) masked cumsum + searchsorted,
which at 1M slots is a few MB of sequential HBM traffic — micro-seconds on
TPU and embarrassingly fusable, where the host's pointer-chasing tree is
exactly the part that needed a C++ core.  (f32 cumsum precision over 1M
slots is ~1e-2 relative worst-case; sampling noise of that size is
irrelevant to PER and the same order as the host tree's f32 leaves.)
"""

from __future__ import annotations

from typing import Optional, Tuple

import chex
import jax
import jax.numpy as jnp
from flax import struct

from rainbow_iqn_apex_tpu.obs import device_scopes
from rainbow_iqn_apex_tpu.ops.learn import Batch


@struct.dataclass
class DeviceReplayState:
    """The whole replay as one device pytree (donate through append/learn)."""

    frames: jnp.ndarray  # [L, S, H, W] uint8
    actions: jnp.ndarray  # [L, S] int32
    rewards: jnp.ndarray  # [L, S] f32
    terminals: jnp.ndarray  # [L, S] bool — true env terminals (stop bootstrap)
    cuts: jnp.ndarray  # [L, S] bool — terminal OR truncation (stream breaks)
    priority: jnp.ndarray  # [L*S] f32 tree-space p^omega; 0 = ineligible
    pos: jnp.ndarray  # [] int32 lane-local write cursor
    filled: jnp.ndarray  # [] int32 lane-local written count (<= S)
    max_priority: jnp.ndarray  # [] f32 tree-space default for fresh items


class DeviceReplay:
    """Static configuration + pure jittable ops over DeviceReplayState.

    All methods are pure functions (state in, state out) safe to close over
    in jit/scan; the class holds only static shape/hyper parameters.
    """

    def __init__(
        self,
        lanes: int,
        seg: int,  # slots per lane (capacity = lanes * seg)
        frame_shape: Tuple[int, int],
        history: int = 4,
        n_step: int = 3,
        gamma: float = 0.99,
        priority_exponent: float = 0.5,
        priority_eps: float = 1e-6,
    ):
        if seg <= history + n_step:
            raise ValueError("per-lane segment too small for history + n_step")
        self.lanes = lanes
        self.seg = seg
        self.frame_shape = frame_shape
        self.history = history
        self.n_step = n_step
        self.gamma = gamma
        self.omega = priority_exponent
        self.eps = priority_eps
        self._lane_base = jnp.arange(lanes, dtype=jnp.int32) * seg
        self._gammas = gamma ** jnp.arange(n_step + 1, dtype=jnp.float32)

    # ------------------------------------------------------------------ init
    def init_state(self) -> DeviceReplayState:
        h, w = self.frame_shape
        L, S = self.lanes, self.seg
        return DeviceReplayState(
            frames=jnp.zeros((L, S, h, w), jnp.uint8),
            actions=jnp.zeros((L, S), jnp.int32),
            rewards=jnp.zeros((L, S), jnp.float32),
            terminals=jnp.zeros((L, S), bool),
            cuts=jnp.zeros((L, S), bool),
            priority=jnp.zeros((L * S,), jnp.float32),
            pos=jnp.zeros((), jnp.int32),
            filled=jnp.zeros((), jnp.int32),
            max_priority=jnp.ones((), jnp.float32),
        )

    # ---------------------------------------------------------------- append
    def append(
        self,
        state: DeviceReplayState,
        frames: jnp.ndarray,  # [L, H, W] uint8
        actions: jnp.ndarray,  # [L] int32
        rewards: jnp.ndarray,  # [L] f32
        terminals: jnp.ndarray,  # [L] bool
        truncations: jnp.ndarray,  # [L] bool
        priorities: Optional[jnp.ndarray] = None,  # [L] raw |TD| or None
    ) -> DeviceReplayState:
        """One lockstep tick of all lanes (mirror of _append_locked,
        replay/buffer.py): ring writes + the three disjoint priority groups
        (fresh slot -> 0, cursor dead zone -> 0, the slot n_step back ->
        eligible with its actor priority / max_priority, unless its window's
        first cut is a truncation)."""
        L, S, h, n = self.lanes, self.seg, self.history, self.n_step
        pos, filled = state.pos, state.filled
        cuts_now = terminals | truncations

        frames_a = state.frames.at[:, pos].set(frames)
        actions_a = state.actions.at[:, pos].set(actions.astype(jnp.int32))
        rewards_a = state.rewards.at[:, pos].set(rewards.astype(jnp.float32))
        terms_a = state.terminals.at[:, pos].set(terminals)
        cuts_a = state.cuts.at[:, pos].set(cuts_now)

        new_pos = (pos + 1) % S
        fresh_slots = self._lane_base + pos  # [L]
        dead_cols = (new_pos + jnp.arange(h, dtype=jnp.int32)) % S  # [h]
        dead_slots = (self._lane_base[:, None] + dead_cols[None, :]).ravel()

        ready_col = (pos - n) % S
        ready_slots = self._lane_base + ready_col
        if priorities is None:
            pri = jnp.full((L,), state.max_priority)
            new_maxp = state.max_priority
        else:
            pri = (priorities.astype(jnp.float32) + self.eps) ** self.omega
            new_maxp = jnp.where(
                filled >= n,
                jnp.maximum(state.max_priority, pri.max()),
                state.max_priority,
            )
        # unbiased time-limit rule: window [ready, ready+n) whose FIRST cut
        # is a truncation can never form a correct bootstrap -> ineligible
        w_cols = (ready_col + jnp.arange(n, dtype=jnp.int32)) % S  # [n]
        cuts_w = cuts_a[:, w_cols]  # [L, n]
        terms_w = terms_a[:, w_cols]
        first_cut = jnp.argmax(cuts_w, axis=1)  # [L]
        has_cut = cuts_w.any(axis=1)
        first_is_trunc = ~jnp.take_along_axis(
            terms_w, first_cut[:, None], axis=1
        )[:, 0]
        pri = jnp.where(has_cut & first_is_trunc, 0.0, pri)
        # before n_step appends exist, the ready slot has no complete future
        pri = jnp.where(filled >= n, pri, state.priority[ready_slots])

        priority_a = state.priority.at[fresh_slots].set(0.0)
        priority_a = priority_a.at[dead_slots].set(0.0)
        priority_a = priority_a.at[ready_slots].set(pri)

        return DeviceReplayState(
            frames=frames_a,
            actions=actions_a,
            rewards=rewards_a,
            terminals=terms_a,
            cuts=cuts_a,
            priority=priority_a,
            pos=new_pos,
            filled=jnp.minimum(filled + 1, S),
            max_priority=new_maxp,
        )

    # ---------------------------------------------------------------- sample
    def _gather_stacks(
        self, state: DeviceReplayState, lane: jnp.ndarray, off: jnp.ndarray
    ) -> jnp.ndarray:
        """[B, H, W, history] stacks ending at lane-local `off`, zeroing
        frames at/before an episode cut inside the lookback window and
        frames older than a young buffer has written (mirror of
        _gather_stacks, replay/buffer.py)."""
        h, S = self.history, self.seg
        steps = jnp.arange(-(h - 1), 1, dtype=jnp.int32)  # [-h+1 .. 0]
        offs = (off[:, None] + steps[None, :]) % S  # [B, h]
        stacks = state.frames[lane[:, None], offs]  # [B, h, H, W]

        cut_w = state.cuts[lane[:, None], offs[:, :-1]]  # [B, h-1]
        # dead_tail[j] = any cut at/after window position j
        dead_tail = (
            jnp.cumsum(cut_w[:, ::-1].astype(jnp.int32), axis=1)[:, ::-1] > 0
        )
        valid = jnp.concatenate(
            [~dead_tail, jnp.ones((off.shape[0], 1), bool)], axis=1
        )
        age_ok = (off[:, None] + steps[None, :]) >= 0
        valid &= jnp.where(state.filled >= S, True, age_ok)
        stacks = stacks * valid[:, :, None, None].astype(jnp.uint8)
        return jnp.moveaxis(stacks, 1, -1)  # [B, H, W, h]

    @jax.named_scope(device_scopes.REPLAY_DRAW)
    def draw(
        self, state: DeviceReplayState, key: chex.PRNGKey, batch_size: int
    ) -> jnp.ndarray:
        """Stratified proportional draw over p^omega (the tree-free
        equivalent of SumTree.sample_stratified): one uniform per stratum,
        inverse-CDF via searchsorted."""
        p = state.priority
        total = p.sum()
        cdf = jnp.cumsum(p)
        u = (jnp.arange(batch_size) + jax.random.uniform(key, (batch_size,)))
        u = u / batch_size * total
        return jnp.clip(
            jnp.searchsorted(cdf, u, side="right"), 0, p.shape[0] - 1
        ).astype(jnp.int32)

    @jax.named_scope(device_scopes.REPLAY_GATHER)
    def assemble(
        self,
        state: DeviceReplayState,
        idx: jnp.ndarray,
        beta: jnp.ndarray,
        *,
        with_weight: bool = True,
    ) -> Tuple[Batch, jnp.ndarray]:
        """n-step assembly + stack gathers + IS weights at given global slot
        ids.  Returns (Batch, prob [B]).

        ``with_weight=False`` skips the locally max-normalised IS weight
        (batch.weight comes back as ones) for callers that derive a globally
        consistent weight from ``prob`` instead — the sharded learner's
        pmax-normalised mixture formula (build_device_learn_sharded)."""
        B, S, n = idx.shape[0], self.seg, self.n_step
        p = state.priority
        total = p.sum()
        prob = jnp.maximum(p[idx] / jnp.maximum(total, 1e-12), 1e-12)

        lane = idx // S
        off = idx % S

        steps = jnp.arange(n, dtype=jnp.int32)
        f_offs = (off[:, None] + steps[None, :]) % S  # [B, n]
        r = state.rewards[lane[:, None], f_offs]
        d = state.terminals[lane[:, None], f_offs]
        alive = jnp.cumprod(1.0 - d[:, :-1].astype(jnp.float32), axis=1)
        alive = jnp.concatenate([jnp.ones((B, 1), jnp.float32), alive], axis=1)
        reward = (r * alive * self._gammas[None, :n]).sum(axis=1)
        done_within = d.any(axis=1)
        discount = jnp.where(done_within, 0.0, self._gammas[n])

        obs = self._gather_stacks(state, lane, off)
        next_obs = self._gather_stacks(state, lane, (off + n) % S)

        if with_weight:
            n_stored = (state.filled * self.lanes).astype(jnp.float32)
            w = (n_stored * prob) ** (-beta)
            weight = w / w.max()
        else:
            weight = jnp.ones_like(prob)

        batch = Batch(
            obs=obs,
            action=state.actions[lane, off],
            reward=reward,
            next_obs=next_obs,
            discount=discount,
            weight=weight,
        )
        return batch, prob

    def sample(
        self,
        state: DeviceReplayState,
        key: chex.PRNGKey,
        batch_size: int,
        beta: jnp.ndarray,
    ) -> Tuple[jnp.ndarray, Batch, jnp.ndarray]:
        """Stratified proportional sample + n-step assembly + IS weights.
        Returns (idx [B] int32 global slots, Batch, prob [B])."""
        idx = self.draw(state, key, batch_size)
        batch, prob = self.assemble(state, idx, beta)
        return idx, batch, prob

    def sample_grouped(
        self,
        state: DeviceReplayState,
        key: chex.PRNGKey,
        batch_size: int,
        groups: int,
        beta: jnp.ndarray,
    ) -> Tuple[jnp.ndarray, Batch, jnp.ndarray]:
        """``groups`` independent stratified draws of ``batch_size``,
        concatenated into ONE [G*B] learn batch — the TPU batch-scaling knob
        (SURVEY §7): a 4x bigger GEMM for the MXU without changing the
        reference's PER semantics, because each group keeps the batch-32
        stratum width (total/B per stratum) and its OWN max-normalised IS
        weights, exactly as G sequential reference learn steps would.  What
        DOES differ from G sequential steps: priorities aren't updated
        between draws (groups sample the same distribution) and the
        optimiser takes one step on the G*B mean gradient instead of G
        steps — the standard large-batch trade, chosen explicitly via
        cfg.sample_groups.

        Returns (idx [G, B], Batch over [G*B], prob [G*B])."""
        keys = jax.random.split(key, groups)
        idx = jax.vmap(lambda k: self.draw(state, k, batch_size))(keys)
        batch, prob = self.assemble(
            state, idx.reshape(-1), beta, with_weight=False
        )
        with jax.named_scope(device_scopes.REPLAY_GATHER):
            n_stored = (state.filled * self.lanes).astype(jnp.float32)
            w = (n_stored * prob) ** (-beta)
            w = w.reshape(groups, batch_size)
            w = w / w.max(axis=1, keepdims=True)  # per-group, as sequential steps
        return idx, batch.replace(weight=w.reshape(-1)), prob

    # ------------------------------------------------------------- priorities
    def update_priorities_grouped(
        self, state: DeviceReplayState, idx: jnp.ndarray, td_abs: jnp.ndarray
    ) -> DeviceReplayState:
        """Write-back for sample_grouped's [G, B] indices with G-sequential
        semantics: on a slot drawn by several groups, the LAST group's
        priority stands (scatter order across duplicate ids inside one
        .at[].set is unspecified, so the groups are applied as G small
        ordered scatters — G is static and tiny)."""
        G = idx.shape[0]
        td = td_abs.reshape(G, -1)
        for g in range(G):
            state = self.update_priorities(state, idx[g], td[g])
        return state

    @jax.named_scope(device_scopes.REPLAY_WRITEBACK)
    def update_priorities(
        self, state: DeviceReplayState, idx: jnp.ndarray, td_abs: jnp.ndarray
    ) -> DeviceReplayState:
        """Learner write-back, never resurrecting cursor-invalidated slots
        (mirror of update_priorities, replay/buffer.py)."""
        pri = (td_abs.astype(jnp.float32) + self.eps) ** self.omega
        new_maxp = jnp.maximum(state.max_priority, pri.max())
        current = state.priority[idx]
        pri = jnp.where(current > 0, pri, 0.0)
        return state.replace(
            priority=state.priority.at[idx].set(pri), max_priority=new_maxp
        )


def build_device_learn_sharded(cfg, num_actions: int, local_replay: DeviceReplay, mesh, axis: str = "dp"):
    """Multi-chip Anakin: the HBM replay lane-sharded over the mesh's dp axis,
    the learn step dp-sharded as usual — zero host traffic per step on every
    chip.

    Scheme (the in-graph twin of the multi-host sharded replay,
    parallel/multihost.py): each device draws a FIXED batch/n quota from its
    OWN lane shard — static shapes, no cross-device gathers of frames — which
    makes global sampling a uniform mixture over shards; IS weights are
    re-derived from that mixture probability q(i) = prob_local(i)/n and
    max-normalised across all shards with one tiny pmax collective
    (`global_is_nq` math).  The gradient all-reduce stays GSPMD-inserted
    from the batch sharding, exactly as in the host-fed apex learner.

    `local_replay` must be configured with the PER-DEVICE lane count
    (total_lanes // n_devices); the replay state passed to the returned
    function is the GLOBAL state, lane-sharded over `axis` (scalars
    replicated) — see `device_replay_specs`.
    """
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step

    P = jax.sharding.PartitionSpec
    n_dev = mesh.shape[axis]
    if cfg.batch_size % n_dev:
        raise ValueError(f"batch {cfg.batch_size} not divisible by {n_dev} devices")
    b_loc = cfg.batch_size // n_dev
    groups = getattr(cfg, "sample_groups", 1)
    learn_step = build_learn_step(cfg, num_actions)
    state_spec = device_replay_specs(axis)
    batch_spec = Batch(
        obs=P(axis), action=P(axis), reward=P(axis),
        next_obs=P(axis), discount=P(axis), weight=P(axis),
    )

    def _draw_assemble(ds_loc, key, beta):
        """Per-shard fixed-quota draw; with cfg.sample_groups > 1 each shard
        draws G stratified groups of b_loc (flattened [G*b_loc], group g at
        rows [g*b_loc, (g+1)*b_loc)) and IS weights are pmax-normalised PER
        GROUP across shards — the sharded twin of sample_grouped, keeping
        each group's weights exactly what a sequential reference step would
        use."""
        k = jax.random.fold_in(key, jax.lax.axis_index(axis))
        if groups > 1:
            keys = jax.random.split(k, groups)
            idx = jax.vmap(
                lambda kk: local_replay.draw(ds_loc, kk, b_loc)
            )(keys).reshape(-1)
        else:
            idx = local_replay.draw(ds_loc, k, b_loc)
        batch, prob = local_replay.assemble(ds_loc, idx, beta, with_weight=False)
        # globally consistent IS weights over the shard mixture
        with jax.named_scope(device_scopes.REPLAY_GATHER):
            n_global = (
                ds_loc.filled * local_replay.lanes * n_dev).astype(jnp.float32)
            nq = jnp.maximum(n_global * prob / n_dev, 1e-12)
            w = nq ** (-beta)
            wg = w.reshape(groups, b_loc)
            with jax.named_scope(device_scopes.GRAD_ALLREDUCE):
                # [G] per-group global max
                wmax = jax.lax.pmax(wg.max(axis=1), axis)
            w = (wg / wmax[:, None]).reshape(-1)
        return idx, batch.replace(weight=w)

    def _write_back(ds_loc, idx, td_abs):
        if groups > 1:
            ds_loc = local_replay.update_priorities_grouped(
                ds_loc, idx.reshape(groups, b_loc), td_abs
            )
        else:
            ds_loc = local_replay.update_priorities(ds_loc, idx, td_abs)
        # keep the replicated max_priority scalar shard-consistent
        with jax.named_scope(device_scopes.REPLAY_WRITEBACK), \
                jax.named_scope(device_scopes.GRAD_ALLREDUCE):
            return ds_loc.replace(
                max_priority=jax.lax.pmax(ds_loc.max_priority, axis)
            )

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

    def _check_geometry(replay_state):
        got = replay_state.frames.shape[0]
        want = local_replay.lanes * n_dev
        if got != want:
            raise ValueError(
                f"sharded device replay geometry mismatch: global state has "
                f"{got} lanes but local_replay.lanes ({local_replay.lanes}) x "
                f"{n_dev} devices = {want}"
            )
        got_seg = replay_state.frames.shape[1]
        if got_seg != local_replay.seg:
            # a seg mismatch would silently mis-decode lane = idx // seg
            # (gather clamps instead of erroring), so refuse loudly
            raise ValueError(
                f"sharded device replay geometry mismatch: global state has "
                f"seg={got_seg} but local_replay.seg={local_replay.seg}"
            )

    def fused(train_state, replay_state, key, beta):
        _check_geometry(replay_state)
        k_sample, k_learn = jax.random.split(key)
        idx, batch = draw_assemble(replay_state, k_sample, beta)
        train_state, info = learn_step(train_state, batch, k_learn)
        replay_state = write_back(replay_state, idx, info["priorities"])
        return train_state, replay_state, info

    # exposed for tests: the in-graph per-shard draw with globally corrected
    # IS weights, without the learn half
    fused.draw_assemble = lambda replay_state, key, beta: (
        _check_geometry(replay_state) or draw_assemble(replay_state, key, beta)
    )
    return fused


def device_replay_specs(axis: str = "dp"):
    """PartitionSpecs for a lane-sharded DeviceReplayState: every per-lane
    array sharded on its lane dimension, cursor scalars replicated."""
    P = jax.sharding.PartitionSpec
    return DeviceReplayState(
        frames=P(axis), actions=P(axis), rewards=P(axis),
        terminals=P(axis), cuts=P(axis), priority=P(axis),
        pos=P(), filled=P(), max_priority=P(),
    )


def device_replay_shardings(mesh, axis: str = "dp"):
    """NamedShardings for placing a global DeviceReplayState on `mesh`:
    `jax.device_put(state, device_replay_shardings(mesh))`.  Wraps
    device_replay_specs in the tree-map callers would otherwise have to
    repeat (PartitionSpec is itself a pytree, hence the is_leaf guard)."""
    P = jax.sharding.PartitionSpec
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s),
        device_replay_specs(axis),
        is_leaf=lambda x: isinstance(x, P),
    )


def build_device_learn(cfg, num_actions: int, replay: DeviceReplay):
    """The Anakin learner tick: sample -> learn -> priority write-back as ONE
    jittable pure function (train_state, replay_state, key, beta) ->
    (train_state, replay_state, info).  Zero host traffic per step; jit with
    donate_argnums=(0, 1) so both states update in place in HBM."""
    from rainbow_iqn_apex_tpu.ops.learn import build_learn_step

    learn_step = build_learn_step(cfg, num_actions)
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
            idx, batch, _prob = replay.sample(
                replay_state, k_sample, cfg.batch_size, beta
            )
            train_state, info = learn_step(train_state, batch, k_learn)
            replay_state = replay.update_priorities(
                replay_state, idx, info["priorities"]
            )
        return train_state, replay_state, info

    return fused
