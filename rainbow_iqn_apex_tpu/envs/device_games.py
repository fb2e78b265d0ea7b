"""Pure-JAX games: Atari-class dynamics that run INSIDE the XLA graph.

Why this exists: the reference's env layer is ALE behind atari-py (SURVEY.md
§2 row 2) — host-side C++ that caps every TPU design at the host->device
frame-transfer rate.  These games keep the reference's observation contract
(uint8 single-channel frames, small discrete action set, clipped-scale
rewards, episodic terminals + time-limit truncations) but are written as pure
jittable functions of (state, action, key), so they can be:

  * vmapped over lanes  -> one [L, H, W] frame tensor per tick, on device;
  * fused into the Anakin trainer's act->step->append->learn graph
    (train_anakin.py), eliminating host traffic entirely — the full Podracer
    "everything on chip" topology the reference's Redis loop cannot express;
  * driven from the host through the ordinary `Env` adapter (JaxGameEnv) so
    every trainer/eval path runs them unchanged.

Dynamics are in the MinAtar family (Young & Tian, arXiv:1903.03176 — cited
as the public spec these games follow; implementations here are original):
10x10 logic grids, one entity class per game mechanic, rendered by intensity
so a frame-stacking conv agent must learn motion.  Design rules for TPU:
static shapes everywhere, no data-dependent Python control flow (jnp.where
only), randomness through explicit keys, state as a NamedTuple of arrays.

Intended dynamics note (collision semantics): collisions are checked at
post-move coincidence only.  On ticks where two entities move toward each
other (a bullet and a marching alien, a bomb and the player, a car and the
freeway chicken) they can swap cells without registering a hit — classic
discrete-grid "tunneling".  This is deliberate: it keeps every entity update
one vectorised move-then-compare (no sub-tick sweep), it is identical for
the agent and for the scripted baselines (jaxsuite.py), and MinAtar-family
play is unaffected beyond an occasional lucky pass-through that the agent
can in fact learn to exploit, like any other game rule.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rainbow_iqn_apex_tpu.envs.base import Env, TimeStep

G = 10  # logic grid is GxG for every game

# render intensities (distinct so the conv net can tell entities apart)
I_PLAYER = np.uint8(140)
I_BALL = np.uint8(255)
I_BRICK = np.uint8(90)
I_ENEMY = np.uint8(200)
I_GOLD = np.uint8(255)
I_BULLET = np.uint8(255)


def _upscale(grid: jnp.ndarray, cell: int) -> jnp.ndarray:
    """[G, G] u8 -> [G*cell, G*cell] u8 (nearest-neighbour)."""
    return jnp.repeat(jnp.repeat(grid, cell, axis=0), cell, axis=1)


def _rand_signs(key, shape=()) -> jnp.ndarray:
    """Uniform ±1 i32 draw — the shared direction-sampling convention."""
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1, -1).astype(
        jnp.int32
    )


class DeviceGame:
    """Base: a pure-functional game.  Subclasses define init/step/render as
    jit-safe single-instance functions; batching is the caller's vmap."""

    num_actions: int
    # frame = (G*cell, G*cell).  cell=8 -> 80x80: the canonical DQN trunk
    # reduces that to a 6x6 feature grid; at cell=5 (50x50) the final grid is
    # only 2x2, too coarse to localise entities (measured: catch learns ~3x
    # slower at 50x50 than at 80x80 on both the host and fused trainers).
    cell: int = 8

    @property
    def frame_shape(self) -> Tuple[int, int]:
        return (G * self.cell, G * self.cell)

    def init(self, key):  # -> state
        raise NotImplementedError

    def step(self, state, action, key):  # -> (state, reward f32, term bool, trunc bool)
        raise NotImplementedError

    def render(self, state) -> jnp.ndarray:  # -> [H, W] uint8
        raise NotImplementedError


# --------------------------------------------------------------------------
# Catch — the learnability anchor (same rules as envs/toy.py CatchEnv)
# --------------------------------------------------------------------------


class CatchState(NamedTuple):
    ball_r: jnp.ndarray  # i32 scalar
    ball_c: jnp.ndarray
    paddle: jnp.ndarray
    t: jnp.ndarray


class CatchGame(DeviceGame):
    """Ball falls straight down; catch it with the bottom paddle.
    Actions: 0=stay 1=left 2=right.  +1 catch / -1 miss, episode ends at the
    bottom row — the in-graph twin of toy.py's CatchEnv (SURVEY §4 Pong-role)."""

    num_actions = 3

    def init(self, key) -> CatchState:
        return CatchState(
            ball_r=jnp.int32(0),
            ball_c=jax.random.randint(key, (), 0, G, jnp.int32),
            paddle=jnp.int32(G // 2),
            t=jnp.int32(0),
        )

    def step(self, s: CatchState, action, key):
        move = jnp.array([0, -1, 1], jnp.int32)[action]
        paddle = jnp.clip(s.paddle + move, 0, G - 1)
        ball_r = s.ball_r + 1
        ball_c = self._ball_col(s, ball_r)
        terminal = ball_r == G - 1
        reward = jnp.where(
            terminal, jnp.where(paddle == ball_c, 1.0, -1.0), 0.0
        ).astype(jnp.float32)
        ns = s._replace(ball_r=ball_r, ball_c=ball_c, paddle=paddle,
                        t=s.t + 1)
        return ns, reward, terminal, jnp.bool_(False)

    def _ball_col(self, s, ball_r):
        """Ball column on entering row `ball_r` — the dynamics hook the
        seeded-level variant overrides (base ball falls straight down)."""
        return s.ball_c

    def render(self, s: CatchState) -> jnp.ndarray:
        grid = jnp.zeros((G, G), jnp.uint8)
        grid = grid.at[s.ball_r, s.ball_c].set(I_BALL)
        grid = grid.at[G - 1, s.paddle].set(I_PLAYER)
        return _upscale(grid, self.cell)


# --------------------------------------------------------------------------
# Breakout
# --------------------------------------------------------------------------


class BreakoutState(NamedTuple):
    paddle: jnp.ndarray  # i32 col
    ball_r: jnp.ndarray
    ball_c: jnp.ndarray
    dr: jnp.ndarray  # i32 in {-1, +1}
    dc: jnp.ndarray
    bricks: jnp.ndarray  # [G, G] bool (rows 1..3 used)
    t: jnp.ndarray


class BreakoutGame(DeviceGame):
    """Paddle/ball/brick-wall: +1 per brick, wall respawns when cleared,
    episode ends when the ball passes the paddle.  Actions: 0=stay 1=left
    2=right."""

    num_actions = 3
    BRICK_ROWS = (1, 2, 3)

    def _wall(self) -> jnp.ndarray:
        bricks = jnp.zeros((G, G), bool)
        for r in self.BRICK_ROWS:
            bricks = bricks.at[r].set(True)
        return bricks

    def init(self, key) -> BreakoutState:
        kc, kd = jax.random.split(key)
        return BreakoutState(
            paddle=jnp.int32(G // 2),
            ball_r=jnp.int32(4),
            ball_c=jax.random.randint(kc, (), 0, G, jnp.int32),
            dr=jnp.int32(1),
            dc=_rand_signs(kd),
            bricks=self._wall(),
            t=jnp.int32(0),
        )

    def step(self, s: BreakoutState, action, key):
        move = jnp.array([0, -1, 1], jnp.int32)[action]
        paddle = jnp.clip(s.paddle + move, 0, G - 1)

        # diagonal flight with side/top reflection
        nc = s.ball_c + s.dc
        dc = jnp.where((nc < 0) | (nc > G - 1), -s.dc, s.dc)
        nc = jnp.clip(nc, 0, G - 1)  # reflected into the wall cell it hit
        nr = s.ball_r + s.dr
        dr = jnp.where(nr < 0, jnp.int32(1), s.dr)
        nr = jnp.where(nr < 0, jnp.int32(1), nr)

        # brick hit: clear it, bounce back (ball keeps its old row)
        nr_idx = jnp.clip(nr, 0, G - 1)
        hit_brick = s.bricks[nr_idx, nc]
        bricks = s.bricks.at[nr_idx, nc].set(
            jnp.where(hit_brick, False, s.bricks[nr_idx, nc])
        )
        reward = jnp.where(hit_brick, 1.0, 0.0).astype(jnp.float32)
        dr = jnp.where(hit_brick, -dr, dr)
        nr = jnp.where(hit_brick, s.ball_r, nr)

        # paddle plane: bounce if aligned, lose otherwise
        at_bottom = nr >= G - 1
        caught = at_bottom & (nc == paddle)
        dr = jnp.where(caught, jnp.int32(-1), dr)
        nr = jnp.where(caught, jnp.int32(G - 2), nr)
        terminal = at_bottom & ~caught

        # cleared wall respawns (dense long-horizon reward, like the
        # reference's multi-life Atari episodes)
        cleared = ~bricks.any()
        bricks = jnp.where(cleared, self._respawn(s), bricks)

        # _replace keeps any subclass state fields (e.g. the variant's
        # per-level wall template) flowing through unchanged
        ns = s._replace(paddle=paddle, ball_r=nr, ball_c=nc, dr=dr, dc=dc,
                        bricks=bricks, t=s.t + 1)
        return ns, reward, terminal, jnp.bool_(False)

    def _respawn(self, s) -> jnp.ndarray:
        return self._wall()

    def render(self, s: BreakoutState) -> jnp.ndarray:
        grid = jnp.where(s.bricks, I_BRICK, jnp.uint8(0)).astype(jnp.uint8)
        grid = grid.at[s.ball_r, s.ball_c].set(I_BALL)
        grid = grid.at[G - 1, s.paddle].set(I_PLAYER)
        return _upscale(grid, self.cell)


# --------------------------------------------------------------------------
# Freeway
# --------------------------------------------------------------------------


class FreewayState(NamedTuple):
    chicken: jnp.ndarray  # i32 row (col fixed at CHICKEN_COL)
    cars: jnp.ndarray  # [8] i32 col of the car in lanes rows 1..8
    t: jnp.ndarray


class FreewayGame(DeviceGame):
    """Cross 8 lanes of traffic: +1 at the top (then restart at the bottom);
    a collision sends the chicken back down.  No terminal state — episodes
    end by time-limit truncation (`cap` ticks), exercising the two-channel
    terminal/truncation replay contract end-to-end."""

    num_actions = 3  # 0=stay 1=up 2=down
    CHICKEN_COL = 4
    # per-lane (speed, direction): car advances every `speed` ticks
    SPEEDS = np.array([2, 3, 2, 4, 2, 3, 4, 2], np.int32)
    DIRS = np.array([1, -1, 1, -1, -1, 1, -1, 1], np.int32)

    def __init__(self, cap: int = 500):
        self.cap = cap

    def init(self, key) -> FreewayState:
        return FreewayState(
            chicken=jnp.int32(G - 1),
            cars=jax.random.randint(key, (8,), 0, G, jnp.int32),
            t=jnp.int32(0),
        )

    def _lane_dynamics(self, s):
        """(speeds [8], dirs [8]) — the variant subclass reads them from the
        per-level state instead of the class constants (NumPy, so that
        importing this module never brings up a backend; callers index the
        result with traced lanes, hence the conversion here)."""
        return jnp.asarray(self.SPEEDS), jnp.asarray(self.DIRS)

    def step(self, s: FreewayState, action, key):
        move = jnp.array([0, -1, 1], jnp.int32)[action]
        chicken = jnp.clip(s.chicken + move, 0, G - 1)

        speeds, dirs = self._lane_dynamics(s)
        advance = (s.t % speeds) == 0
        cars = (s.cars + jnp.where(advance, dirs, 0)) % G

        # lanes are rows 1..8; car in the chicken's row at the chicken's col?
        lane = chicken - 1  # -1 or 8+ when off the road
        on_road = (lane >= 0) & (lane < 8)
        car_col = cars[jnp.clip(lane, 0, 7)]
        hit = on_road & (car_col == self.CHICKEN_COL)
        chicken = jnp.where(hit, jnp.int32(G - 1), chicken)

        scored = chicken == 0
        reward = jnp.where(scored, 1.0, 0.0).astype(jnp.float32)
        chicken = jnp.where(scored, jnp.int32(G - 1), chicken)

        t = s.t + 1
        trunc = t >= self.cap
        ns = s._replace(chicken=chicken, cars=cars, t=t)
        return ns, reward, jnp.bool_(False), trunc

    def render(self, s: FreewayState) -> jnp.ndarray:
        grid = jnp.zeros((G, G), jnp.uint8)
        grid = grid.at[jnp.arange(1, 9), s.cars].set(I_ENEMY)
        grid = grid.at[s.chicken, self.CHICKEN_COL].set(I_PLAYER)
        return _upscale(grid, self.cell)


# --------------------------------------------------------------------------
# Asterix
# --------------------------------------------------------------------------


class AsterixState(NamedTuple):
    pr: jnp.ndarray  # player row/col, i32
    pc: jnp.ndarray
    active: jnp.ndarray  # [8] bool — one entity per lane (rows 1..8)
    col: jnp.ndarray  # [8] i32
    dirn: jnp.ndarray  # [8] i32 in {-1, +1}
    gold: jnp.ndarray  # [8] bool — collectible vs lethal
    t: jnp.ndarray


class AsterixGame(DeviceGame):
    """Dodge enemies, collect gold.  Entities stream through 8 lanes; walking
    into gold is +1, into an enemy is death.  Actions: 0=stay 1=left 2=right
    3=up 4=down (player confined to the road rows 1..8)."""

    num_actions = 5
    SPAWN_P = 0.25  # per empty lane per tick
    MOVE_EVERY = 2  # entities advance every 2nd tick

    def _lane_speeds(self, s):
        """[8] i32 per-lane entity beat (advance every `speed` ticks) — the
        variant subclass reads it from the per-level state."""
        return jnp.full((8,), self.MOVE_EVERY, jnp.int32)

    def _spawn_dirs(self, s, key):
        """[8] i32 direction a spawn in each lane would take."""
        return _rand_signs(key, (8,))

    def _gold_probs(self, s):
        """[8] f32 per-lane gold probability (base: MinAtar's 1-in-3)."""
        return jnp.full((8,), 1.0 / 3.0, jnp.float32)

    def init(self, key) -> AsterixState:
        return AsterixState(
            pr=jnp.int32(G // 2),
            pc=jnp.int32(G // 2),
            active=jnp.zeros(8, bool),
            col=jnp.zeros(8, jnp.int32),
            dirn=jnp.ones(8, jnp.int32),
            gold=jnp.zeros(8, bool),
            t=jnp.int32(0),
        )

    def step(self, s: AsterixState, action, key):
        k_spawn, k_dir, k_gold = jax.random.split(key, 3)
        dmove = jnp.array([[0, 0], [0, -1], [0, 1], [-1, 0], [1, 0]], jnp.int32)
        pr = jnp.clip(s.pr + dmove[action, 0], 1, 8)
        pc = jnp.clip(s.pc + dmove[action, 1], 0, G - 1)

        # advance entities on their beat; deactivate on exit
        advance = s.active & ((s.t % self._lane_speeds(s)) == 0)
        col = s.col + jnp.where(advance, s.dirn, 0)
        exited = (col < 0) | (col > G - 1)
        active = s.active & ~exited
        col = jnp.clip(col, 0, G - 1)

        # spawn into empty lanes (left edge moving right / right edge moving
        # left), 1-in-3 gold — MinAtar's treasure ratio
        spawn = (~active) & (jax.random.uniform(k_spawn, (8,)) < self.SPAWN_P)
        new_dir = self._spawn_dirs(s, k_dir)
        new_gold = jax.random.uniform(k_gold, (8,)) < self._gold_probs(s)
        dirn = jnp.where(spawn, new_dir, s.dirn)
        col = jnp.where(spawn, jnp.where(new_dir > 0, 0, G - 1), col)
        gold = jnp.where(spawn, new_gold, s.gold)
        active = active | spawn

        # collision in the player's lane
        lane = pr - 1
        collide = active[lane] & (col[lane] == pc)
        hit_gold = collide & gold[lane]
        terminal = collide & ~gold[lane]
        reward = jnp.where(hit_gold, 1.0, 0.0).astype(jnp.float32)
        active = active.at[lane].set(jnp.where(hit_gold, False, active[lane]))

        ns = s._replace(pr=pr, pc=pc, active=active, col=col, dirn=dirn,
                        gold=gold, t=s.t + 1)
        return ns, reward, terminal, jnp.bool_(False)

    def render(self, s: AsterixState) -> jnp.ndarray:
        grid = jnp.zeros((G, G), jnp.uint8)
        lane_rows = jnp.arange(1, 9)
        val = jnp.where(
            s.active, jnp.where(s.gold, I_GOLD, I_ENEMY), jnp.uint8(0)
        ).astype(jnp.uint8)
        grid = grid.at[lane_rows, s.col].max(val)
        grid = grid.at[s.pr, s.pc].set(I_PLAYER)
        return _upscale(grid, self.cell)


# --------------------------------------------------------------------------
# Space Invaders
# --------------------------------------------------------------------------


class InvadersState(NamedTuple):
    pc: jnp.ndarray  # player col (row G-1), i32
    aliens: jnp.ndarray  # [G, G] bool (block starts rows 1..4, cols 2..7)
    adir: jnp.ndarray  # i32 march direction
    shot_r: jnp.ndarray  # player bullet (-1 row = inactive)
    shot_c: jnp.ndarray
    bomb_r: jnp.ndarray  # alien bomb (-1 row = inactive)
    bomb_c: jnp.ndarray
    t: jnp.ndarray


class InvadersGame(DeviceGame):
    """March-and-shoot: +1 per alien; death by bomb or by the fleet reaching
    the bottom row; fleet respawns when cleared.  Actions: 0=stay 1=left
    2=right 3=fire."""

    num_actions = 4
    MARCH_EVERY = 4  # fleet advances every 4th tick
    BOMB_EVERY = 6  # a random front-line alien bombs every 6th tick

    def _fleet(self) -> jnp.ndarray:
        a = jnp.zeros((G, G), bool)
        return a.at[1:5, 2:8].set(True)

    def _march_every(self, s):
        """Fleet march beat — the variant subclass reads it per-level."""
        return jnp.int32(self.MARCH_EVERY)

    def _bomb_every(self, s):
        """Bomb release beat — the variant subclass reads it per-level."""
        return jnp.int32(self.BOMB_EVERY)

    def _respawn_fleet(self, s) -> jnp.ndarray:
        """Fleet pattern a cleared wave respawns with."""
        return self._fleet()

    def init(self, key) -> InvadersState:
        return InvadersState(
            pc=jnp.int32(G // 2),
            aliens=self._fleet(),
            adir=jnp.int32(1),
            shot_r=jnp.int32(-1),
            shot_c=jnp.int32(0),
            bomb_r=jnp.int32(-1),
            bomb_c=jnp.int32(0),
            t=jnp.int32(0),
        )

    def step(self, s: InvadersState, action, key):
        move = jnp.array([0, -1, 1, 0], jnp.int32)[action]
        pc = jnp.clip(s.pc + move, 0, G - 1)

        # fire: one player bullet in flight at a time
        fire = (action == 3) & (s.shot_r < 0)
        shot_r = jnp.where(fire, jnp.int32(G - 2), s.shot_r - (s.shot_r >= 0))
        shot_c = jnp.where(fire, pc, s.shot_c)

        # bullet hits the alien it flies into
        shot_live = shot_r >= 0
        sr = jnp.clip(shot_r, 0, G - 1)
        hit = shot_live & s.aliens[sr, shot_c]
        aliens = s.aliens.at[sr, shot_c].set(
            jnp.where(hit, False, s.aliens[sr, shot_c])
        )
        reward = jnp.where(hit, 1.0, 0.0).astype(jnp.float32)
        shot_r = jnp.where(hit, jnp.int32(-1), shot_r)

        # fleet march: sideways on the beat, down + reverse at an edge
        march = (s.t % self._march_every(s)) == 0
        cols_occ = aliens.any(axis=0)
        leftmost = jnp.argmax(cols_occ)
        rightmost = G - 1 - jnp.argmax(cols_occ[::-1])
        at_edge = jnp.where(s.adir > 0, rightmost >= G - 1, leftmost <= 0)
        drop = march & at_edge & cols_occ.any()
        shift = march & ~at_edge
        aliens = jnp.where(drop, jnp.roll(aliens, 1, axis=0), aliens)
        adir = jnp.where(drop, -s.adir, s.adir)
        aliens = jnp.where(shift, jnp.roll(aliens, s.adir, axis=1), aliens)

        # bombing: a pseudorandom occupied column releases a bomb from its
        # lowest alien on the bomb beat
        bomb_due = ((s.t % self._bomb_every(s)) == 0) & (s.bomb_r < 0) & aliens.any()
        occ = aliens.any(axis=0)
        pick = jax.random.randint(key, (), 0, G, jnp.int32)
        # nearest occupied column to `pick` (static-shape argmin trick)
        dist = jnp.where(occ, jnp.abs(jnp.arange(G) - pick), G + 1)
        bcol = jnp.argmin(dist).astype(jnp.int32)
        lowest = G - 1 - jnp.argmax(aliens[::-1, bcol]).astype(jnp.int32)
        bomb_r = jnp.where(bomb_due, lowest + 1, s.bomb_r + (s.bomb_r >= 0))
        bomb_c = jnp.where(bomb_due, bcol, s.bomb_c)
        bomb_r = jnp.where(bomb_r > G - 1, jnp.int32(-1), bomb_r)

        # deaths: bomb reaches the player row at the player's col, or the
        # fleet reaches the bottom row
        killed = (bomb_r == G - 1) & (bomb_c == pc)
        terminal = killed | aliens[G - 1].any()

        # cleared fleet respawns
        cleared = ~aliens.any()
        aliens = jnp.where(cleared, self._respawn_fleet(s), aliens)

        ns = s._replace(pc=pc, aliens=aliens, adir=adir, shot_r=shot_r,
                        shot_c=shot_c, bomb_r=bomb_r, bomb_c=bomb_c, t=s.t + 1)
        return ns, reward, terminal, jnp.bool_(False)

    def render(self, s: InvadersState) -> jnp.ndarray:
        grid = jnp.where(s.aliens, I_ENEMY, jnp.uint8(0)).astype(jnp.uint8)
        shot_live = s.shot_r >= 0
        grid = grid.at[jnp.clip(s.shot_r, 0, G - 1), s.shot_c].max(
            jnp.where(shot_live, I_BULLET, jnp.uint8(0))
        )
        bomb_live = s.bomb_r >= 0
        grid = grid.at[jnp.clip(s.bomb_r, 0, G - 1), s.bomb_c].max(
            jnp.where(bomb_live, I_BULLET, jnp.uint8(0))
        )
        grid = grid.at[G - 1, s.pc].set(I_PLAYER)
        return _upscale(grid, self.cell)


# --------------------------------------------------------------------------
# seeded level variants (the Procgen-class generalization stand-in,
# BASELINE.md config 5): "<game>@var" draws each episode's level from a
# TRAIN pool of seeds, "<game>@var-test" from a disjoint HELD-OUT pool.
# A level is a deterministic function of its id (fold_in of a fixed base
# key), so train/test splits are reproducible everywhere; per-episode
# randomness (ball entry, car phases) stays on top of the level layout.
# --------------------------------------------------------------------------

N_TRAIN_LEVELS = 16
N_TEST_LEVELS = 16
_LEVEL_BASE_KEY = 9137


def _level_fold(level):
    """Level id -> the level's layout key.  `level` may be a traced i32, so
    per-level eval harnesses can vmap a pinned level over lanes."""
    return jax.random.fold_in(jax.random.PRNGKey(_LEVEL_BASE_KEY), level)


def _draw_level(pool_base: int, pool_size: int, key):
    return pool_base + jax.random.randint(key, (), 0, pool_size, jnp.int32)


class BreakoutVarState(NamedTuple):
    paddle: jnp.ndarray
    ball_r: jnp.ndarray
    ball_c: jnp.ndarray
    dr: jnp.ndarray
    dc: jnp.ndarray
    bricks: jnp.ndarray
    wall: jnp.ndarray  # [G, G] bool — this level's respawn template
    t: jnp.ndarray


class BreakoutVarGame(BreakoutGame):
    """Level-randomized breakout: the level id fixes the brick-wall pattern
    (random ~3/4-density mask over rows 1..3) and the paddle start; ball
    entry column/direction remain per-episode randomness.  The wall template
    rides in the state so cleared walls respawn THIS level's pattern."""

    def __init__(self, pool_base: int, pool_size: int):
        self.pool_base = pool_base
        self.pool_size = pool_size

    def init(self, key) -> BreakoutVarState:
        kl, kc, kd = jax.random.split(key, 3)
        level = _draw_level(self.pool_base, self.pool_size, kl)
        return self._init_level(level, kc, kd)

    def init_at_level(self, level, key) -> BreakoutVarState:
        """Pinned-level init (per-level generalization eval): the layout
        comes from `level` (traced i32 welcome), per-episode randomness
        (ball entry column/direction) from `key`."""
        kc, kd = jax.random.split(key)
        return self._init_level(level, kc, kd)

    def _init_level(self, level, kc, kd) -> BreakoutVarState:
        kw, kp = jax.random.split(_level_fold(level))
        mask = jax.random.uniform(kw, (3, G)) < 0.75
        mask = mask.at[1, G // 2].set(True)  # a level can never be brickless
        wall = jnp.zeros((G, G), bool).at[1:4].set(mask)
        return BreakoutVarState(
            paddle=jax.random.randint(kp, (), 0, G, jnp.int32),
            ball_r=jnp.int32(4),
            ball_c=jax.random.randint(kc, (), 0, G, jnp.int32),
            dr=jnp.int32(1),
            dc=_rand_signs(kd),
            # distinct buffers: bricks and wall both ride the (donated)
            # fused-trainer carry, and donating one buffer twice is a
            # runtime error
            bricks=jnp.array(wall),
            wall=wall,
            t=jnp.int32(0),
        )

    def _respawn(self, s) -> jnp.ndarray:
        return s.wall


class FreewayVarState(NamedTuple):
    chicken: jnp.ndarray
    cars: jnp.ndarray
    speeds: jnp.ndarray  # [8] i32 — this level's per-lane beat
    dirs: jnp.ndarray  # [8] i32 in {-1, +1}
    t: jnp.ndarray


class FreewayVarGame(FreewayGame):
    """Level-randomized freeway: the level id fixes per-lane speeds (2..4)
    and directions; car starting phases remain per-episode randomness."""

    def __init__(self, pool_base: int, pool_size: int, cap: int = 500):
        super().__init__(cap=cap)
        self.pool_base = pool_base
        self.pool_size = pool_size

    def init(self, key) -> FreewayVarState:
        kl, kc = jax.random.split(key)
        level = _draw_level(self.pool_base, self.pool_size, kl)
        return self._init_level(level, kc)

    def init_at_level(self, level, key) -> FreewayVarState:
        """Pinned-level init: lane speeds/dirs from `level` (traced i32
        welcome), car starting phases from `key`."""
        return self._init_level(level, key)

    def _init_level(self, level, kc) -> FreewayVarState:
        ks, kd = jax.random.split(_level_fold(level))
        return FreewayVarState(
            chicken=jnp.int32(G - 1),
            cars=jax.random.randint(kc, (8,), 0, G, jnp.int32),
            speeds=jax.random.randint(ks, (8,), 2, 5, jnp.int32),
            dirs=_rand_signs(kd, (8,)),
            t=jnp.int32(0),
        )

    def _lane_dynamics(self, s):
        return s.speeds, s.dirs


class AsterixVarState(NamedTuple):
    pr: jnp.ndarray
    pc: jnp.ndarray
    active: jnp.ndarray
    col: jnp.ndarray
    dirn: jnp.ndarray
    gold: jnp.ndarray
    speeds: jnp.ndarray  # [8] i32 — this level's per-lane entity beat
    lane_dir: jnp.ndarray  # [8] i32 — this level's fixed per-lane stream dir
    gold_p: jnp.ndarray  # [8] f32 — this level's per-lane gold probability
    t: jnp.ndarray


class AsterixVarGame(AsterixGame):
    """Level-randomized asterix: the level id fixes per-lane entity speeds
    (beat 1..3 — some lanes faster than the base game's 2), a fixed stream
    direction per lane, and a per-lane gold probability (the 'gold layout');
    spawn timing and which lanes fire remain per-episode randomness."""

    def __init__(self, pool_base: int, pool_size: int):
        self.pool_base = pool_base
        self.pool_size = pool_size

    def init(self, key) -> AsterixVarState:
        return self.init_at_level(
            _draw_level(self.pool_base, self.pool_size, key), key
        )

    def init_at_level(self, level, key) -> AsterixVarState:
        """Pinned-level init: asterix levels fully determine the initial
        state (spawn timing is step randomness), so `key` is unused."""
        del key
        ks, kd, kg = jax.random.split(_level_fold(level), 3)
        return AsterixVarState(
            pr=jnp.int32(G // 2),
            pc=jnp.int32(G // 2),
            active=jnp.zeros(8, bool),
            col=jnp.zeros(8, jnp.int32),
            dirn=jnp.ones(8, jnp.int32),
            gold=jnp.zeros(8, bool),
            speeds=jax.random.randint(ks, (8,), 1, 4, jnp.int32),
            lane_dir=_rand_signs(kd, (8,)),
            gold_p=jax.random.uniform(kg, (8,), minval=0.15, maxval=0.5),
            t=jnp.int32(0),
        )

    def _lane_speeds(self, s):
        return s.speeds

    def _spawn_dirs(self, s, key):
        return s.lane_dir

    def _gold_probs(self, s):
        return s.gold_p


class InvadersVarState(NamedTuple):
    pc: jnp.ndarray
    aliens: jnp.ndarray
    adir: jnp.ndarray
    shot_r: jnp.ndarray
    shot_c: jnp.ndarray
    bomb_r: jnp.ndarray
    bomb_c: jnp.ndarray
    fleet: jnp.ndarray  # [G, G] bool — this level's respawn template
    march_every: jnp.ndarray  # i32 — this level's march beat
    bomb_every: jnp.ndarray  # i32 — this level's bomb beat
    t: jnp.ndarray


class InvadersVarGame(InvadersGame):
    """Level-randomized invaders: the level id fixes the initial fleet
    pattern (~4/5-density mask over the 4x6 block), the march beat (3..5)
    and the bomb beat (4..8), plus the starting march direction; bomb column
    choice stays per-episode randomness.  The fleet template rides in the
    state so cleared waves respawn THIS level's pattern."""

    def __init__(self, pool_base: int, pool_size: int):
        self.pool_base = pool_base
        self.pool_size = pool_size

    def init(self, key) -> InvadersVarState:
        return self.init_at_level(
            _draw_level(self.pool_base, self.pool_size, key), key
        )

    def init_at_level(self, level, key) -> InvadersVarState:
        """Pinned-level init: invaders levels fully determine the initial
        state (bomb columns are step randomness), so `key` is unused."""
        del key
        kf, km, kb, kd = jax.random.split(_level_fold(level), 4)
        mask = jax.random.uniform(kf, (4, 6)) < 0.8
        mask = mask.at[0, 3].set(True)  # a level can never start alien-less
        fleet = jnp.zeros((G, G), bool).at[1:5, 2:8].set(mask)
        return InvadersVarState(
            pc=jnp.int32(G // 2),
            # distinct buffers: aliens and fleet both ride the (donated)
            # fused-trainer carry, and donating one buffer twice is a
            # runtime error
            aliens=jnp.array(fleet),
            adir=_rand_signs(kd),
            shot_r=jnp.int32(-1),
            shot_c=jnp.int32(0),
            bomb_r=jnp.int32(-1),
            bomb_c=jnp.int32(0),
            fleet=fleet,
            march_every=jax.random.randint(km, (), 3, 6, jnp.int32),
            bomb_every=jax.random.randint(kb, (), 4, 9, jnp.int32),
            t=jnp.int32(0),
        )

    def _march_every(self, s):
        return s.march_every

    def _bomb_every(self, s):
        return s.bomb_every

    def _respawn_fleet(self, s) -> jnp.ndarray:
        return s.fleet


class CatchVarState(NamedTuple):
    ball_r: jnp.ndarray
    ball_c: jnp.ndarray
    paddle: jnp.ndarray
    drift: jnp.ndarray  # [G] i32 in {-1,0,+1} — this level's per-row wind
    t: jnp.ndarray


class CatchVarGame(CatchGame):
    """Level-randomized catch: the level id fixes a per-row lateral drift
    pattern ('wind' in {-1,0,+1} per row) the ball rides on its way down;
    ball entry column remains per-episode randomness.  Completes 5/5
    variant coverage of the jaxsuite (the Procgen-class stand-in,
    BASELINE.md config 5).

    Design note — this is the suite's NULL-CALIBRATION probe: with the
    terminal row wind-free (see _init_level), a level-blind greedy tracker
    measures 1.0 on BOTH pools (wall clipping lets the 1-cell/step paddle
    catch any persistent wind), so a competent agent's train/held-out gap
    should be ~0 BY CONSTRUCTION.  A measured nonzero gap on catch@var
    flags harness or pool-variance artifacts, not memorization — the
    memorization-sensitive probes are the other four variants, whose
    layouts/dynamics gate score more deeply.  (With terminal wind left in,
    tracking measured 0.06 train / -0.63 held-out vs random -0.69: the
    last-row shift is a coin-flip for any pixel policy since it lands
    after the paddle's final move, which would make the off_random gate
    unclearable by fair play — hence wind-free.)"""

    def __init__(self, pool_base: int, pool_size: int):
        self.pool_base = pool_base
        self.pool_size = pool_size

    def init(self, key) -> CatchVarState:
        kl, kc = jax.random.split(key)
        return self._init_level(_draw_level(self.pool_base, self.pool_size,
                                            kl), kc)

    def init_at_level(self, level, key) -> CatchVarState:
        """Pinned-level init: the wind from `level` (traced i32 welcome),
        the ball entry column from `key`."""
        return self._init_level(level, key)

    def _init_level(self, level, kc) -> CatchVarState:
        drift = jax.random.randint(_level_fold(level), (G,), -1, 2,
                                   jnp.int32)
        # no wind on the terminal row: a last-step shift lands after the
        # paddle's final move and is unobservable-before-commit, so it
        # would be a coin-flip for ANY pixel policy, memorizer or not
        drift = drift.at[G - 1].set(0)
        return CatchVarState(
            ball_r=jnp.int32(0),
            ball_c=jax.random.randint(kc, (), 0, G, jnp.int32),
            paddle=jnp.int32(G // 2),
            drift=drift,
            t=jnp.int32(0),
        )

    def _ball_col(self, s, ball_r):
        return jnp.clip(s.ball_c + s.drift[ball_r], 0, G - 1)


VARIANT_GAMES = {
    "catch": CatchVarGame,
    "breakout": BreakoutVarGame,
    "freeway": FreewayVarGame,
    "asterix": AsterixVarGame,
    "invaders": InvadersVarGame,
}


# --------------------------------------------------------------------------
# registry + batched auto-reset step (the Anakin building block)
# --------------------------------------------------------------------------

GAMES = {
    "catch": CatchGame,
    "breakout": BreakoutGame,
    "freeway": FreewayGame,
    "asterix": AsterixGame,
    "invaders": InvadersGame,
}

# the suite's episode cap, in ticks — the SABER 30-min-cap analog for these
# games: eval/baseline rollouts score each lane's FIRST episode, and a lane
# still mid-episode at the cap contributes its partial return (capped-return
# semantics, eval.py parity) rather than being censored, so unbounded games
# (breakout/invaders respawn their targets) cannot under-count strong agents
EPISODE_TICK_BUDGET = {"catch": 64, "breakout": 512, "freeway": 600,
                       "asterix": 512, "invaders": 512}


def build_rollout(game: "DeviceGame", action_fn, episodes: int,
                  max_ticks: int, history: int = 0, actor_init=None,
                  init_fn=None):
    """One jitted (aux, key) -> first-episode returns [episodes] rollout over
    `episodes` parallel auto-reset lanes — the single episode-accounting core
    shared by the trainers' in-graph eval (train_anakin.build_fused_eval) and
    the benchmark baselines (jaxsuite.rollout_returns).

    `action_fn(aux, states, stack, key) -> actions [episodes]` chooses
    actions from either the game states (state-based scripts; `history=0`
    skips stack upkeep) or the device frame stack (`history=C` maintains a
    [L, H, W, C] stack with cut-zeroing exactly like the training tick).

    Recurrent actors: pass `actor_init(episodes) -> actor_state` (a pytree
    of [episodes, ...] leaves whose reset value is zero, e.g. an LSTM (c, h))
    and an `action_fn(aux, states, stack, key, actor_state) -> (actions,
    actor_state)`; lanes whose episode cut are zero-reset by a keep mask,
    exactly like the training tick's LSTM handling (train_anakin_r2d2.py).

    `init_fn(aux, key) -> [episodes, ...] state pytree` overrides the default
    per-lane pool init (per-level generalization eval pins each lane's level
    via `game.init_at_level`; taking `aux` lets the lane->level assignment be
    a traced argument, so one compile serves every level chunk).  Mid-rollout
    auto-resets still draw from the game's own pool, which is harmless under
    first-episode accounting.

    Returns are capped, never censored: a lane whose first episode is still
    running at `max_ticks` yields its partial return."""
    step = batched_reset_step(game)
    h, w = game.frame_shape

    def mask_actor(actor_state, keep):
        return jax.tree.map(
            lambda x: x * keep.astype(x.dtype).reshape(
                (-1,) + (1,) * (x.ndim - 1)
            ),
            actor_state,
        )

    @jax.jit
    def run(aux, key):
        k_init, k_scan = jax.random.split(key)
        states = (init_fn(aux, k_init) if init_fn is not None
                  else batched_init(game, k_init, episodes))

        def tick(carry, k):
            states, ep, stack, frame, keep, first, done, actor = carry
            ka, ks = jax.random.split(k)
            if history:
                from rainbow_iqn_apex_tpu.parallel.multihost import shift_stack

                stack = shift_stack(stack, frame, keep)
            if actor_init is None:
                actions = action_fn(aux, states, stack, ka)
            else:
                actions, actor = action_fn(aux, states, stack, ka, actor)
            states, ep, nframe, _r, term, trunc, out_ret = step(
                states, ep, actions, ks
            )
            ended = ~jnp.isnan(out_ret)
            first = jnp.where(ended & ~done, out_ret, first)
            done = done | ended
            keep = (~(term | trunc)).astype(jnp.uint8)
            if actor_init is not None:
                actor = mask_actor(actor, keep)
            return (states, ep, stack, nframe, keep, first, done, actor), None

        carry = (
            states, jnp.zeros(episodes),
            jnp.zeros((episodes, h, w, max(history, 1)), jnp.uint8),
            jax.vmap(game.render)(states), jnp.ones(episodes, jnp.uint8),
            jnp.full((episodes,), jnp.nan), jnp.zeros(episodes, bool),
            actor_init(episodes) if actor_init is not None else (),
        )
        carry, _ = jax.lax.scan(tick, carry, jax.random.split(k_scan, max_ticks))
        _s, ep, _st, _f, _k, first, done, _a = carry
        # capped-return semantics: an unfinished first episode scores its
        # running return (ep still tracks the first episode iff never done)
        return jnp.where(done, first, ep)

    return run


def make_device_game(name: str, tick_cap: int = 0) -> DeviceGame:
    """The game of a `jaxgame:<name>` id.  `tick_cap` > 0 truncates an episode
    at so many ticks where the game ends its episodes by a time limit of its
    own (freeway's `cap`, 500 otherwise); 0 leaves the game as it is."""
    game = _named_game(name)
    if tick_cap:
        if not hasattr(game, "cap"):
            raise ValueError(
                f"game '{name}' has no time limit of its own to set: "
                f"device_game_tick_cap is for freeway")
        game.cap = int(tick_cap)
    return game


def _named_game(name: str) -> DeviceGame:
    if "@" in name:
        base, variant = name.split("@", 1)
        cls = VARIANT_GAMES.get(base)
        if cls is None:
            raise ValueError(
                f"game '{base}' has no seeded-variant mode (have: "
                f"{', '.join(sorted(VARIANT_GAMES))})"
            )
        if variant == "var":
            return cls(0, N_TRAIN_LEVELS)
        if variant == "var-test":
            return cls(N_TRAIN_LEVELS, N_TEST_LEVELS)
        raise ValueError(
            f"unknown variant '@{variant}' for '{base}' (want '@var' for the "
            "train pool or '@var-test' for the held-out pool)"
        )
    try:
        return GAMES[name]()
    except KeyError:
        raise ValueError(
            f"unknown jax game '{name}' (have: {', '.join(sorted(GAMES))})"
        ) from None


def tick_budget(name: str, default: int = 512) -> int:
    """Episode tick cap for a game id, variant-suffix aware."""
    return EPISODE_TICK_BUDGET.get(name.split("@", 1)[0], default)


def batched_init(game: DeviceGame, key, lanes: int):
    """Per-lane independent initial states: [L, ...] state pytree."""
    return jax.vmap(game.init)(jax.random.split(key, lanes))


def batched_reset_step(game: DeviceGame):
    """Returns step(states, actions, key) -> (states, frames, reward,
    terminal, truncated, ep_return) for [L]-batched lanes, with auto-reset:
    on terminal OR truncation the lane's state is re-initialised and the
    returned frame is the new episode's first observation — the exact
    VectorEnv.step contract (envs/base.py), in-graph.  ep_return is the
    completed episode's return on cut ticks and NaN elsewhere; the running
    accumulator rides in the state pytree via a wrapper field."""

    def one(carry, action, key):
        state, ep_ret = carry
        k_step, k_reset = jax.random.split(key)
        ns, reward, term, trunc = game.step(state, action, k_step)
        cut = term | trunc
        ep_ret = ep_ret + reward
        out_ret = jnp.where(cut, ep_ret, jnp.nan)
        fresh = game.init(k_reset)
        ns = jax.tree.map(lambda new, init: jnp.where(cut, init, new), ns, fresh)
        frame = game.render(ns)
        ep_ret = jnp.where(cut, 0.0, ep_ret)
        return (ns, ep_ret), frame, reward, term, trunc & ~term, out_ret

    vone = jax.vmap(one)

    def step(states, ep_rets, actions, key):
        lanes = actions.shape[0]
        keys = jax.random.split(key, lanes)
        (states, ep_rets), frames, reward, term, trunc, out_ret = vone(
            (states, ep_rets), actions, keys
        )
        return states, ep_rets, frames, reward, term, trunc, out_ret

    return step


# --------------------------------------------------------------------------
# host adapter: a DeviceGame as an ordinary Env (works in every trainer)
# --------------------------------------------------------------------------


class JaxGameEnv(Env):
    """Host-loop adapter.  Heavier per step than a native NumPy env (one
    jitted dispatch per step) — it exists for eval/CI parity and for running
    jax games through the host trainers; the fused Anakin path is where
    these games perform."""

    def __init__(self, name: str, seed: int = 0):
        self.game = make_device_game(name)
        self._key = jax.random.PRNGKey(seed)
        self._step = jax.jit(self.game.step)
        self._init = jax.jit(self.game.init)
        self._render = jax.jit(self.game.render)
        self._state = None
        self._ret = 0.0

    @property
    def num_actions(self) -> int:
        return self.game.num_actions

    @property
    def frame_shape(self) -> Tuple[int, int]:
        return self.game.frame_shape

    def _split(self):
        self._key, k = jax.random.split(self._key)
        return k

    def reset(self) -> np.ndarray:
        self._state = self._init(self._split())
        self._ret = 0.0
        return np.asarray(self._render(self._state))

    def step(self, action: int) -> TimeStep:
        self._state, reward, term, trunc = self._step(
            self._state, jnp.int32(action), self._split()
        )
        reward = float(reward)
        self._ret += reward
        done = bool(term) or bool(trunc)
        info = {"episode_return": self._ret} if done else None
        return TimeStep(
            np.asarray(self._render(self._state)),
            reward,
            bool(term),
            bool(trunc),
            info,
        )
