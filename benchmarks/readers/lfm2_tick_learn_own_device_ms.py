"""Device self time a learn step of `tick_learn`'s own ops (in `tick_learn`
and in none of `learn_step`, `replay_draw`, `replay_gather`,
`replay_writeback`) in the cell `lfm2-r2d2-fused`: what
`tick_learn_own_device_ms` reads in its cells: the conditional and the scan
over `learn_fn`, the relayouts of the learn step's operands, the fills
shaped like the expert `switch`'s largest row buffer."""

from benchmarks.readers.tick_learn_own_device_ms import read  # noqa: F401
