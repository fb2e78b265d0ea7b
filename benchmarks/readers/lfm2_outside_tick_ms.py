"""Device self time a dispatch of the operations in no `tick_*` scope, in the
cell `lfm2-r2d2-fused`: what `outside_tick_ms` reads in its cell (the scan's
own `while`, whose self time is the waits between a tick's ops, the copies
and slices round the scan).  Until the program put the instructions that
bear the compiler's own label down to what feeds them
(`obs/device_scopes.instruction_scopes`, PR 43) every grouped product of the
expert layers stood here: 178 ms of a 930 ms dispatch."""

from benchmarks.readers.outside_tick_ms import read  # noqa: F401
