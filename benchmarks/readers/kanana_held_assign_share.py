"""Share of the router's assignments that fell on the experts this chip
holds, in the last learn step the driver saw (the program's own counter, the
mean over its four expert layers): 100 x 16/128 = 12.5 if routing is even,
and by the benchmark's seeded selection bias on every seed.  None where the
driver keeps no such counter."""

from benchmarks.readers.moe_held_assign_share import read  # noqa: F401
