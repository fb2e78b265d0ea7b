"""Share of the router's assignments that fell on the experts this chip
holds, in the last learn step the driver saw (the program's own counter, the
mean over its four expert layers): 100 x 16/256 = 6.25 if routing is even,
and by the benchmark's seeded selection bias on every seed (one held expert
among the eight chosen in two of the four layers, none in the other two).
None where the driver keeps no such counter."""

from benchmarks.readers.moe_held_assign_share import read  # noqa: F401
