"""Share of the router's assignments that fell on the experts this chip
holds, in the last learn step the driver saw (the program's own counter, the
mean over its four expert layers): 100 x 8/32 = 25.0 if routing is even, and
by the benchmark's seeded selection bias on every seed (one held expert among
each layer's four chosen).  None where the driver keeps no such counter."""

from benchmarks.readers.moe_held_assign_share import read  # noqa: F401
