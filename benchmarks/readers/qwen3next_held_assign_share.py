"""Share of the router's assignments that fell on the experts this chip
holds, in the last learn step the driver saw (the program's own counter, the
mean over its four expert layers): 100 x 32/512 = 6.25 if routing is even,
5.0 by the benchmark's seeded selection bias on every seed (2 of the 40
chosen slots are held ones: round(2.5)).  None where the driver keeps no such
counter."""

from benchmarks.readers.moe_held_assign_share import read  # noqa: F401
