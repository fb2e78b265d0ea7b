"""Device self time of one learn step (`learn_step`) in the cell
`laguna-xs2-r2d2-fused`: the scope `learn_device_ms` and the five other core
cells' `*_learn_device_ms` read in theirs, under a name of its own because
the seven are not comparable (8 sequences of 1,024 steps here)."""

from benchmarks.readers.learn_device_ms import read  # noqa: F401
