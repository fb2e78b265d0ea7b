"""Device self time of one learn step (`learn_step`) in the cell
`lfm2-r2d2-fused`: the scope `learn_device_ms` and the four other core cells'
`*_learn_device_ms` read in theirs, under a name of its own because the six
are not comparable."""

from benchmarks.readers.learn_device_ms import read  # noqa: F401
