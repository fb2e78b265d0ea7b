"""Device self time of one learn step (`learn_step`) in the cell
`qwen3-next-r2d2-fused`: the scope `learn_device_ms`, `core_learn_device_ms`
and `kanana_learn_device_ms` read in their cells, under a name of its own
because the four are not comparable."""

from benchmarks.readers.learn_device_ms import read  # noqa: F401
