"""Device self time a learn step of the optimizer (`optimizer` inside
`learn_step`) in the cell `ouro-r2d2-fused`: what `optimizer_device_ms` reads
in its cells, here over 215M parameters that the forward uses four times
each, so a quarter of the other core cells' share."""

from benchmarks.readers.optimizer_device_ms import read  # noqa: F401
