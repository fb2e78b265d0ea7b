"""Device self time a learn step of the optimizer (`optimizer` inside
`learn_step`) in the cell `laguna-xs2-r2d2-fused`: what `optimizer_device_ms`
reads in its cells, here over 448M parameters of which 45% are the held
experts'."""

from benchmarks.readers.optimizer_device_ms import read  # noqa: F401
