"""1 minus the union of the innermost device-op intervals over the traced
window, in percent: how far the host and the waits inside a dispatch hold
the chip back in this cell (what `device_idle_share` reads in `r2d2-fused`
and `ouro_device_idle_share` in its cell: a `model_config` PR may not append
a cell to an accepted list, so the cell has an entry of its own until a
`benchmark` PR folds them)."""

from benchmarks.readers.ouro_device_idle_share import read  # noqa: F401
