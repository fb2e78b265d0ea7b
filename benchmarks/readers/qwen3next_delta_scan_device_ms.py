"""Device self time a learn step of the chunked delta-rule recurrence in the
three Gated DeltaNet layers, forward and backward (`kda_scan` inside
`learn_step`, which holds `kda_prep`, on a TPU the two tile kernels of
models/kda_tile.py): what `kda_scan_device_ms` reads in its cell, here with
one scalar gate a head broadcast over the key channels.  None where the
program has no such scope."""

from benchmarks.readers.kda_scan_device_ms import read  # noqa: F401
