"""Device self time a tick of acting with the core (`tick_act`: shift_stack,
trunk, input projection, one step of the five layers, heads): what
`core_act_device_ms` reads in its cell.  A tick reads all 352M expert
parameters for 16 lanes' 64 assignments a layer."""

from benchmarks.readers.core_act_device_ms import read  # noqa: F401
