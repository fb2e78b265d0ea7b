"""Device self time a tick of acting with the core (`tick_act`: shift_stack,
trunk, input projection, one step of the five layers, heads): what
`core_act_device_ms` reads in its cell.  A tick reads the 16 lanes' five
rings, 0.47 GB of keys and values, and the experts the lanes' tokens chose."""

from benchmarks.readers.core_act_device_ms import read  # noqa: F401
