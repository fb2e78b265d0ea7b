"""Device self time a tick of acting with the core (`tick_act`: shift_stack,
trunk, input projection, one step of all 16 layer applications, heads): what
`core_act_device_ms` reads in its cell."""

from benchmarks.readers.core_act_device_ms import read  # noqa: F401
