"""Device self time a dispatch of the instructions the compiler made (no
`op_name` of their own: layout copies, the slices and copies of its async
pairs) in the cell `lfm2-r2d2-fused`: what `compiler_made_device_ms` reads
in its cells; the run's stderr lists them by the scope path of the op that
reads each.  None on a program that does not tell them apart."""

from benchmarks.readers.compiler_made_device_ms import read  # noqa: F401
