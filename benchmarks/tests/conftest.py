"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of tier-1 (`pytest tests/`), which this directory may not
join: BENCHMARK.json's `paths` hold the benchmark and nothing else.  Nothing
here describes a TPU topology or loads libtpu at import or collection.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
