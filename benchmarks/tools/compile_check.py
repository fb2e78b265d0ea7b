"""Compile a cell's programs at their real sizes for a described v5e:2x2, with
no chip attached: the program that makes the state from the seed, and the
fused segment.  What the TPU compiler refuses here (a ring that does not fit
the chip's memory, above all) costs no chip time.  Nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_check.py <cell>
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import importlib

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import harness

    cell = sys.argv[1]
    wl, cfg, traffic = harness.load_cell(cell)
    chips = int(wl["chips"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    drv = importlib.import_module(
        "benchmarks.drivers." + cfg["driver"]).Driver(
            cfg["fields"], traffic, 0, chips, make_state=False)
    shaped = lambda tree, shardings: jax.tree.map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)
    abstract_key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    abstract_carry = jax.eval_shape(drv.make_carry, abstract_key, abstract_key)
    one = SingleDeviceSharding(topo.devices[0])
    key_s, carry_s = one, jax.tree.map(lambda _: one, abstract_carry)
    key = shaped(abstract_key, key_s)
    carry = shaped(abstract_carry, carry_s)
    make = jax.jit(drv.make_carry)
    for name, fn, args in [("make_carry", make, (key, key)),
                           ("segment", drv.segment, (carry, key))]:
        t = time.time()
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        print(f"{cell} {name}: compiled in {time.time() - t:.0f} s; "
              f"arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"outputs {m.output_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.2f} GB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
