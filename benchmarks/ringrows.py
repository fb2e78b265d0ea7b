"""Ring rows in and out of a sequence replay's state, in their logical
shapes: the one seam between the benchmark and how a ring stores a row.

A row is a dict over `FIELDS`: `frames [n, L, h, w] u8`, `actions`,
`rewards`, `dones`, `valids` `[n, L]`, `init_c`, `init_h` `[n, m]` (m may be
0: a core that stores no state), as `ringfill.rows` makes them.  A replay
that stores a row in another shape says how, with two jit-safe methods of
its own:

    write_rows(state, rows, start) -> state   rows [start, start + n) written,
                                              cast to the stored dtypes; n is
                                              static, start may be traced;
                                              priority, pos, filled and the
                                              builders untouched
    read_rows(state, start, stop) -> rows     static bounds

and the functions below call them.  A replay without them (the program's
`DeviceSequenceReplay` today) stores a row as it is made, field for field
with the rows leading, and gets the plain slice update and slice: this file
is the only one in the benchmark that indexes a replay state's row arrays
(`tests/test_ring_storage.py` holds every other file to that).
"""

from __future__ import annotations

import jax

FIELDS = ("frames", "actions", "rewards", "dones", "valids",
          "init_c", "init_h")


def write_rows(replay, state, rows, start):
    own = getattr(replay, "write_rows", None)
    if own is not None:
        return own(state, rows, start)
    return state._replace(**{
        name: jax.lax.dynamic_update_slice_in_dim(
            getattr(state, name),
            rows[name].astype(getattr(state, name).dtype), start, 0)
        for name in FIELDS})


def read_rows(replay, state, start, stop):
    own = getattr(replay, "read_rows", None)
    if own is not None:
        return own(state, start, stop)
    return {name: getattr(state, name)[start:stop] for name in FIELDS}
