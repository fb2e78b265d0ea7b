"""Host-clock time of one call of a jitted layer program: calls back to back,
closed by block_until_ready, enough of them to span 0.3 s (the host's clock
is off by some half a millisecond), and the mean over them."""

import time

import jax

SPAN_S, MIN_CALLS, MAX_CALLS = 0.3, 50, 20000


def mean_call_ms(fn, args):
    jax.block_until_ready(fn(*args))  # compile, outside the timing
    t = time.perf_counter()
    for _ in range(10):
        out = fn(*args)
    jax.block_until_ready(out)
    est = max((time.perf_counter() - t) / 10, 1e-6)
    calls = int(min(max(SPAN_S / est, MIN_CALLS), MAX_CALLS))
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / calls
