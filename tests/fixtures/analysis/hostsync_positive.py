"""Golden fixture: host-sync POSITIVE — bare materializations of device
values inside a declared hot-path function."""

import numpy as np


def hot_learn(info):
    loss = float(info["loss"])  # the classic regression: a per-step float(loss)
    pri = np.asarray(info["priorities"])  # device pull outside sanctioned()
    steps = info["steps"].item()  # scalar sync
    return loss, pri, steps
