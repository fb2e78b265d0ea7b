"""Device self time of one learn step (`learn_step`) in the cells whose
recurrent core is not the LSTM: the scope `learn_device_ms` reads in the LSTM
cell, under a name of its own because the two are not comparable."""

from benchmarks.readers.learn_device_ms import read  # noqa: F401
