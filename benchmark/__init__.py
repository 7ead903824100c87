"""The benchmark of tpu-rpc: everything the yardstick is made of.

`BENCHMARK.json` at the repo root names the cells.  A cell names a
configuration (`configs/<name>.json`) and a traffic mix
(`traffic/<name>.json`); the configuration names its driver
(`drivers/<name>.py`); every per-layer metric is a reader of its own
(`layer_metrics/<name>.py`).  A later PR adds files and entries and edits
nothing that is here.  From the program the benchmark takes only the
system under test, its counters and its kernel names.
"""
