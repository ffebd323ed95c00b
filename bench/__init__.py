"""Chip benchmark of the served prediction-query path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

* ``bench/configs/<config>.json``  the deployment: schema, scale, model;
* ``bench/traffic/<traffic>.json`` the traffic mix read by ``traffic.py``;
* ``bench/metrics/<metric>.py``    a reader with ``read(ctx)``;
* ``bench/peaks.json``             the chip's peaks, keyed by device kind.

The yardstick lives here too: data and model generation (``data.py``,
``model.py``), the numpy reference (``reference.py``), the trace reduction
(``trace.py``) and the operation and byte counts (``work.py``). Only
``program.py`` imports the system under test.
"""
