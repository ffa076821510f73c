"""The benchmark of hetu-tpu: one command runs one cell once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation (``traffic.py``), the peak table
(``peaks.py``), operation and byte counts (``counts.py``), the reduction of
a profiler trace (``trace.py``), the plain float32 references
(``reference/``) and the comparison that decides ``correct``.  From the
program it takes the system under test (``adapters/``) and nothing else.

Cells, configurations and per-layer metrics are data: ``BENCHMARK.json``
names them, ``workloads/<cell>.json``, ``configs/<config>.json`` and
``metrics/<metric>.json`` hold them, and nothing in this package asks for
one by name.
"""
