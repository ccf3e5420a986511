"""On-chip serving benchmark of ``repro``: one cell per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one entry of ``workloads`` in ``BENCHMARK.json`` on the accelerator it
is started on.  Configurations, traffic mixes and per-layer metric readers
are files under ``bench/`` found by the names ``BENCHMARK.json`` gives.
"""
