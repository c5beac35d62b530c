"""On-chip benchmark of the fleet scheduler service.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` and prints one JSON
line. Everything that measures lives here: the traffic generator, the
plain reference that decides ``correct``, the trace reduction, the peak
table and the per-layer metric readers.
"""
