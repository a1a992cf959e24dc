"""On-chip benchmark of the ACiS reproduction.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator it
finds and prints one JSON result line.  Everything a cell needs is found
by name: its model configuration in ``bench/configs/<config>.json``, its
traffic in ``bench/traffic/<traffic>.json``, its correctness limits in
``bench/limits/<cell>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.
"""
