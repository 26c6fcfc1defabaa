"""Chip benchmark of the serving engine: open-loop traffic on models at
published widths, judged against a plain float32 reference.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. Cells, configurations, traffic mixes and
metrics are data: ``BENCHMARK.json`` names them, and the files under
``bench/configs``, ``bench/traffic`` and ``bench/metrics`` hold them; a
configuration's architecture is its module under ``bench/models``. A cell
on N chips serves N one-chip replicas behind the program's router.
"""
