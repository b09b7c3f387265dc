"""GraphMP benchmark: cells, traffic, plain references and trace reduction.

Run one cell with ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.  Everything a
cell needs is found by name: ``BENCHMARK.json`` names the cell, its
configuration (``bench/configs/<name>.json``), its traffic mix
(``bench/traffic/<name>.json``) and its per-layer metrics
(``bench/metrics/<name>.py``).
"""
