"""On-chip benchmark of the circulant collectives' ZeRO-1 trainer and the
paged-KV server.  ``python3 -m bench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell of ``BENCHMARK.json`` once
and prints one JSON line; see ``bench/run.py``."""
