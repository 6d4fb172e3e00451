"""YCSB benchmark of the chain-offloaded key-value store on the chip.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything here is
the yardstick: traffic generation, the plain reference, the trace
reduction, the peak table and the roofline byte count.  From the program
it takes only ``ShardedKVService`` and its host bootstrap.
"""
