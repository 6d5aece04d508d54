"""On-chip benchmark of the OTA-FL fleet sweep (see BENCHMARK.json, PERF.md).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once and prints one JSON result line.  Everything that
belongs to one configuration, traffic mix, cell or per-layer metric lives
in a file of its own under this directory, found by the name that
BENCHMARK.json gives it:

    configs/<config>.json   sizes of the configuration as it is run
    configs/<config>.py     its plain reference model, inputs and FLOP count
    traffic/<traffic>.json  the sweep mix: seeds, batch, rounds, placement
    limits/<cell>.json      the correctness limits of the cell
    metrics/<metric>.py     the reader of one per-layer metric
"""
