"""On-chip benchmark of the planned sparse runtime.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on and prints
one JSON result line.  Configurations (``configs/<name>.json``), traffic
mixes (``traffic/<name>.json``), op drivers (``ops/<op>.py``) and per-layer
metric readers (``metrics/<metric>.py``) are found by name, so a new cell,
mix or metric is a new file.
"""
