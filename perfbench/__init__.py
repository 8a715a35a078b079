"""perfbench — the benchmark of record for pinot-tpu (see perfbench/README.md).

Nothing here is imported by the program, and the launcher (`run.py`) imports
neither jax nor pinot_tpu: the chip belongs to the server process it starts.
"""
