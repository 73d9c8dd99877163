"""The chip benchmark of the FedDCT federation server: ``run.py`` runs one
cell of ``BENCHMARK.json`` once (see its docstring)."""
