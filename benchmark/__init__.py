"""The repository's benchmark: one command, driven by data files.

``BENCHMARK.json`` at the root names every configuration, cell and metric;
this package holds the yardstick they are measured with (traffic
parameters, drivers that stand a trainer up, the plain float32 reference,
shape-based operation counts, the peaks table and the trace reduction).
Later PRs add files here and entries there; they do not edit what exists.
"""
