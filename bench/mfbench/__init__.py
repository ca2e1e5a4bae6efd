"""The chip benchmark of the matrix-function service (``bench/run.py``).

It drives ``repro.serve.MatFnEngine`` as a started daemon and takes from
the program only the engine itself, its counters, spans and kernel
names. Traffic generation, the reduction of traces and spans to metrics,
the peaks table, the operation counts and the host f64 reference that
decides ``correct`` all live here.
"""
