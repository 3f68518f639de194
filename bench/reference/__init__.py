"""The plain reference the benchmark holds the program to.

A straightforward restatement, in plain Python, of what one simulation
cell means: the key stream of the configured workload, the engine's
recorded suboperations, and the single-core scheduler with its prefetch
window, parked-thread heap and per-SSD token clocks.  It imports nothing
of the program under test and takes nothing the program has made; each
engine, key distribution and arrival process is a module of its own under
``engines/``, ``workloads/`` and ``arrivals/``, found by the name the
configuration gives it.
"""
