"""chipbench: the chip benchmark of kfserving-tpu (see BENCHMARK.json, PERF.md).

Everything the yardstick needs lives here: traffic generation, the load
generator, the reduction from client events, counters and the profiler trace
to metrics, the peaks table, the ops-and-bytes functions and the plain
references.  From the program it takes only the servers under test, which it
starts as children and reads from outside (HTTP, /metrics, logs, a trace).
"""
