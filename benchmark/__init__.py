"""The benchmark of the served path (BENCHMARK.json, ``python3 benchmark/run.py``).

Everything a later PR must not be able to change lives here: the traffic
generators, the client, the reduction from arrivals, counters and the device
trace to metrics, the table of peaks and the comparison that decides
``correct``.  From the program it takes the system under test (built the way
``web/server_main.py:run()`` builds it), its ``/metrics`` text and the names
the profiler prints.
"""
