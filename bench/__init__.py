"""On-chip benchmark of the coloring runtime (``python bench/run.py``).

Everything that measures lives here and nowhere in ``src/``: the data
generators (``graphs/``), the one request generator (``requests.py``)
that reads the traffic mixes (``traffic/*.json``) with their mask kinds
(``masks/``), the configurations (``configs/*.json``), the plain
reference and its checks (``reference.py``, one module per problem in
``references/``), the trace reduction (``trace.py``), the least-bytes
function of the local step (``roofline.py``), the table of peaks
(``peaks.json``) and one reader per metric (``metrics/<name>.py``).
From the program it takes only the system under test
(``repro.serve.coloring.ColoringService``), its stats and counters, and
the names of its device operations in the profiler trace.
"""
