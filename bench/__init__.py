"""On-chip benchmark of the Bloom Clock causality service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Everything
the harness needs is found by name under this directory:

- ``configs/<config>.json``   a deployment: sizes, guarantees, limits
- ``traffic/<traffic>.json``  a traffic mix, read by ``lib/traffic.py``
- ``drivers/<driver>.py``     drives one kind of deployment (named by
                              the configuration's ``driver`` key)
- ``metrics/<metric>.py``     one reader per per-layer metric
- ``kernels/<kernel>.py``     a kernel's logical bytes and operations
- ``reference/``              the plain references ``correct`` rests on
- ``peaks.json``              published chip peaks, by ``device_kind``
"""
