"""On-chip benchmark of FedSTIL: federated rounds and retrieval serving.

``python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything a cell needs is found by name: ``cells/<cell>.json`` names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the configuration's ``system`` picks the
driver (``systems/<system>.py``) and its plain reference
(``reference/<system>.py``); every per-layer metric is one reader in
``metrics/<metric>.py``, and every kernel's operations and bytes one
function in ``kernels/<kernel>.py``.
"""
