"""The benchmark's own library: launch, wire, load loop, scrapes, reduction.

Nothing here imports the program (``ingress_plus_tpu``), ``chip_smoke.py``
or ``bench.py``; the parent process never imports JAX.
"""
