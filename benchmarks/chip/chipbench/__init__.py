"""On-chip benchmark of the historical graph store, driven by data:
configurations in ``configs/``, traffic mixes in ``traffic/`` (each
naming the module of its kind there), operations in ``operations/`` and
metric readers in ``metrics/``, each found by its name in
``BENCHMARK.json`` or in the mix."""
