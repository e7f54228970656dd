"""What is left of the pre-chip timing package: two modules that tier-1 tests
hold, and nothing that times the program.

The yardstick is ``benchmark/`` at the repo root (``benchmark/run.py``,
``BENCHMARK.json``); it imports nothing from here.

- ``northstar.py``: the AOT compile against a described v5p topology
  (``get_topology`` / ``analyze`` and the program builders) that
  ``tests/test_census.py``, ``tests/test_northstar.py`` and
  ``tests/test_overlap.py`` share.
- ``pretrain.py``: the training CLI that ``tests/test_data.py`` drives in a
  subprocess to check the native data loader end to end.
"""
