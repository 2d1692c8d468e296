"""The on-chip benchmark harness: everything between ``BENCHMARK.json`` and the
program under test.

The harness finds each piece of a cell by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json     the configuration's sizes, as it is run
  configs/<config>.py       its network: the plain reference (weights
                            from the seed, forward in plain ``jax.numpy``),
                            the program's config and the forward's work
                            op by op (the contract is in ``spec.py``)
  traffic/<traffic>.json    a traffic mix: parameters read by ``traffic.py``;
                            its ``kind`` names the module of ``kinds/``
                            that runs it
  metrics/<metric>.py       one per-layer metric: ``read(ctx)`` returns a
                            number, or None where it finds nothing to read

Nothing here runs at import.
"""
