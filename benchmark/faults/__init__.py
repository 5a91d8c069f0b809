"""Faults planted under a family's timed path, one module a family: each
takes a ``pytest.MonkeyPatch`` and breaks the PROGRAM (never the
reference). `benchmark/tests/` plants them at the rehearsal's size,
`benchmark/faulted_readings.py` at the cell's own size on the chip."""
