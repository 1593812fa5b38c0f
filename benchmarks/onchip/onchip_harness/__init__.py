"""The on-chip benchmark's own code: corpus and query streams, the exact
reference, trace reduction and metric readers.  Nothing here imports the
system under test except where it drives it (``cell.py``)."""
