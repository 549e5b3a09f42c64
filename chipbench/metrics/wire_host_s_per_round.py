"""Host seconds per traced round on the wire path: slicing each client's
row out of the stacked deltas, the per-leaf wire round trip with the
freeze mask, and the wire-byte count (self time of the program's
``unstack``, ``wire`` and ``wire_bytes`` spans, ``fl/executor.py``)."""
from harness import program_spans


def read(run):
    return program_spans.seconds_per_round(
        run, ["unstack", "wire", "wire_bytes"])
