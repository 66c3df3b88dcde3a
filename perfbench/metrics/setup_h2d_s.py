"""Seconds of the set-up in which the registered tables' planes go to
the device: the `ingest.h2d` spans (physical/operators.py, the leaf's
first `execute`) before the window — Arrow to numpy, padding to the
tile's capacity, and the enqueue of every host-to-device copy. The copies
are asynchronous: what is still in flight when the span ends is waited
for by the first program and counts there."""

from perfbench import spans

LAYER = "ingest"
SOURCE = "program_span"
MOVES = "setup_s"
UNIT = "s"


def read(run):
    return spans.seconds(spans.before_window(run), ("ingest.h2d",))
