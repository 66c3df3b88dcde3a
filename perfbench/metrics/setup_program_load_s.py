"""Seconds of the set-up spent making programs runnable: every kernel's
first launch (`kernel.first_launch`: trace, lower, and the XLA compile or
its load from the disk cache) plus the second trace and lowering that
reads its cost (`kernel.cost_capture`), both in
physical/compile.KernelCache, before the window. Warm it is cache loads;
cold it is the compiles."""

from perfbench import spans

LAYER = "compile"
SOURCE = "program_span"
MOVES = "setup_s"
UNIT = "s"


def read(run):
    return spans.seconds(spans.before_window(run),
                         ("kernel.first_launch", "kernel.cost_capture"))
