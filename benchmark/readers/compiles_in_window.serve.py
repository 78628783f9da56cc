"""Programs compiled or loaded from the compile cache inside the window
(the program's ``jit.compile`` and ``jit.cache_load`` records,
``program_spans.py``): the warm-up should leave none."""

import program_spans


def read(run):
    return program_spans.compiles_in_window(run)
