"""Device time of the grammar traversal programs per answered query (ms),
from the profiler trace.

The programs are the XLA modules of the batched traversals in
``core/batch.py`` (``_frontier_*``, ``_leveled_*``, ``_per_file_*``, the
fused frontier) and the single-corpus ones in ``core/traversal.py``
(``_top_down_*`` and the jitted inner ``run`` loops), as the trace names
them: ``jit_<function>``.
"""

import re

TRAVERSAL = re.compile(r"^jit__(frontier|leveled|per_file|top_down)|^jit_run$")


def read(record):
    if record.trace is None or not record.answered:
        return None
    ms = 1e3 * sum(s for name, s in record.trace.module_s.items()
                   if TRAVERSAL.match(name))
    return ms / record.answered if ms > 0 else None
