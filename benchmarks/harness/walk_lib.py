"""How much of the admission width's attention square the ``paged_decode``
kernel walks, from the engine's own counters over the window
(``harness/engine_lib.py`` says how they are differenced): per admission
call ``admit_attn_blocks_walked`` counts the (query tile, key block of 128
keys) pairs the kernel walks, each tile of each row (256 or 320 rows of a
``docs_closed`` bucket: ``ops/paged_attention.py:query_tile``) to its own
sweep bound, and ``admit_attn_blocks_square`` the tiles of the call's bucket
times each slot's bound, what the kernel walked while one grid step held the
bucket whole (``paddle_tpu/serving/metrics.py:ADMIT_WALK_COUNTERS``, PR 42;
both by ``ops/paged_attention.py:sweep_bound`` of the arrays the program was
given).  None, and the metric is left out, for a program from before the
counters."""
from benchmarks.harness.engine_lib import _ratio


def admit_attn_walked_share(ev):
    return _ratio(ev, ("admit_attn_blocks_walked",),
                  ("admit_attn_blocks_square",), 100.0)
