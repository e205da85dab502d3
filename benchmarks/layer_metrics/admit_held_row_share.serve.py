"""Admitted rows that were held back to fill an admission call with the next freed slot's row / admitted rows, x 100 (counters admit_rows_held, admit_rows), gpt2_small.docs_closed."""
from benchmarks.harness.hold_lib import admit_held_row_share as read  # noqa: F401
