"""Median idle gap on the device between consecutive executions of the step program."""
from benchmarks.harness.layer_lib import host_gap_ms as read  # noqa: F401
