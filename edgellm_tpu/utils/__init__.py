"""Shared utilities: timing helpers, the host-side clock protocol."""
from .clock import MONOTONIC, Clock, FakeClock, sequence_clock
from .profiling import timed, throughput

__all__ = ["timed", "throughput",
           "Clock", "MONOTONIC", "FakeClock", "sequence_clock"]
