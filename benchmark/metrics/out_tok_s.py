"""Tokens made visible in the window over the window's seconds."""
from benchmark.latency import tokens_in_window


def read(record: dict):
    return tokens_in_window(record) / record["window_s"]
