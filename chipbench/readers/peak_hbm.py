"""Bytes of the fullest chip's memory, in GB (1e9): the `ctx` entry the
metric's file names under `key`, by default the peak the result line
reports as `memory_peak_bytes`."""


def read(ctx, params):
    held = ctx[params.get("key", "memory_peak_bytes")]
    return held / 1e9 if held else None
