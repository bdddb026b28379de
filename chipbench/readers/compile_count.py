"""XLA compilations the program's own counter saw between the start and
the end of the traced job: cache hits are not compilations."""


def read(ctx, params):
    before, after = ctx["compiles"]
    asked = after["xla_compiles"] - before["xla_compiles"]
    hits = after["compile_cache_hits"] - before["compile_cache_hits"]
    return float(asked - hits)
