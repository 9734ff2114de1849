"""As engine.prefill_ms_per_1k_tokens, for the long-document cell, where
prefill is the main layer and moves its throughput."""
import readers

UNIT = "ms"


def read(ctx):
    return readers.prefill_ms_per_1k(ctx)
