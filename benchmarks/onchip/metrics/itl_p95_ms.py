"""95th percentile of every gap between consecutive tokens of the
window's requests, prefill stalls included, in ms."""
import readers

UNIT = "ms"


def read(ctx):
    return readers.percentile(readers.itls_ms(ctx), 95)
