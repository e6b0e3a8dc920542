"""Traffic kind `closed-loop`: a fixed pool of callers, each sending its
next query when its last came back. Parameters:
benchmarks/traffic/<mix>.json."""

from lib import serve


def run(ctx, cell):
    return serve.run(ctx, cell, "closed")
