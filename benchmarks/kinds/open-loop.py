"""Traffic kind `open-loop`: requests due on a schedule (Poisson gaps at
the cell's fixed rate), sent whatever the server does, each timed from
when it was due. Parameters: benchmarks/traffic/<mix>.json."""

from lib import serve


def run(ctx, cell):
    return serve.run(ctx, cell, "open")
