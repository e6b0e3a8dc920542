"""Traffic kind `seq-closed-loop`: a fixed pool of callers, each sending
its next query when its last came back, against a served sequence model
whose users' histories (lengths, items) are part of the traffic.
Parameters: benchmarks/traffic/<mix>.json."""

from lib import seq_serve


def run(ctx, cell):
    return seq_serve.run(ctx, cell)
