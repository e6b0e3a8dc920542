"""Traffic kind `mixed-closed-loop`: a fixed pool of callers, each
sending its next query when its last came back, against a served hybrid
state-space decoder whose users' histories are part of the traffic and
span a 512-fold range of lengths in ONE queue (short requests and long
ones in the same steps). Every child of the run (seeding, deploy, check)
has a time limit of its own (`limits_s` in the mix), so a run that would
overrun ends itself with a line that names the phase. Parameters:
benchmarks/traffic/<mix>.json."""

from lib import hybrid_ssm_serve


def run(ctx, cell):
    return hybrid_ssm_serve.run(ctx, cell)
