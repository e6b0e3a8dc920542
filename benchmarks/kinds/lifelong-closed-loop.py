"""Traffic kind `lifelong-closed-loop`: a fixed pool of callers, each
sending its next query when its last came back, against a served
latent-attention mixture-of-experts decoder whose users' histories
(thousands of events; lengths, items) are part of the traffic. Every
child of the run (seeding, deploy, check) has a time limit of its own
(`limits_s` in the mix), so a run that would overrun ends itself with a
line that names the phase. Parameters: benchmarks/traffic/<mix>.json."""

from lib import latent_moe_serve


def run(ctx, cell):
    return latent_moe_serve.run(ctx, cell)
