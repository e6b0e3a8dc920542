"""Reader `client-less-stats`: the client's mean of a window less the
server's own mean of the same requests: what a request spends outside
the server's handler (the connection, aiohttp's parsing, the wire).

args: {"client": harness key in ms, "num"/"den": /stats.json paths of
the server's sum and count, "scale": to ms}."""

from lib.evidence import stats_delta


def read(args, evidence):
    client = evidence["harness"].get(args["client"])
    server = stats_delta(evidence, args["num"], args["den"])
    if client is None or server is None:
        return None
    return client - server * args["scale"]
