"""Reader `harness`: a span or a figure the traffic kind took itself, on
the harness's or the benchmark engine's clock. args: {"key": name}."""


def read(args, evidence):
    return evidence["harness"].get(args["key"])
